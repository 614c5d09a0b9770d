"""paddle_tpu.jit — trace-to-XLA compilation (replaces dy2static/SOT/PIR/CINN).

Reference namespace: python/paddle/jit/__init__.py.
"""
from .api import (  # noqa: F401
    InputSpec, StaticFunction, ignore_module, not_to_static, to_static,
)
from .compile_cache import use_compile_cache  # noqa: F401
from .control_flow import (  # noqa: F401
    case, cond, scan_loop, switch_case, while_loop,
)
from .save_load import TranslatedLayer, load, save  # noqa: F401
