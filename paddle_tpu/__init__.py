"""paddle_tpu — a TPU-native deep learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas/pjit.

Public surface mirrors ``import paddle`` (reference: python/paddle/__init__.py):
tensors + ~200 ops, nn, optimizer, amp, autograd, io, jit, distributed, with
eager (dygraph) semantics over XLA and trace-to-HLO compilation replacing the
static-graph/PIR/CINN stack.
"""
from __future__ import annotations

__version__ = "0.1.0"

# ---- tpu-lint boot fast-path (ISSUE 12) ------------------------------------
# `python -m paddle_tpu.tools.analyze` must scan the tree WITHOUT importing
# jax: runpy imports this package before the analyzer's __main__ gets
# control, so the only place to skip framework init is here. The boot shape
# is detected from the interpreter command line (during parent-package
# import under `-m`, sys.argv[0] is still the '-m' placeholder and
# /proc/self/cmdline names the target module); anything else — including
# every other `-m` target — initializes normally. Hosts without procfs
# fall back to full init (the CLI still works, just not jax-free);
# PADDLE_TPU_LINT_BOOT=1 is the portable override.


def _tpu_lint_boot() -> bool:
    import os as _os
    import sys as _sys
    if _os.environ.get("PADDLE_TPU_LINT_BOOT") == "1":
        return True
    if not _sys.argv or _sys.argv[0] != "-m":
        return False
    try:
        with open("/proc/self/cmdline", "rb") as _f:
            _argv = _f.read().split(b"\0")
    except OSError:
        return False
    for _i, _tok in enumerate(_argv):
        if _tok == b"paddle_tpu.tools.analyze" and _i and _argv[_i - 1] == b"-m":
            return True
        if _tok == b"-mpaddle_tpu.tools.analyze":
            return True
    return False


_TPU_LINT_BOOT = _tpu_lint_boot()

if not _TPU_LINT_BOOT:
    # the entire framework surface assembles below; the tpu-lint boot leaves
    # paddle_tpu a stub package so paddle_tpu.tools.analyze imports jax-free

    import jax as _jax

    # Paddle's dtype surface includes int64/float64 as first-class (int64 is the
    # default index dtype); enable x64 so those dtypes exist. Perf-critical paths
    # use bf16/f32 explicitly, so TPU speed is unaffected.
    _jax.config.update("jax_enable_x64", True)

    from .core.autograd import enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled  # noqa: F401
    from .core.device import (  # noqa: F401
        CPUPlace, Place, TPUPlace, device_count, get_device, is_compiled_with_tpu,
        set_device,
    )
    from .core.dtype import (  # noqa: F401
        bfloat16, bool_ as bool8, complex64, complex128, float16, float32, float64,
        get_default_dtype, int8, int16, int32, int64, set_default_dtype, uint8,
    )
    from .core.random import get_rng_state, seed, set_rng_state  # noqa: F401
    from .core.tensor import Parameter, Tensor, to_tensor  # noqa: F401
    from .ops import *  # noqa: F401,F403
    from .ops import einsum, one_hot  # noqa: F401

    from . import amp  # noqa: F401
    from . import audio  # noqa: F401
    from . import autograd  # noqa: F401
    from . import fft  # noqa: F401
    from . import framework  # noqa: F401
    from . import inference  # noqa: F401
    from . import io  # noqa: F401
    from . import jit  # noqa: F401
    from . import linalg  # noqa: F401
    from . import nn  # noqa: F401
    from . import optimizer  # noqa: F401
    from . import regularizer  # noqa: F401
    from . import signal  # noqa: F401
    from . import utils  # noqa: F401
    from . import version  # noqa: F401
    from .version import full_version as __version__  # noqa: F401
    from . import distributed  # noqa: F401
    from . import distribution  # noqa: F401
    from . import hapi  # noqa: F401
    from . import observability  # noqa: F401
    from . import serving  # noqa: F401
    from . import metric  # noqa: F401
    from . import models  # noqa: F401
    from . import profiler  # noqa: F401
    from .hapi import Model  # noqa: F401
    from .hapi.summary import summary  # noqa: F401
    from .hapi.dynamic_flops import flops  # noqa: F401
    from .framework.io import load, save  # noqa: F401
    from .framework.param_attr import ParamAttr  # noqa: F401
    from .framework.dtype_info import (  # noqa: F401
        finfo, iinfo, is_complex, is_floating_point, is_integer,
    )
    from .framework.compat import (  # noqa: F401
        LazyGuard, batch, check_shape, create_parameter, get_cuda_rng_state,
        set_cuda_rng_state,
    )
    from . import geometric  # noqa: F401
    from . import hub  # noqa: F401

    # paddle aliases
    bool = bool8  # noqa: A001


    def disable_static(place=None):
        from .static.program import disable_static_mode
        disable_static_mode()
        return None


    def enable_static():
        """Reference: paddle.enable_static — switch to Program recording.
        Ops on paddle.static.data() variables append to the default main
        Program; Executor.run(feed/fetch) evaluates it (static/program.py)."""
        from .static.program import enable_static_mode
        enable_static_mode()


    def in_dynamic_mode():
        from .static.program import in_static_mode
        return not in_static_mode()


    def set_printoptions(precision=None, threshold=None, edgeitems=None,
                         sci_mode=None, linewidth=None):
        """Reference: paddle.set_printoptions — forwards to numpy's print
        options (Tensor repr renders through numpy)."""
        import numpy as _np
        kw = {}
        if precision is not None:
            kw["precision"] = precision
        if threshold is not None:
            kw["threshold"] = threshold
        if edgeitems is not None:
            kw["edgeitems"] = edgeitems
        if linewidth is not None:
            kw["linewidth"] = linewidth
        if sci_mode is not None:
            kw["suppress"] = not sci_mode
        _np.set_printoptions(**kw)


    def disable_signal_handler():
        """Reference parity no-op: the jax runtime installs no paddle-style
        signal handlers to disable."""
        return None


    def is_compiled_with_cuda():
        return False  # TPU-native build


    def is_compiled_with_xpu():
        return False


    def is_compiled_with_cinn():
        return False  # XLA plays CINN's role


    def is_compiled_with_rocm():
        return False


    def is_compiled_with_custom_device(device_type="tpu"):
        return device_type == "tpu"  # PjRt TPU is the device


    def is_grad_enabled_():
        return is_grad_enabled()


    from .framework.flags import get_flags, set_flags  # noqa: F401,E402
    from . import incubate  # noqa: F401,E402
    from . import vision  # noqa: F401,E402
    from . import static  # noqa: F401,E402
    from . import device  # noqa: F401,E402
    from . import text  # noqa: F401,E402
    from . import onnx  # noqa: F401,E402
    from . import quantization  # noqa: F401,E402
    from . import sparse  # noqa: F401,E402
    from . import strings  # noqa: F401,E402

    # bind the tensor methods that need the fully-assembled namespace
    from .core.tensor import Tensor as _T  # noqa: E402
    _T._late_bind()
    del _T

    # InferMeta preflights: paddle-style shape/dtype errors before XLA
    # (reference: phi/infermeta/*) — wraps the assembled namespaces, so last
    from .core import infermeta as _infermeta  # noqa: E402
    _infermeta.install()
    del _infermeta
