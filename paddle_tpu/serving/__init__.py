"""paddle_tpu.serving — continuous-batching inference over paged KV.

The serving tier (SURVEY layer 11; ROADMAP items 2+3): ONE ragged paged
attention launch per scheduler round (mixed decode rows + prefill chunks
over a paged KV cache), iteration-level scheduling between rounds,
streaming token callbacks, A/B-gated attention backends, and Poisson
open-loop load tooling for the bench.

    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(model, page_size=16, num_pages=128, max_slots=8)
    eng.start()
    req = eng.submit(prompt_ids, max_new_tokens=64,
                     on_token=lambda r, tok, fin: stream(tok))
    tokens = req.result(timeout=60)
"""
from .kv_cache import (  # noqa: F401
    BlockAllocator, LayerState, OutOfPages, PagedKVCache, kv_state, pages_for)
from .prefix_cache import PrefixCache  # noqa: F401
from .scheduler import (  # noqa: F401
    ContinuousBatchingScheduler, EngineClosed, EngineShuttingDown,
    GenerationRequest, OutOfSlots, QueueFull,
)
from .ragged_attention import (  # noqa: F401
    ab_compare_ragged, pad_total_tokens, ragged_paged_attention,
    resolve_backend, sharded_ragged_attention,
)
from .engine import ServingEngine  # noqa: F401
from .metrics import ServingMetrics  # noqa: F401
from .load import (  # noqa: F401
    make_mixed_length_prompts, make_session_prompts,
    make_shared_prefix_prompts, run_poisson_load, summarize_requests,
)
# the fleet tier (router / page sharing / disaggregation) lives in the
# .fleet subpackage — imported lazily by ServingEngine(page_share=) and
# explicitly by fleet users, so single-engine serving pays nothing

