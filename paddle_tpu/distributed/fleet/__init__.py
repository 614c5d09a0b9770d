"""fleet — hybrid-parallel facade.

Reference: python/paddle/distributed/fleet/__init__.py (fleet.init:167,
distributed_model fleet/model.py:32, distributed_optimizer fleet.py:1307,
DistributedStrategy fleet/base/distributed_strategy.py).
"""
from __future__ import annotations

from ..topology import HybridCommunicateGroup, _set_hcg, \
    get_hybrid_communicate_group
from .mp_layers import (  # noqa: F401
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding,
)
from .pipeline import (  # noqa: F401
    LayerDesc, PipelineLayer, PipelineParallel,
    PipelineParallelWithInterleave, SharedLayerDesc,
)
from .pipeline_compiled import CompiledPipelineParallel  # noqa: F401
from .recompute import recompute, recompute_sequential  # noqa: F401
from . import sequence_parallel_utils  # noqa: F401
from .sharding import DygraphShardingOptimizer, group_sharded_parallel  # noqa: F401
from . import metrics  # noqa: F401
from . import utils_fs  # noqa: F401
from .utils_fs import HDFSClient, LocalFS  # noqa: F401
from .meta_optimizers import (  # noqa: F401
    DGCMomentumOptimizer, LarsMomentumOptimizer, LocalSGDOptimizer,
)

__all__ = ["DistributedStrategy", "init", "distributed_model",
           "distributed_optimizer", "get_hybrid_communicate_group",
           "HybridParallelOptimizer", "HybridParallelClipGrad",
           "ColumnParallelLinear",
           "RowParallelLinear", "VocabParallelEmbedding",
           "ParallelCrossEntropy", "DygraphShardingOptimizer",
           "group_sharded_parallel"]


class DistributedStrategy:
    """Reference: fleet/base/distributed_strategy.py (proto-backed knobs).
    Holds the hybrid degrees + common toggles as plain attributes."""

    def __init__(self):
        self.hybrid_configs = {
            "dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
            "sharding_degree": 1, "sep_degree": 1,
        }
        self.amp = False
        self.amp_configs = {}
        self.recompute = False
        self.recompute_configs = {}
        self.sharding = False
        self.sharding_configs = {}
        self.pipeline = False
        self.pipeline_configs = {"accumulate_steps": 1,
                                 "micro_batch_size": 1}
        self.gradient_merge = False
        self.gradient_merge_configs = {}
        self.find_unused_parameters = False
        # communication-overlap engine (distributed/overlap.py): bucketed
        # async DP grad sync + quantized transport. Off by default; the
        # env twins are PADDLE_TPU_DP_OVERLAP / PADDLE_TPU_DP_QUANT.
        self.dp_comm_overlap = False
        self.dp_comm_quant = None          # None/"off" | "int8" | "bf16"
        self.comm_buffer_size = 25         # MB per grad bucket
        self.last_comm_buffer_size = 1     # MB cap on the final bucket


_fleet_initialized = False
_strategy: DistributedStrategy | None = None


def init(role_maker=None, is_collective=True, strategy=None, log_level="INFO"):
    """Reference: fleet/fleet.py:167 — builds the hybrid topology mesh."""
    global _fleet_initialized, _strategy
    from ..env import init_parallel_env
    init_parallel_env()
    _strategy = strategy or DistributedStrategy()
    hcg = HybridCommunicateGroup(strategy=_strategy)
    _set_hcg(hcg)
    _fleet_initialized = True
    return hcg


def is_initialized():
    return _fleet_initialized


def distributed_model(model):
    """Reference: fleet/model.py:32. With mp/pp the parallel layers already
    carry their shardings; pure-dp wraps in DataParallel, routing the
    strategy's comm-overlap knobs (buffer sizes, overlap toggle, quantized
    transport) into the bucket scheduler.

    Parameters no parallel layer placed (norms, the position table) were
    created on the default device; they are committed to the mesh,
    replicated, here. A staged step returns them in that layout anyway,
    and an input layout that changes after the first step costs a second
    compile of the whole program."""
    hcg = get_hybrid_communicate_group()
    from jax.sharding import NamedSharding, PartitionSpec
    from ..placement import place_global
    replicated = NamedSharding(hcg.mesh, PartitionSpec())
    for p in model.parameters():
        if not isinstance(p._data.sharding, NamedSharding):
            p._data = place_global(p._data, replicated)
    if hcg.get_model_parallel_world_size() == 1 and \
            hcg.get_pipe_parallel_world_size() == 1:
        from ..parallel import DataParallel
        s = _strategy
        kw = {}
        if s is not None:
            kw = dict(comm_buffer_size=s.comm_buffer_size,
                      last_comm_buffer_size=s.last_comm_buffer_size)
        return DataParallel(model, strategy=s,
                            group=hcg.get_data_parallel_group(), **kw)
    return model


class HybridParallelClipGrad:
    """Reference: dygraph_optimizer/hybrid_parallel_optimizer.py:44.

    The reference sums squared norms per rank and all-reduces across the
    mp/pp/sharding groups because each rank holds only its shard. On the
    single-controller mesh every parameter is a global (GSPMD-sharded)
    array, so the cross-group reduction collapses into one fused global
    norm — computed here in a single reduction over the whole parameter
    set, honouring per-param ``need_clip`` and counting TP-duplicated
    (replicated) parameters exactly once, which global arrays do by
    construction."""

    def __init__(self, clip, hcg=None):
        self._clip = clip
        self.clip_norm = getattr(clip, "clip_norm", None)
        self._hcg = hcg

    def __call__(self, params_grads):
        # one global norm over global arrays IS the cross-group norm —
        # delegate to the wrapped clip so the math lives in one place
        # (nn/clip.py ClipGradByGlobalNorm)
        return self._clip(params_grads)


class HybridParallelOptimizer:
    """Reference: dygraph_optimizer/hybrid_parallel_optimizer.py:254.
    Replaces an inner ClipGradByGlobalNorm with HybridParallelClipGrad
    (reference behavior) so the clip norm is the true global norm across
    every parallel group."""

    def __init__(self, optimizer, hcg=None, strategy=None):
        self._inner_opt = optimizer
        from ...nn.clip import ClipGradByGlobalNorm
        inner_clip = getattr(optimizer, "_grad_clip", None)
        if isinstance(inner_clip, ClipGradByGlobalNorm):
            optimizer._grad_clip = HybridParallelClipGrad(inner_clip, hcg)

    def __getattr__(self, name):
        return getattr(self._inner_opt, name)

    def step(self):
        self._inner_opt.step()

    def clear_grad(self, *a, **k):
        self._inner_opt.clear_grad(*a, **k)


def distributed_optimizer(optimizer, strategy=None):
    """Reference: fleet/fleet.py:1307."""
    hcg = get_hybrid_communicate_group()
    if _strategy is not None and _strategy.sharding:
        return DygraphShardingOptimizer(
            optimizer, group=hcg.get_sharding_parallel_group())
    return HybridParallelOptimizer(optimizer, hcg, strategy)
