"""Tensor-parallel (Megatron-style) layers.

Reference: python/paddle/distributed/fleet/layers/mpu/mp_layers.py
(VocabParallelEmbedding:47, ColumnParallelLinear:333, RowParallelLinear:540,
ParallelCrossEntropy:741) and mp_ops.py (_c_identity/_c_concat/_c_split/
_mp_allreduce autograd ops).

TPU-native: the layer owns the FULL logical weight committed with a
NamedSharding over the 'model' mesh axis; GSPMD partitions every op touching
it and inserts the identity/all-reduce/all-gather collectives the reference
writes by hand — including in the backward (the _c_identity-grad-is-allreduce
trick is exactly GSPMD's partial-sum handling). The same layers therefore
work eagerly, under jit.to_static, and inside the dryrun multi-chip mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core.dispatch import apply
from ...nn import Layer, functional as F
from ..topology import get_hybrid_communicate_group

__all__ = ["VocabParallelEmbedding", "ColumnParallelLinear",
           "RowParallelLinear", "ParallelCrossEntropy"]


def _mp_mesh(mp_group):
    if mp_group is not None:
        return mp_group.mesh, mp_group.axis
    hcg = get_hybrid_communicate_group()
    return hcg.mesh, "model"


def _place(t, mesh, spec):
    from ..placement import place_global
    t._data = place_global(t._data, NamedSharding(mesh, spec))
    return t


def _constrain(x, mesh, spec):
    """Sharding constraint as a taped op (works eager and under jit)."""
    return apply("sharding_constraint",
                 lambda a: jax.lax.with_sharding_constraint(
                     a, NamedSharding(mesh, spec)), [x])


_U = P.UNCONSTRAINED


def _last_dim_spec(ndim, axis):
    """Constrain only the last dim; leave the others to GSPMD (so dp/sep
    shardings on batch/seq dims survive the TP boundary)."""
    return P(*([_U] * (ndim - 1)), axis)


def _maybe_chunked(layer, kernel, x):
    """The latency-hiding decomposition for a TP matmul+collective pair
    (overlap engine, ROADMAP item 2): chunk the matmul along the free
    (sequence) dimension and interleave the per-chunk collectives so the
    wire hides under the next chunk's compute. Serving policy mirrors the
    Pallas demotion gate exactly — ``tp_overlap=None`` (auto) consults the
    measured :func:`~paddle_tpu.distributed.overlap.measure_tp_overlap`
    verdict at the EXACT shape and never serves off-TPU; ``True`` forces
    (tests/bench); ``False`` disables. Returns the chunked output, or
    None → caller takes the plain fused path."""
    mode = layer._tp_overlap
    if mode is False or x.ndim != 3:
        return None
    if mode is None:
        key = (tuple(x.shape), str(x._data.dtype))
        serve = layer._tp_overlap_cache.get(key)
        if serve is None:
            from ..overlap import tp_overlap_serves
            from ...ops.pallas._common import shape_sig
            serve = tp_overlap_serves(
                kernel, shape_sig(x._data, layer.weight._data))
            layer._tp_overlap_cache[key] = serve
        if not serve:
            return None
    from ..overlap import chunked_linear
    # both served pairs end replicated on the last dim (column
    # gather-output's all-gather, row's partial-sum all-reduce)
    return chunked_linear(x, layer.weight, layer.bias, layer._mesh,
                          out_axis=None)


class VocabParallelEmbedding(Layer):
    """Reference: mp_layers.py:47 — vocab dim sharded across the mp axis."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        mesh, axis = _mp_mesh(mp_group)
        self._mesh, self._axis = mesh, axis
        # same default initializer as nn.Embedding (reference
        # mp_layers.py:47 passes none either): a tensor-parallel model
        # draws the weights its single-device twin draws from one seed
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim], attr=weight_attr)
        _place(self.weight, mesh, P(axis, None))

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return _constrain(out, self._mesh,
                          _last_dim_spec(x.ndim + 1, None))


class ColumnParallelLinear(Layer):
    """Reference: mp_layers.py:333 — weight [in, out] sharded on out."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=None, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None, tp_overlap=None):
        super().__init__()
        mesh, axis = _mp_mesh(mp_group)
        self._mesh, self._axis = mesh, axis
        self._gather_output = gather_output
        self._tp_overlap = tp_overlap
        self._tp_overlap_cache = {}
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr)
        _place(self.weight, mesh, P(None, axis))
        has_bias = True if has_bias is None else has_bias
        if has_bias:
            self.bias = self.create_parameter(
                shape=[out_features], attr=None, is_bias=True)
            _place(self.bias, mesh, P(axis))
        else:
            self.bias = None

    def forward(self, x):
        if self._gather_output:
            # the matmul→all-gather pair is the latency-hiding candidate
            y = _maybe_chunked(self, "tp_overlap_column", x)
            if y is not None:
                return y
        y = F.linear(x, self.weight, self.bias)
        if self._gather_output:
            return _constrain(y, self._mesh, _last_dim_spec(y.ndim, None))
        # keep output sharded on the last dim (feeds RowParallelLinear)
        return _constrain(y, self._mesh, _last_dim_spec(y.ndim, self._axis))


class RowParallelLinear(Layer):
    """Reference: mp_layers.py:540 — weight [in, out] sharded on in; the
    matmul's contraction over the sharded dim yields partial sums that GSPMD
    all-reduces (the reference's explicit mp_allreduce)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False, fuse_matmul_bias=False,
                 mp_group=None, name=None, tp_overlap=None):
        super().__init__()
        mesh, axis = _mp_mesh(mp_group)
        self._mesh, self._axis = mesh, axis
        self._input_is_parallel = input_is_parallel
        self._tp_overlap = tp_overlap
        self._tp_overlap_cache = {}
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr)
        _place(self.weight, mesh, P(axis, None))
        if has_bias:
            self.bias = self.create_parameter(
                shape=[out_features], attr=None, is_bias=True)
            _place(self.bias, mesh, P(None))
        else:
            self.bias = None

    def forward(self, x):
        if not self._input_is_parallel:
            x = _constrain(x, self._mesh,
                           _last_dim_spec(x.ndim, self._axis))
        # the partial-sum matmul→all-reduce pair is the latency-hiding
        # candidate: each chunk's reduction rides under the next matmul
        y = _maybe_chunked(self, "tp_overlap_row", x)
        if y is not None:
            return y
        y = F.linear(x, self.weight, self.bias)
        return _constrain(y, self._mesh, _last_dim_spec(y.ndim, None))


class ParallelCrossEntropy(Layer):
    """Reference: mp_layers.py:741 — softmax cross entropy over class-dim-
    sharded logits; the log-sum-exp reduction over the sharded axis compiles
    to an all-reduce."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        mesh, axis = _mp_mesh(mp_group)
        self._mesh, self._axis = mesh, axis
        self._ignore_index = ignore_index

    def forward(self, input, label):
        logits = _constrain(input, self._mesh,
                            _last_dim_spec(input.ndim, self._axis))
        loss = F.cross_entropy(logits, label, reduction="none",
                               ignore_index=self._ignore_index)
        return loss
