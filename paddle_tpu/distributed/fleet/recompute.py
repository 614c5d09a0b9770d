"""Activation recomputation (gradient checkpointing).

Reference: python/paddle/distributed/fleet/recompute/recompute.py:108
(RecomputeFunction PyLayer + :404 recompute, with TP RNG-state replay).

TPU-native: the wrapped block is staged as a pure function of
(params..., activations...) and wrapped in ``jax.checkpoint`` — XLA's
rematerialization replaces the reference's hand-written save/replay PyLayer,
and composes with jit.to_static whole-step staging (the compiled program
recomputes the block in the backward pass, trading FLOPs for HBM — SURVEY §7
step 7). RNG replay is free: the block's dropout keys are folded from the
same traced key in forward and rematerialized backward.

Recomputing everything is the default and the reference's "full". A caller
that knows which of its intermediates are worth their bytes hands
``recompute`` a ``keep`` set: the block tags those tensors with
:func:`keep_name`, and ``jax.checkpoint`` runs under
``save_only_these_names(*keep)``, so the backward reads them instead of
running their producers (a matmul, and under tensor parallelism its
all-reduce) a second time. How much can be kept is a question of memory:
:func:`choose_keep` fills what :func:`device_free_bytes` reports, less the
caller's reserve for the step's own temporaries, in the caller's order of
worth. ``models/gpt.py`` is the caller.
"""
from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name

from ...core import random as _random
from ...core.dispatch import apply
from ...core.tensor import Tensor

__all__ = ["recompute", "recompute_sequential", "keep_name", "choose_keep",
           "device_free_bytes"]

_keeping = ()  # the keep set of the recompute call being traced


def keep_name(x, name):
    """Tag tensor ``x`` as ``name`` for the enclosing ``recompute`` call to
    keep for its backward. Outside such a call, or where its ``keep`` does
    not hold ``name``, ``x`` comes back as it is: no op is recorded."""
    if name not in _keeping:
        return x
    return apply("checkpoint_name", lambda a: checkpoint_name(a, name), [x])


def device_free_bytes():
    """What the fullest local device could still hold: its allocator's
    limit minus the bytes in use. None where the platform reports no
    memory (the CPU)."""
    free = None
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if not stats.get("bytes_limit"):
            return None
        left = int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
        free = left if free is None else min(free, left)
    return free


def choose_keep(free_bytes, name_bytes, num_layers):
    """Which tagged tensors each of ``num_layers`` equal blocks keeps.

    ``name_bytes`` maps a name to the bytes one block's tensor of that name
    takes on a device, in falling order of worth per byte. The budget is
    filled greedily in that order: the first name in every block, then the
    second, and it stops for good at the first tensor that no longer fits
    (so the last blocks are the first to fall back to recomputing a name).
    -> one tuple of names a block; all empty at a budget of 0 or less."""
    keep = [[] for _ in range(num_layers)]
    left = int(free_bytes)
    for name, nbytes in name_bytes.items():
        for block in keep:
            if nbytes > left:
                return [tuple(k) for k in keep]
            block.append(name)
            left -= nbytes
    return [tuple(k) for k in keep]


def recompute(function, *args, use_reentrant=True, preserve_rng_state=True,
              keep=(), **kwargs):
    """Reference: paddle.distributed.fleet.recompute (recompute.py:404).
    ``keep``: names (:func:`keep_name`) the backward keeps instead of
    recomputing; empty, the default, keeps nothing but the inputs."""
    global _keeping
    from ...nn import Layer

    if isinstance(function, Layer):
        layer = function
        fn = function.forward
    else:
        layer = getattr(function, "__self__", None)
        layer = layer if isinstance(layer, Layer) else None
        fn = function

    params = []
    if layer is not None:
        params = [p for p in layer.parameters() if p is not None]

    tensor_pos = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    tensor_args = [args[i] for i in tensor_pos]
    rng_key = _random.next_key() if preserve_rng_state else None
    out_meta = {}
    keep = tuple(keep)

    def pure(*arrs):
        p_arrs = arrs[:len(params)]
        a_arrs = arrs[len(params):]
        saved = [(p, p._data) for p in params]
        try:
            for p, a in zip(params, p_arrs):
                p._data = a
            call_args = list(args)
            for pos, a in zip(tensor_pos, a_arrs):
                call_args[pos] = Tensor(a, stop_gradient=True)
            if rng_key is not None:
                with _random.trace_key_scope(rng_key):
                    out = fn(*call_args, **kwargs)
            else:
                out = fn(*call_args, **kwargs)
            if isinstance(out, (tuple, list)):
                out_meta["n"] = len(out)
                return tuple(t._data for t in out)
            out_meta["n"] = 1
            return out._data
        finally:
            for p, a in saved:
                p._data = a

    if not keep:
        # dispatch.apply infers single-vs-tuple outputs from the traced result
        return apply("recompute", jax.checkpoint(pure), params + tensor_args)
    ck = jax.checkpoint(
        pure, policy=jax.checkpoint_policies.save_only_these_names(*keep))
    outer, _keeping = _keeping, keep   # the block is traced inside this call
    try:
        return _apply_with_pullback("recompute", ck, params + tensor_args)
    finally:
        _keeping = outer


def _apply_with_pullback(name, fwd, inputs):
    """``dispatch.apply`` for a forward whose pullback holds tensors of the
    forward: the pullback is taken now, with the outputs, and not at
    backward. The tape's deferred ``jax.vjp`` traces the forward a second
    time and leaves it to the compiler to merge the two; that is free
    while the second one is dead (full recompute keeps only inputs), but a
    kept tensor makes it live, and XLA does not merge two calls of a
    Pallas kernel."""
    from ...core import autograd, dispatch
    if dispatch._amp_enabled():
        inputs = dispatch._amp_cast(name, inputs)
    arrs = [t._data for t in inputs]
    diff_idx = [i for i, t in enumerate(inputs) if dispatch._is_diff(t)] \
        if autograd.is_grad_enabled() else []
    if not diff_idx:
        return apply(name, fwd, inputs)

    def f(*diff_arrs):
        merged = list(arrs)
        for pos, a in zip(diff_idx, diff_arrs):
            merged[pos] = a
        return fwd(*merged)

    out, vjp_fn = jax.vjp(f, *[arrs[i] for i in diff_idx])
    outs = [Tensor(o, stop_gradient=False)
            for o in (out if isinstance(out, tuple) else (out,))]
    autograd.record_op(name, [inputs[i] for i in diff_idx], vjp_fn, outs,
                       fwd=fwd, const_arrs=arrs, diff_idx=diff_idx)
    return outs[0] if len(outs) == 1 else tuple(outs)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """Reference: recompute.py:542 — checkpoint a Sequential in segments."""
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else 1
    layers = list(functions)
    seg_size = max(1, len(layers) // segments)
    out = args[0] if len(args) == 1 else args

    i = 0
    while i < len(layers):
        end = min(i + seg_size, len(layers))
        # parameters of the segment's layers must be lifted for remat
        from ...nn import Layer as _L

        class _Seg(_L):
            def __init__(self, sub):
                super().__init__()
                for j, s in enumerate(sub):
                    self.add_sublayer(str(j), s)

            def forward(self, x):
                for s in self._sub_layers.values():
                    x = s(x)
                return x

        out = recompute(_Seg(layers[i:end]), out)
        i = end
    return out
