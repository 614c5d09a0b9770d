"""jit.to_static — trace-to-XLA compilation.

Reference: python/paddle/jit/api.py:171 (to_static). The reference lowers
dygraph python to ProgramDesc/PIR via AST rewriting + SOT bytecode
interception, then executes with the static executor and optionally CINN.
TPU-native collapse (SURVEY §7 step 4): eager Tensors transparently hold jax
tracers, so to_static simply re-runs the python function under jax.jit —
parameters/buffers are lifted to traced inputs, the op tape records pullbacks
on tracers, and XLA compiles the whole graph (this one mechanism replaces
dy2static, SOT, PIR, CINN and the new executor).

Two modes:
- forward staging (default): the compiled function participates in the eager
  autograd tape (its pullback is the compiled VJP), so ``loss.backward()``
  outside still works — matching reference to_static training semantics.
- whole-step staging (``capture=(model, optimizer)``): the function may call
  ``backward()`` + ``optimizer.step()`` inside; parameters, buffers and
  optimizer accumulators become donated inputs/outputs of one XLA program —
  the max-performance train-step path (no reference analog; CINN whole-graph
  fusion is the closest).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _Annotation

from ..core import random as _random
from ..core.dispatch import apply
from ..core.dtype import convert_dtype
from ..core.tensor import Tensor
from ..observability import tracing as _trc

__all__ = ["to_static", "not_to_static", "InputSpec", "StaticFunction",
           "ignore_module"]


class _EagerFallback(Exception):
    """Internal: this input signature graph-broke before — skip tracing."""


def _aval(a):
    """Abstract value for compiled_text()/aot avals. Mesh shardings matter
    for SPMD lowering; single-device placements are left off (committed
    single-device avals would conflict with mesh-sharded peers at lower()
    time)."""
    sh = getattr(a, "sharding", None)
    if sh is not None and hasattr(sh, "mesh"):
        try:
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
        except Exception:
            pass
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


_STRUCT_VERSION = None


def _struct_version():
    """The nn.Layer structural version counter (lazy: jit.api must not
    import nn at module load)."""
    global _STRUCT_VERSION
    if _STRUCT_VERSION is None:
        from ..nn.layer.layers import STRUCT_VERSION
        _STRUCT_VERSION = STRUCT_VERSION
    return _STRUCT_VERSION


_IDLE_SPEC = None


def _idle_rng_spec():
    """Shared RNG spec for steps whose trace consumed no randomness: the
    global generator is neither advanced nor touched, and no per-step
    host→device constant is created."""
    global _IDLE_SPEC
    if _IDLE_SPEC is None:
        _IDLE_SPEC = np.zeros(3, np.uint32)
    return _IDLE_SPEC


class _break_key_scope:
    """Tags exceptions escaping a trace/execute region with the cache key
    they broke under, so __call__ blacklists the right signature even when
    nested calls of the same StaticFunction are in flight."""

    def __init__(self, key):
        self._key = key

    def __enter__(self):
        return self

    def __exit__(self, etype, e, tb):
        if e is not None and not isinstance(e, _EagerFallback) \
                and not hasattr(e, "_pd_break_key"):
            try:
                e._pd_break_key = self._key
            except AttributeError:
                pass
        return False


def _is_graph_break(e):
    """True when the exception means 'this python cannot be staged' (not
    'the user program is wrong'): our converter's explicit GraphBreak plus
    jax's trace-time concretization family (data-dependent bool/int/array
    use of a tracer, leaked tracers from side-effecty code). The dispatch
    layer wraps jax errors with op context (`raise ... from e`), so walk
    the cause chain. StaticFunction degrades to eager on these — the SOT
    graph-break analog."""
    from .dy2static import GraphBreak
    kinds = (GraphBreak, jax.errors.ConcretizationTypeError,
             jax.errors.TracerArrayConversionError,
             jax.errors.TracerIntegerConversionError,
             jax.errors.UnexpectedTracerError)
    seen = 0
    while e is not None and seen < 10:
        if isinstance(e, kinds):
            return True
        e = e.__cause__
        seen += 1
    return False


class InputSpec:
    """Reference: paddle.static.InputSpec — shape may contain None for
    dynamic dims (compiled polymorphically via jax.export symbolic shapes
    where supported; concrete shapes otherwise)."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape)
        self.dtype = convert_dtype(dtype) or jnp.float32
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def _tree_flatten(obj, tensors, rebuild_path):
    """Flatten nested args: collect Tensors, return a skeleton rebuilder key."""
    if isinstance(obj, Tensor):
        tensors.append(obj)
        return ("T", len(tensors) - 1)
    if isinstance(obj, (list, tuple)):
        return (type(obj).__name__,
                tuple(_tree_flatten(o, tensors, rebuild_path) for o in obj))
    if isinstance(obj, dict):
        # flatten in sorted-key order so tensor indices are insertion-order
        # independent (two dicts with equal keys flatten identically)
        return ("dict", tuple(
            (k, _tree_flatten(obj[k], tensors, rebuild_path))
            for k in sorted(obj)))
    return ("C", obj)  # static constant (part of cache key)


def _tree_rebuild(skel, arrays, wrap):
    kind = skel[0]
    if kind == "T":
        return wrap(arrays[skel[1]])
    if kind in ("list", "tuple"):
        seq = [_tree_rebuild(s, arrays, wrap) for s in skel[1]]
        return seq if kind == "list" else tuple(seq)
    if kind == "dict":
        return {k: _tree_rebuild(v, arrays, wrap) for k, v in skel[1]}
    return skel[1]


def _amp_key():
    """AMP autocast decisions are baked in at trace time, so the compile
    cache must be keyed on the active auto_cast state (ADVICE r2) —
    including custom white/black op lists, which also steer amp_dtype_for.
    The frozenset construction is cached per state identity (auto_cast
    replaces, never mutates, the white/black sets) so the per-step key
    probe is a tuple build, not two set copies."""
    from ..amp.auto_cast import amp_key_cached
    return amp_key_cached()


def _static_key(skel, tensors, extra):
    shapes = tuple((tuple(t.shape), str(t.dtype)) for t in tensors)

    def hashable(s):
        kind = s[0]
        if kind == "C":
            try:
                hash(s[1])
                return s
            except TypeError:
                return ("C", repr(s[1]))
        if kind in ("list", "tuple", "dict"):
            return (kind, tuple(hashable(x) if not isinstance(x, str) else x
                                for x in s[1]))
        return s
    return (hashable(skel), shapes, extra)


def _convert_fn(fn):
    """Dy2static AST pass: tensor-dependent python if/while/for(range)
    lower onto lax control flow (reference: program_translator.py:773);
    bound methods are converted on __func__ and re-bound."""
    import inspect
    import types

    from .dy2static import convert_to_static
    if inspect.ismethod(fn):
        conv = convert_to_static(fn.__func__)
        if conv is not fn.__func__:
            return types.MethodType(conv, fn.__self__)
        return fn
    return convert_to_static(fn)


class StaticFunction:
    """Callable wrapper produced by to_static (reference:
    jit/dy2static/program_translator.py ASTStaticFunction analog)."""

    def __init__(self, function, input_spec=None, capture=None,
                 build_strategy=None, backend=None, full_graph=False,
                 donate_state=True, convert_control_flow=True):
        from ..nn import Layer
        self._raw_fn = function
        self._input_spec = input_spec
        self._capture = list(capture) if capture is not None else None
        self._donate_state = donate_state
        self._full_graph = full_graph
        self._broken_keys = set()  # input signatures that graph-broke
        self._cache = {}
        self._state_cache = None   # cached _state() walk (invalidate())
        self._fast_step = {}       # steady-state whole-step dispatch memo
        self._steps_run = 0        # whole steps executed (``train_step``)
        self._layer = None
        if isinstance(function, Layer):
            self._layer = function
            self._fn = function.forward
        else:
            self._fn = function
            owner = getattr(function, "__self__", None)
            if isinstance(owner, Layer):
                self._layer = owner
        if convert_control_flow:
            self._fn = _convert_fn(self._fn)

    # -- state discovery --
    def invalidate(self):
        """Drop the cached state walk + fast-dispatch memo. Call after a
        structural change to a captured module (adding/removing sublayers
        or parameters, re-materializing optimizer state) — the staged step
        otherwise keeps using the parameter set discovered at first call."""
        self._state_cache = None
        self._fast_step = {}

    def _state_cached(self):
        """The `_state()` walk (a full recursive parameters()/buffers()
        traversal of every captured layer) costs O(model size) python per
        call — caching it is a large slice of the per-step host-overhead
        win. The cache is guarded by the process-wide Layer structural
        version: any parameter/sublayer/buffer registration anywhere bumps
        it, forcing a re-walk (and, if the captured module really changed,
        a re-key + retrace) — :meth:`invalidate` remains for exotic edits
        the registration hooks cannot see (direct `_parameters` dict
        mutation)."""
        st = getattr(self, "_state_cache", None)
        if st is not None and _struct_version()[0] != self._state_version:
            # a Layer somewhere gained/lost a param/sublayer/buffer since
            # the walk — stale state must never reach the forward path OR
            # the whole-step slow path, not just the fast memo
            self.invalidate()
            st = None
        if st is None:
            st = self._state()
            self._state_cache = st
            self._state_version = _struct_version()[0]
        return st

    def _state(self):
        """(diff_params, buffers, opt_slots): every mutable tensor/array the
        traced function can read or write."""
        from ..nn import Layer
        from ..optimizer import Optimizer
        layers, opts = [], []
        if self._layer is not None:
            layers.append(self._layer)
        for item in self._capture or []:
            if isinstance(item, Layer):
                layers.append(item)
            elif isinstance(item, Optimizer):
                opts.append(item)
            elif isinstance(getattr(item, "_inner", None), Optimizer):
                opts.append(item._inner)  # sharding/hybrid wrappers
            elif isinstance(getattr(item, "_inner_opt", None), Optimizer):
                opts.append(item._inner_opt)
            else:
                raise TypeError(
                    f"capture item {type(item).__name__} is neither a Layer "
                    "nor an Optimizer (or optimizer wrapper); its state "
                    "cannot be staged")
        params, buffers = [], []
        seen = set()
        for layer in layers:
            for p in layer.parameters():
                if id(p) not in seen:
                    seen.add(id(p))
                    params.append(p)
            for b in layer.buffers():
                if b is not None and id(b) not in seen:
                    seen.add(id(b))
                    buffers.append(b)
        slots = []
        for opt in opts:
            slots.extend(opt._state_slots())
        # grad-sync schedulers (overlap engine) can carry cross-step device
        # state of their own: the quantized transports' per-bucket error-
        # feedback residuals. Any attached scheduler watching this step's
        # parameters exposes the same _state_slots protocol as an
        # optimizer — staging the residuals lets the quantized DP path
        # serve inside the compiled step instead of falling back to the
        # exact psum (ROADMAP item 2c).
        from ..core.autograd import _grad_sync_hooks
        pids = {id(p) for p in params}
        for ref in list(_grad_sync_hooks):
            hook = ref()
            if hook is None or not hasattr(hook, "_state_slots"):
                continue
            if pids & set(hook.param_ids()):
                slots.extend(hook._state_slots())
        return params, buffers, slots, layers, opts

    def __call__(self, *args, **kwargs):
        try:
            if self._capture is not None:
                from ..distributed import watchdog as _watchdog
                _watchdog.beat()  # collective-hang watchdog (if armed)
                return self._call_whole_step(args, kwargs)
            return self._call_forward(args, kwargs)
        except _EagerFallback:
            return self._eager_fallback(args, kwargs)
        except Exception as e:
            if self._full_graph or not _is_graph_break(e):
                raise
            import warnings
            first_line = (str(e).splitlines() or [""])[0]
            warnings.warn(
                f"to_static: falling back to eager for "
                f"{getattr(self._raw_fn, '__name__', self._raw_fn)} — "
                f"{type(e).__name__}: {first_line[:200]} "
                "(graph-break fallback; pass full_graph=True to make this "
                "an error)", stacklevel=2)
            # cache the break per input signature (SOT's guarded-subgraph
            # analog): this call pattern skips tracing from now on, while
            # other shapes/paths that staged fine keep their compiled
            # entry. The key rides on the exception (not instance state) so
            # nested calls of the same StaticFunction can't clobber it.
            key = getattr(e, "_pd_break_key", None)
            if key is not None:
                self._broken_keys.add(key)
                self._cache.pop(key, None)  # entry never executed compiled
            return self._eager_fallback(args, kwargs)

    def _eager_fallback(self, args, kwargs):
        """SOT-analog graph break (reference jit/sot/translate.py:31): the
        region that refused to stage runs eagerly. The converted fn keeps
        exact python semantics for concrete predicates, so correctness is
        unchanged — only staging is lost. Caveat (inherent to trace-then-
        rerun, unlike SOT's pre-execution bytecode split): the breaking
        call runs the function's python twice, so side effects before the
        break repeat; tracked params/buffers are restored by the trace's
        ``finally`` so tensor state is safe."""
        if self._capture is not None:
            from ..distributed import watchdog as _watchdog
            _watchdog.beat()
        return self._fn(*args, **kwargs)

    # -- mode 1: compiled forward on the eager tape --
    def _call_forward(self, args, kwargs):
        params, buffers, _, layers, _ = self._state_cached()
        arg_tensors: list = []
        skel = _tree_flatten((args, tuple(sorted(kwargs.items()))),
                             arg_tensors, [])
        training = tuple(layer.training for layer in layers)
        key_extra = ("fwd", len(params), len(buffers), training,
                     _amp_key())
        cache_key = _static_key(skel, params + buffers + arg_tensors,
                                key_extra)
        if cache_key in self._broken_keys:
            raise _EagerFallback
        with _break_key_scope(cache_key):
            entry = self._cache.get(cache_key)
            if entry is None:
                entry = self._build_forward(skel, params, buffers,
                                            len(arg_tensors))
                self._cache[cache_key] = entry
            jitted, n_buf, meta = entry
            if meta.get("uses_rng", True):
                rng_key = _random.next_key_spec()
            else:
                rng_key = _idle_rng_spec()

            ins = params + arg_tensors
            if n_buf:
                out = apply("to_static", lambda *arrs: jitted(
                    arrs[:len(params)],
                    [b._data for b in buffers],
                    arrs[len(params):], rng_key), ins, has_aux=True)
                out = list(out) if isinstance(out, tuple) else [out]
                # trailing aux outputs are the updated buffer values
                new_bufs = out[-n_buf:]
                outputs = out[:-n_buf]
                for b, nb in zip(buffers, new_bufs):
                    b._data = nb._data
                return _tree_rebuild(meta["out_skel"], outputs, lambda t: t)
            out = apply("to_static", lambda *arrs: jitted(
                arrs[:len(params)], [], arrs[len(params):], rng_key), ins)
            outputs = list(out) if isinstance(out, tuple) else [out]
            return _tree_rebuild(meta["out_skel"], outputs, lambda t: t)

    def _build_forward(self, skel, params, buffers, n_args):
        fn = self._fn
        _trc.listen_compiles()      # the compile log, from the first build
        meta = {}  # per-cache-entry output skeleton (set during trace)

        def pure(param_arrs, buf_arrs, arg_arrs, rng_spec):
            rng_key = _random.derive_key(rng_spec)
            saved = [(t, t._data) for t in params + buffers]
            saved_grads = [(t, t._grad) for t in params]
            try:
                for t, a in zip(params, param_arrs):
                    t._data = a
                for t, a in zip(buffers, buf_arrs):
                    t._data = a
                rebuilt_args, kw_items = _tree_rebuild(
                    skel, list(arg_arrs),
                    lambda a: Tensor(a, stop_gradient=True))
                with _random.trace_key_scope(rng_key):
                    out = fn(*rebuilt_args, **dict(kw_items))
                    meta["uses_rng"] = _random._trace_rng.counter > 0
                out_tensors: list = []
                meta["out_skel"] = _tree_flatten(out, out_tensors, [])
                out_arrs = tuple(t._data for t in out_tensors)
                new_bufs = tuple(b._data for b in buffers)
            finally:
                for t, a in saved:
                    t._data = a
                for t, g in saved_grads:
                    t._grad = g
            if buffers:
                return out_arrs, list(new_bufs)
            return out_arrs if len(out_arrs) > 1 else out_arrs[0]

        # NOTE: jax.jit caching keys on shapes; our cache keys on structure.
        return jax.jit(pure, static_argnums=()), len(buffers), meta

    # -- mode 2: whole train step (fwd+bwd+update) in one XLA program --
    def _fast_sig(self, args, kwargs, layers):
        """Cheap dispatch signature for the steady-state re-call: engages
        only for the plain ``step(x, y, ...)`` calling convention (bare
        Tensor positionals, no kwargs). Params/buffers/slots shapes are
        covered by the full key once at entry build and assumed stable
        thereafter (see :meth:`invalidate`)."""
        if kwargs:
            return None
        sig = []
        for a in args:
            if isinstance(a, Tensor):
                d = a._data
                sig.append((d.shape, d.dtype))
            else:
                return None
        return (tuple(sig), tuple(layer.training for layer in layers),
                _amp_key())

    def _call_whole_step(self, args, kwargs):
        fast = getattr(self, "_fast_step", None)
        st = getattr(self, "_state_cache", None)
        if fast and st is not None:
            if _struct_version()[0] != self._state_version:
                # some Layer somewhere gained/lost a param/sublayer since
                # the state walk: re-discover. If the captured module is
                # unchanged this re-memoizes without retracing.
                self.invalidate()
            else:
                sig = self._fast_sig(args, kwargs, st[3])
                hit = fast.get(sig) if sig is not None else None
                if hit is not None:
                    return self._exec_whole_step(hit, list(args), st)
        params, buffers, slots, layers, opts = self._state_cached()
        if not getattr(self, "_materialized", False):
            # accumulators are created lazily — materialize each optimizer's
            # state up front so the whole step stages without an eager warmup
            for opt in opts:
                if not opt._state_slots():
                    opt.materialize()
            self._materialized = True
            self._state_cache = None
            params, buffers, slots, layers, opts = self._state_cached()
        arg_tensors: list = []
        skel = _tree_flatten((args, tuple(sorted(kwargs.items()))),
                             arg_tensors, [])
        training = tuple(layer.training for layer in layers)
        # lr is a traced input (scalar array), so it is NOT part of the key
        key_extra = ("step", len(params), len(buffers), len(slots),
                     training, _amp_key())
        cache_key = _static_key(skel, params + buffers + arg_tensors,
                                key_extra)
        if cache_key in self._broken_keys:
            raise _EagerFallback
        entry = self._cache.get(cache_key)
        if entry is None:
            entry = self._build_whole_step(skel, params, buffers, slots,
                                           opts, len(arg_tensors))
            self._cache[cache_key] = entry
        with _break_key_scope(cache_key):  # tracing happens at this call
            out = self._exec_whole_step(entry, arg_tensors,
                                        (params, buffers, slots, layers,
                                         opts))
        # memoize AFTER a successful compiled execution so a graph-broken
        # signature can never land in the fast memo
        sig = self._fast_sig(args, kwargs, layers)
        if sig is not None:
            self._fast_step[sig] = entry
        return out

    def _exec_whole_step(self, entry, arg_tensors, state):
        """Steady-state step dispatch: build the state list, call the ONE
        compiled program, write results back. Zero eager device ops on the
        host side — the RNG key is derived in-program from a numpy spec
        (:func:`core.random.next_key_spec`) only when the traced step
        actually consumed randomness, and the learning rates ride a numpy
        array straight into the pjit call."""
        jitted, meta = entry
        params, buffers, slots, layers, opts = state
        # the step's ONE tracing gate: the buffer is on, or a profile is
        # being taken (the benchmark's training runner starts the profiler
        # and not the buffer; the phases then go to the annotation alone).
        # On: ``train_step`` and its three phases
        # (observability/tracing.py lists them)
        tr = _trc._TR if _trc._loaded else _trc._load()
        stp = ph = None
        if tr is not None or _Annotation.is_enabled():
            stp = _trc.phase(tr, "train_step", cat="step",
                             step=self._steps_run).open()
            ph = stp.inner("step.gather")
        self._steps_run += 1
        if meta.get("uses_rng", True):
            rng_spec = _random.next_key_spec()
        else:
            rng_spec = _idle_rng_spec()
        # tpu-lint: ok[HS002] operands are python floats — host numpy rides into pjit with no device fetch (PR 7 zero-eager-op design)
        lrs = np.asarray([opt.get_lr() for opt in opts], np.float32)
        state_in = [t._data for t in params] + [b._data for b in buffers] + \
            [cont[k] for cont, k in slots]
        last = getattr(self, "_last_exec", None)
        if last is None or last[0] is not jitted:
            self._last_exec = (jitted, ([_aval(a) for a in state_in],
                                        [_aval(t._data) for t in
                                         arg_tensors],
                                        jax.ShapeDtypeStruct(
                                            (3,), jnp.uint32),
                                        jax.ShapeDtypeStruct(
                                            lrs.shape, jnp.float32)))
        if ph is not None:
            ph = ph.then("step.launch")
        out_arrs, new_state = jitted(state_in,
                                     [t._data for t in arg_tensors],
                                     rng_spec, lrs)
        if ph is not None:
            ph = ph.then("step.rebind")
        if meta.get("unstaged_accumulators"):
            raise RuntimeError(
                "optimizer state was created during tracing and cannot be "
                f"staged: {sorted(meta['unstaged_accumulators'])}. Implement "
                "_materialize_param on the optimizer (see "
                "paddle_tpu/optimizer/optimizers.py) so its accumulators "
                "exist before compilation.")
        n_p, n_b = len(params), len(buffers)
        for t, a in zip(params, new_state[:n_p]):
            t._data = a
            t._grad = None
        for b, a in zip(buffers, new_state[n_p:n_p + n_b]):
            b._data = a
        for (cont, k), a in zip(slots, new_state[n_p + n_b:]):
            cont[k] = a
        out = _tree_rebuild(meta["out_skel"], [
            Tensor(a, stop_gradient=True) for a in out_arrs], lambda t: t)
        if stp is not None:
            stp.close(ph.close())
        return out

    def _build_whole_step(self, skel, params, buffers, slots, opts, n_args):
        fn = self._fn
        _trc.listen_compiles()      # the compile log, from the first build
        meta = {}  # per-cache-entry output skeleton (set during trace)

        def pure(state_arrs, arg_arrs, rng_spec, lrs):
            # the step key is derived IN-program from the uint32
            # [seed_hi, seed_lo, counter] spec — bit-identical to the
            # eager next_key(), but zero eager device ops per step
            rng_key = _random.derive_key(rng_spec)
            n_p, n_b = len(params), len(buffers)
            saved = [(t, t._data, t._grad) for t in params] + \
                [(b, b._data, None) for b in buffers]
            saved_slots = [(cont, k, cont[k]) for cont, k in slots]
            # snapshot accumulator keys so entries created DURING tracing
            # (e.g. an optimizer without _materialize_param) can be purged —
            # they would otherwise leak tracers into eager state
            acc_keys_before = [
                (opt, name, frozenset(per))
                for opt in opts
                for name, per in opt._accumulators.items()]
            try:
                for t, a in zip(params, state_arrs[:n_p]):
                    t._data = a
                    t._grad = None
                for b, a in zip(buffers, state_arrs[n_p:n_p + n_b]):
                    b._data = a
                for (cont, k), a in zip(slots, state_arrs[n_p + n_b:]):
                    cont[k] = a
                for i, opt in enumerate(opts):
                    opt._lr_override = lrs[i]
                rebuilt_args, kw_items = _tree_rebuild(
                    skel, list(arg_arrs),
                    lambda a: Tensor(a, stop_gradient=True))
                with _random.trace_key_scope(rng_key):
                    out = fn(*rebuilt_args, **dict(kw_items))
                    # consumed trace keys mean the step needs a FRESH spec
                    # per call; otherwise dispatch reuses one idle spec
                    meta["uses_rng"] = _random._trace_rng.counter > 0
                out_tensors: list = []
                meta["out_skel"] = _tree_flatten(out, out_tensors, [])
                out_arrs = tuple(t._data for t in out_tensors)
                new_state = [t._data for t in params] + \
                    [b._data for b in buffers] + \
                    [cont[k] for cont, k in slots]
            finally:
                for t, a, g in saved:
                    t._data = a
                    t._grad = g
                for cont, k, v in saved_slots:
                    cont[k] = v
                for opt in opts:
                    opt._lr_override = None
                    for name, per in list(opt._accumulators.items()):
                        before = next(
                            (ks for o, n, ks in acc_keys_before
                             if o is opt and n == name), frozenset())
                        for k in list(per):
                            if k not in before:
                                del per[k]  # purge tracer created in trace
                                meta.setdefault("unstaged_accumulators",
                                                set()).add(
                                    (type(opt).__name__, name))
            return out_arrs, new_state

        donate = (0,) if self._donate_state else ()
        return jax.jit(pure, donate_argnums=donate), meta

    def aot_compile(self, *args, **kwargs):
        """Trace + XLA-compile the whole-step program for these example
        inputs WITHOUT executing it. Returns the jax Compiled object —
        ``.memory_analysis()`` gives per-device argument/temp/output bytes,
        so an N-billion-param config's HBM footprint is checkable on a
        virtual CPU mesh before any chip time (reference capability:
        memory estimation tools, auto_parallel cost model memory pass)."""
        if self._capture is None:
            raise RuntimeError("aot_compile requires whole-step staging "
                               "(capture=(model, optimizer))")
        params, buffers, slots, layers, opts = self._state()
        if not getattr(self, "_materialized", False):
            for opt in opts:
                if not opt._state_slots():
                    opt.materialize()
            self._materialized = True
            params, buffers, slots, layers, opts = self._state()
        arg_tensors: list = []
        skel = _tree_flatten((args, tuple(sorted(kwargs.items()))),
                             arg_tensors, [])
        jitted, meta = self._build_whole_step(skel, params, buffers, slots,
                                              opts, len(arg_tensors))

        state_avals = [_aval(t._data) for t in params] + \
            [_aval(b._data) for b in buffers] + \
            [_aval(cont[k]) for cont, k in slots]
        arg_avals = [_aval(t._data) for t in arg_tensors]
        rng_aval = jax.ShapeDtypeStruct((3,), jnp.uint32)
        lrs_aval = jax.ShapeDtypeStruct((max(len(opts), 1),), jnp.float32)
        return jitted.lower(state_avals, arg_avals, rng_aval,
                            lrs_aval).compile()

    def compiled_text(self):
        """Optimized-HLO text of the most recent whole-step call. Lets tests
        assert on the collectives GSPMD actually inserted (reduce-scatter
        for ZeRO-2 grads, all-gather-on-use for ZeRO-3 params, no weight
        all-gather under TP) instead of trusting the sharding annotations."""
        if not hasattr(self, "_last_exec"):
            raise RuntimeError(
                "call the to_static function once before compiled_text()")
        jitted, args = self._last_exec
        return jitted.lower(*args).compile().as_text()

    @property
    def code(self):
        import inspect
        try:
            return inspect.getsource(self._fn)
        except OSError:
            return "<source unavailable>"

    def concrete_program(self):  # reference-API stub for introspection
        return self._cache


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, capture=None, **kwargs):
    """Reference: python/paddle/jit/api.py:171 (paddle.jit.to_static).

    ``full_graph=False`` (default, reference SOT semantics) falls back to
    eager with a warning when tracing hits an unstageable construct;
    ``full_graph=True`` makes that a hard error.

    ``capture=(model, optimizer, ...)`` enables whole-train-step staging —
    see module docstring."""
    def decorate(fn):
        from ..nn import Layer
        if isinstance(fn, Layer):
            static = StaticFunction(fn, input_spec, capture,
                                    full_graph=full_graph)
            fn.forward = static
            return fn
        return StaticFunction(fn, input_spec, capture,
                              full_graph=full_graph)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    return None
