"""paddle_tpu.Tensor — eager tensor over jax.Array with dygraph autograd semantics.

Reference equivalents: public ``paddle::Tensor`` (paddle/phi/api/include/tensor.h:82),
eager AutogradMeta/hooks (paddle/fluid/eager/autograd_meta.h), python method patches
(python/paddle/base/dygraph/tensor_patch_methods.py). The tensor transparently holds
either a concrete ``jax.Array`` or a JAX tracer, so the same eager code path can be
staged under ``jax.jit`` (this replaces dy2static/SOT for the compile story).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from . import dispatch
from .dtype import convert_dtype, get_default_dtype, is_floating

_tensor_counter = [0]
_ops_mod = None  # paddle_tpu.ops, resolved once by _binop (circular import)


class Tensor:
    __slots__ = ("_data", "_grad", "_grad_fn", "_output_index", "_grad_hooks",
                 "stop_gradient", "name", "persistable", "is_leaf_", "__weakref__",
                 "trainable", "_pp_meta")

    def __init__(self, data, dtype=None, stop_gradient: bool = True, name=None):
        dtype = convert_dtype(dtype)
        if isinstance(data, Tensor):
            data = data._data
        if isinstance(data, (jax.Array,)) or _is_tracer(data):
            self._data = data if dtype is None else data.astype(dtype)
        else:
            arr = np.asarray(data)
            if dtype is None:
                if arr.dtype == np.float64:
                    dtype = get_default_dtype()
                elif arr.dtype == np.int64 and arr.size and np.all(
                        np.abs(arr) < 2**31):
                    dtype = jnp.dtype("int64")  # keep paddle's int64 default
            self._data = jnp.asarray(arr, dtype=dtype)
        self._grad = None
        self._grad_fn = None
        self._output_index = 0
        self._grad_hooks = []
        self.stop_gradient = stop_gradient
        if name is None:
            _tensor_counter[0] += 1
            name = f"generated_tensor_{_tensor_counter[0]}"
        self.name = name
        self.persistable = False
        self.trainable = not stop_gradient

    # ---- metadata ----
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def place(self):
        from .device import get_place
        return get_place()

    @property
    def is_leaf(self):
        return self._grad_fn is None

    def numel(self):
        return Tensor(jnp.asarray(self.size), stop_gradient=True)

    def element_size(self):
        return self._data.dtype.itemsize

    # ---- value access ----
    def numpy(self) -> np.ndarray:
        if dispatch._nan_pending:
            # a widened FLAGS_check_nan_inf_window defers the NaN flag
            # fetch; a host read is a sync point anyway, so surface the
            # pending error here instead of dropping it in forward-only
            # runs that never reach backward()
            dispatch.flush_nan_checks()
        return np.asarray(self._data)

    def item(self):
        if dispatch._nan_pending:
            dispatch.flush_nan_checks()
        return self._data.item()

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __float__(self):
        return float(self._data)

    def __int__(self):
        return int(self._data)

    def __bool__(self):
        return bool(self._data)

    def __index__(self):
        return int(self._data)

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype}"
                f"{grad_info},\n       {self._data})")

    def __hash__(self):
        return id(self)

    # ---- autograd ----
    @property
    def grad(self) -> Optional["Tensor"]:
        if self._grad is None:
            return None
        return Tensor(self._grad, stop_gradient=True)

    @grad.setter
    def grad(self, value):
        self._grad = None if value is None else (
            value._data if isinstance(value, Tensor) else jnp.asarray(value))

    def _accumulate_grad(self, g):
        self._grad = g if self._grad is None else self._grad + g

    def backward(self, grad_tensor: Optional["Tensor"] = None,
                 retain_graph: bool = False):
        """Reference: tensor_patch_methods.py:255 → eager/backward.cc:428."""
        autograd.backward([self], [grad_tensor] if grad_tensor is not None else None,
                          retain_graph=retain_graph)

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def register_hook(self, hook):
        """Gradient hook, fired during backward (reference: eager hooks)."""
        self._grad_hooks.append(hook)

        class _Handle:
            def remove(_self):
                if hook in self._grad_hooks:
                    self._grad_hooks.remove(hook)
        return _Handle()

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True)
        return t

    def detach_(self):
        self._grad_fn = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from .dispatch import apply
        return apply("clone", lambda x: x + 0, [self])

    # ---- dtype/shape sugar (full op surface is bound by ops.registry) ----
    def astype(self, dtype) -> "Tensor":
        from .dispatch import apply
        dt = convert_dtype(dtype)
        if is_floating(self.dtype) and is_floating(dt):
            return apply("cast", lambda x: x.astype(dt), [self])
        t = Tensor(self._data.astype(dt),
                   stop_gradient=True if not is_floating(dt) else self.stop_gradient)
        return t

    cast = astype

    def to(self, *args, **kwargs):
        # to(dtype) / to(device) / to(device, dtype)
        dtype = kwargs.get("dtype")
        device = kwargs.get("device")
        for a in args:
            if isinstance(a, str) and (a in ("cpu", "tpu", "gpu", "cuda",
                                             "xla") or ":" in a):
                device = a
            else:
                dtype = a
        out = self
        if device is not None:
            kind = device.split(":")[0]
            if kind in ("tpu", "gpu", "cuda", "xla"):
                want = "tpu"  # accelerator strings route to the TPU backend
            elif kind == "cpu":
                want = "cpu"
            else:
                raise ValueError(
                    f"Tensor.to({device!r}): unknown device kind {kind!r} "
                    "(supported: tpu/gpu/cuda/xla → TPU, cpu)")
            targets = [d for d in jax.devices() if d.platform == want]
            if not targets and want == "cpu":
                try:
                    targets = jax.devices("cpu")
                except RuntimeError:
                    targets = []
            if not targets:
                raise RuntimeError(
                    f"Tensor.to({device!r}): no such device is attached "
                    f"(available: {[d.platform for d in jax.devices()]})")
            idx = int(device.split(":")[1]) if ":" in device else 0
            if idx >= len(targets):
                raise RuntimeError(
                    f"Tensor.to({device!r}): device index {idx} out of "
                    f"range — only {len(targets)} {want} device(s) attached")
            out = Tensor(jax.device_put(out._data, targets[idx]),
                         stop_gradient=out.stop_gradient)
        return out if dtype is None else out.astype(dtype)

    def cpu(self):
        return Tensor(jax.device_get(self._data), stop_gradient=self.stop_gradient)

    def tpu(self):
        return self

    cuda = tpu

    def pin_memory(self):
        return self

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    # ---- in-place value ops (tape-aware adopt pattern) ----
    def _snapshot(self) -> "Tensor":
        """Detached-identity copy carrying this tensor's current grad history.
        Used as the tape input of in-place ops so adopting the result doesn't
        sever the chain (the producing node's output slot is re-pointed here)."""
        import weakref
        t = Tensor(self._data, stop_gradient=self.stop_gradient)
        t._grad_fn = self._grad_fn
        t._output_index = self._output_index
        if t._grad_fn is not None:
            t._grad_fn.outputs[t._output_index] = weakref.ref(t)
        return t

    def _inplace(self, fn, *args, **kwargs):
        """Run fn on a snapshot of self and adopt the result (tape-aware)."""
        from . import autograd as _ag
        if (_ag.is_grad_enabled() and self._grad_fn is None
                and not self.stop_gradient):
            # matches the reference's eager engine: in-place on a leaf that
            # requires grad would silently divert gradient accumulation
            raise RuntimeError(
                "a leaf Tensor that requires grad is being used in an "
                "in-place operation; wrap the update in paddle.no_grad()")
        return self._adopt(fn(self._snapshot(), *args, **kwargs))

    def _adopt(self, new_tensor: "Tensor"):
        """In-place semantics: this tensor takes over new value + grad history."""
        import weakref
        self._data = new_tensor._data
        self._grad_fn = new_tensor._grad_fn
        self._output_index = new_tensor._output_index
        if self._grad_fn is not None:
            # re-point the tape node's output slot at the surviving tensor
            self._grad_fn.outputs[self._output_index] = weakref.ref(self)
        if not new_tensor.stop_gradient:
            self.stop_gradient = False
        return self

    def set_value(self, value):
        value = value._data if isinstance(value, Tensor) else jnp.asarray(value)
        self._data = value.astype(self.dtype) if value.dtype != self.dtype else value
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def fill_(self, value):
        self._data = jnp.full_like(self._data, value)
        return self

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        return self

    # ---- indexing ----
    def __getitem__(self, idx):
        from .dispatch import apply
        idx = _unwrap_index(idx)
        return apply("getitem", lambda x: x[idx], [self])

    def __setitem__(self, idx, value):
        from . import autograd as _ag
        from .dispatch import apply
        if (_ag.is_grad_enabled() and self._grad_fn is None
                and not self.stop_gradient):
            raise RuntimeError(
                "a leaf Tensor that requires grad is being used in an "
                "in-place operation; wrap the update in paddle.no_grad()")
        idx = _unwrap_index(idx)
        snap = self._snapshot()
        if isinstance(value, Tensor):
            out = apply("setitem", lambda x, v: x.at[idx].set(
                v.astype(x.dtype) if v.dtype != x.dtype else v), [snap, value])
        else:
            out = apply("setitem", lambda x: x.at[idx].set(value), [snap])
        self._adopt(out)

    # ---- arithmetic operators (delegate to ops.math through the tape) ----
    def _binop(self, other, opname, reverse=False):
        # the ops module is resolved ONCE (a per-op `from .. import ops`
        # runs the import machinery on every arithmetic operator — the
        # dispatch fast path budget is O(10 µs), imports don't fit)
        global _ops_mod
        if _ops_mod is None:
            from .. import ops as _ops_mod_local
            _ops_mod = _ops_mod_local
        fn = getattr(_ops_mod, opname)
        return fn(other, self) if reverse else fn(self, other)

    def __add__(self, o):
        return self._binop(o, "add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "subtract")

    def __rsub__(self, o):
        return self._binop(o, "subtract", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "multiply")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "divide")

    def __rtruediv__(self, o):
        return self._binop(o, "divide", reverse=True)

    def __floordiv__(self, o):
        return self._binop(o, "floor_divide")

    def __mod__(self, o):
        return self._binop(o, "remainder")

    def __pow__(self, o):
        return self._binop(o, "pow")

    def __rpow__(self, o):
        return self._binop(o, "pow", reverse=True)

    def __matmul__(self, o):
        return self._binop(o, "matmul")

    def __neg__(self):
        from .. import ops
        return ops.neg(self)

    def __abs__(self):
        from .. import ops
        return ops.abs(self)

    def __eq__(self, o):  # noqa: A003 - paddle returns elementwise tensor
        return self._binop(o, "equal")

    def __ne__(self, o):
        return self._binop(o, "not_equal")

    def __lt__(self, o):
        return self._binop(o, "less_than")

    def __le__(self, o):
        return self._binop(o, "less_equal")

    def __gt__(self, o):
        return self._binop(o, "greater_than")

    def __ge__(self, o):
        return self._binop(o, "greater_equal")

    def __invert__(self):
        from .. import ops
        return ops.logical_not(self)

    def __and__(self, o):
        return self._binop(o, "logical_and" if self.dtype == jnp.dtype("bool")
                           else "bitwise_and")

    def __or__(self, o):
        return self._binop(o, "logical_or" if self.dtype == jnp.dtype("bool")
                           else "bitwise_or")

    @property
    def T(self):
        from .. import ops
        return ops.transpose(self, list(range(self.ndim))[::-1])

    # numpy protocol: let np.asarray(tensor) work
    def __array__(self, dtype=None):
        arr = np.asarray(self._data)
        return arr.astype(dtype) if dtype is not None else arr


class Parameter(Tensor):
    """Trainable tensor (reference: python/paddle/base/framework.py Parameter)."""

    __slots__ = ("optimize_attr", "regularizer", "need_clip", "is_distributed")

    def __init__(self, data, dtype=None, trainable=True, name=None):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _unwrap_index(idx):
    """Convert Tensor indices inside (possibly nested) index tuples to jax arrays."""
    if isinstance(idx, Tensor):
        return idx._data
    if isinstance(idx, tuple):
        return tuple(_unwrap_index(i) for i in idx)
    if isinstance(idx, list):
        return [_unwrap_index(i) for i in idx]
    return idx


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """paddle.to_tensor (reference: python/paddle/tensor/creation.py)."""
    return Tensor(data, dtype=dtype, stop_gradient=stop_gradient)
