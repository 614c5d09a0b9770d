"""Optimizer base class (reference: python/paddle/optimizer/optimizer.py).

Semantics kept from the reference: explicit parameter lists (dygraph mode),
param groups as dicts, grad clip hook, L2 regularization fold-in, accumulator
state_dict round-trip, master weights (multi_precision) for low-precision
params. TPU-native: updates are raw jnp expressions on the underlying
jax.Array — under jit.to_static the whole step (fwd+bwd+update) stages into
one XLA program; eagerly XLA fuses each param update chain.
"""
from __future__ import annotations

from collections import OrderedDict, defaultdict

import jax.numpy as jnp

from ..core.dtype import is_floating
from ..core.tensor import Parameter, Tensor
from ..regularizer import L1Decay, L2Decay
from .lr import LRScheduler

__all__ = ["Optimizer"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        if parameters is None:
            from ..static import program as _sp
            if _sp.in_static_mode():
                parameters = []  # filled by minimize from the Program
            else:
                raise ValueError(
                    "parameters is required in dygraph mode: pass "
                    "model.parameters() (reference: optimizer.py dygraph "
                    "check)")
        parameters = list(parameters)
        if parameters and isinstance(parameters[0], dict):
            self._param_groups = []
            self._parameter_list = []
            for g in parameters:
                group = dict(g)
                group["params"] = list(g["params"])
                self._param_groups.append(group)
                self._parameter_list += group["params"]
        else:
            self._parameter_list = parameters
            self._param_groups = [{"params": parameters}]
        self._learning_rate = learning_rate
        self.regularization = weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: dict = defaultdict(dict)  # acc name -> {pid: arr}
        self._master_weights: dict = {}  # pid -> f32 arr
        self._pid_to_param = {id(p): p for p in self._parameter_list}
        self._global_step = 0
        self._lr_override = None  # set by jit whole-step staging (traced lr)
        # distributed hooks (set by DygraphShardingOptimizer): reshard the
        # grad before the sharded accumulator update (ZeRO reduce-scatter)
        # and the updated param after it (all-gather / keep-sharded)
        self._dist_grad_hook = None
        self._dist_out_hook = None

    # ---- learning rate ----
    def get_lr(self):
        if self._lr_override is not None:
            return self._lr_override
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when the learning rate is an LRScheduler; "
                "call scheduler.step() instead")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # ---- weight decay ----
    def _coupled_decay_coeff(self, group):
        """L2 coeff folded into grads (SGD/Momentum/Adam reference behavior).
        AdamW overrides to return 0 here and applies decoupled decay."""
        wd = group.get("weight_decay", self.regularization)
        if wd is None:
            return 0.0, None
        if isinstance(wd, L2Decay):
            return wd.coeff, None
        if isinstance(wd, L1Decay):
            return 0.0, wd.coeff
        return float(wd), None

    # ---- accumulators ----
    def _get_accumulator(self, name, p, init=None):
        d = self._accumulators[name]
        pid = id(p)
        if pid not in d:
            dtype = jnp.float32 if self._use_master(p) else p._data.dtype
            d[pid] = jnp.zeros(p._data.shape, dtype) if init is None else init
        return d[pid]

    def _set_accumulator(self, name, p, value):
        self._accumulators[name][id(p)] = value

    def _use_master(self, p):
        return self._multi_precision and p._data.dtype in (
            jnp.bfloat16, jnp.float16)

    def _master_of(self, p):
        pid = id(p)
        if pid not in self._master_weights:
            self._master_weights[pid] = p._data.astype(jnp.float32)
        return self._master_weights[pid]

    # ---- the step ----
    def step(self):
        for group in self._param_groups:
            params_grads = []
            for p in group["params"]:
                if p.stop_gradient or p._grad is None:
                    continue
                params_grads.append((p, Tensor(p._grad, stop_gradient=True)))
            if not params_grads:
                continue
            if self._grad_clip is not None:
                params_grads = self._grad_clip(params_grads)
            lr = group.get("learning_rate", 1.0)
            lr = self.get_lr() * lr if isinstance(lr, (int, float)) else lr
            l2, l1 = self._coupled_decay_coeff(group)
            for p, g in params_grads:
                garr = g._data
                use_master = self._use_master(p)
                w = self._master_of(p) if use_master else p._data
                garr = garr.astype(w.dtype)
                if l2:
                    garr = garr + l2 * w
                if l1:
                    garr = garr + l1 * jnp.sign(w)
                plr = lr * p.optimize_attr.get("learning_rate", 1.0) \
                    if isinstance(p, Parameter) and p.optimize_attr else lr
                if self._dist_grad_hook is not None:
                    garr = self._dist_grad_hook(p, garr)
                new_w = self._update(p, w, garr, plr, group)
                if use_master:
                    # the f32 master is optimizer state and keeps the
                    # (ZeRO-sharded) layout of the update; the out hook
                    # regathers only the copy the forward reads
                    self._master_weights[id(p)] = new_w
                    new_w = new_w.astype(p._data.dtype)
                if self._dist_out_hook is not None:
                    new_w = self._dist_out_hook(p, new_w)
                p._data = new_w
        self._global_step += 1

    def _update(self, p, w, g, lr, group):
        """Return the new param value (raw array). Subclasses implement."""
        raise NotImplementedError

    # ---- grads ----
    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    def backward(self, loss, retain_graph=False):
        loss.backward(retain_graph=retain_graph)

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static import program as _sp
        if _sp.in_static_mode() and isinstance(loss, _sp.Variable):
            # static graph: record; Executor.run replays backward+step
            loss._program.minimize_ops.append((self, loss))
            return None, None
        self.backward(loss)
        self.step()
        return None, None

    # ---- state dict ----
    def state_dict(self):
        """acc-name_param-name → Tensor + LR_Scheduler + master weights
        (reference format: optimizer.py state_dict)."""
        state = OrderedDict()
        for acc_name, per_param in self._accumulators.items():
            for pid, arr in per_param.items():
                p = self._pid_to_param.get(pid)
                if p is None:
                    continue
                state[f"{p.name}_{acc_name}"] = Tensor(arr,
                                                       stop_gradient=True)
        if self._master_weights:
            mw = {}
            for pid, arr in self._master_weights.items():
                p = self._pid_to_param.get(pid)
                if p is not None:
                    mw[p.name] = Tensor(arr, stop_gradient=True)
            state["master_weights"] = mw
        if isinstance(self._learning_rate, LRScheduler):
            state["LR_Scheduler"] = self._learning_rate.state_dict()
        state["global_step"] = self._global_step
        return state

    def set_state_dict(self, state_dict):
        by_name = {p.name: p for p in self._parameter_list}
        for key, value in state_dict.items():
            if key == "LR_Scheduler":
                if isinstance(self._learning_rate, LRScheduler):
                    self._learning_rate.set_state_dict(value)
                continue
            if key == "global_step":
                self._global_step = int(value)
                continue
            if key == "master_weights":
                for pname, t in value.items():
                    p = by_name.get(pname)
                    if p is not None:
                        self._master_weights[id(p)] = jnp.asarray(
                            t.numpy(), jnp.float32)
                continue
            # key = f"{param_name}_{acc_name}"; param names contain no '_'
            # ambiguity risk, so match by longest param-name prefix
            matched = None
            for pname, p in by_name.items():
                if key.startswith(pname + "_"):
                    if matched is None or len(pname) > len(matched[0]):
                        matched = (pname, p)
            if matched is None:
                continue
            pname, p = matched
            acc_name = key[len(pname) + 1:]
            arr = value._data if isinstance(value, Tensor) else \
                jnp.asarray(value)
            self._accumulators[acc_name][id(p)] = arr

    # ---- state materialization (skip the eager warmup in jit staging) ----
    def materialize(self):
        """Create all accumulators (and master weights) up front so the
        compiled whole-step program can stage them as inputs without an
        eager first step."""
        for group in self._param_groups:
            for p in group["params"]:
                if p.stop_gradient:
                    continue
                if self._use_master(p):
                    self._master_of(p)
                self._materialize_param(p)

    def _materialize_param(self, p):
        """Subclasses pre-create their accumulators for param p."""

    # ---- functionalization hooks for jit.to_static ----
    def _state_slots(self):
        """[(container_dict, key)] of every mutable raw array — the compile
        layer swaps these with tracers to stage optimizer state."""
        slots = []
        for per_param in self._accumulators.values():
            for pid in per_param:
                slots.append((per_param, pid))
        for pid in self._master_weights:
            slots.append((self._master_weights, pid))
        return slots
