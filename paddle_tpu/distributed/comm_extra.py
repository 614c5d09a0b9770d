"""Distributed long-tail API (reference: python/paddle/distributed/
__init__.py __all__ — p2p send/recv, gather, alltoall, object
collectives, spawn, ParallelEnv/ParallelMode, dist.split, gloo bootstrap,
shard_optimizer/dtensor_from_fn and the PS dataset/entry configs).

TPU-native notes: under single-controller SPMD the "ranks" of a group are
mesh coordinates in one process, so p2p and object collectives are host
moves; under multi-controller (env.init_parallel_env multi-process) the
TCPStore carries the payloads, exactly like the reference's Gloo side
channel for object collectives.
"""
from __future__ import annotations

import pickle

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from . import flight_recorder as _fr
from .collective import ReduceOp, _as_group, all_gather  # noqa: F401

__all__ = ["gather", "alltoall", "alltoall_single", "send", "recv",
           "isend", "irecv", "wait", "all_gather_object",
           "broadcast_object_list", "scatter_object_list", "is_available",
           "get_backend", "ParallelMode", "ParallelEnv", "spawn", "split",
           "gloo_init_parallel_env", "gloo_barrier", "gloo_release",
           "ReduceType", "Placement", "DistAttr", "dtensor_from_fn",
           "shard_optimizer", "Strategy", "DistModel", "to_static",
           "QueueDataset", "InMemoryDataset", "CountFilterEntry",
           "ShowClickEntry", "ProbabilityEntry"]


def is_available():
    """Reference: dist.is_available — collectives exist on this build."""
    return True


def get_backend(group=None):
    """Reference: dist.get_backend — the comm backend name ('XCCL' family
    there; XLA collectives over ICI/DCN here)."""
    return "xla"


class ParallelMode:
    """Reference: parallel.ParallelMode constants."""

    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3


class ParallelEnv:
    """Reference: parallel.ParallelEnv — env-derived rank/world info."""

    @property
    def rank(self):
        from .env import get_rank
        return get_rank()

    @property
    def world_size(self):
        from .env import get_world_size
        return get_world_size()

    @property
    def device_id(self):
        import os
        return int(os.environ.get("FLAGS_selected_devices", "0"))

    @property
    def device_type(self):
        return jax.devices()[0].platform

    nranks = world_size
    local_rank = rank


# -- collectives ----------------------------------------------------------

def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """Reference: communication/gather.py — like all_gather but only dst
    keeps the result (single-controller: every coordinate is in-process,
    so dst-ness is API compatibility)."""
    if gather_list is None:
        gather_list = []
    all_gather(gather_list, tensor, group=group, sync_op=sync_op)
    return gather_list


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """Reference: communication/all_to_all.py alltoall."""
    from .collective import all_to_all
    return all_to_all(out_tensor_list, in_tensor_list, group=group,
                      sync_op=sync_op)


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """Reference: alltoall_single — every rank's buffer is cut into nranks
    chunks; chunk j goes to rank j. Global view (this module's eager
    contract, see collective.all_to_all): in_tensor is [nranks, len] with
    row r = rank r's buffer; the exchange is the chunk transpose
    out[r] = concat_j in[j, r·k:(r+1)·k]."""
    g = _as_group(group)
    n = g.nranks
    if in_split_sizes is not None and len(set(in_split_sizes)) > 1:
        raise NotImplementedError(
            "alltoall_single with uneven split sizes is not supported")
    arr = in_tensor._data
    if arr.shape[0] != n:
        raise ValueError(
            f"alltoall_single expects the global [nranks={n}, len] buffer, "
            f"got shape {tuple(arr.shape)}")
    rec = _fr.record_issue("alltoall_single", group=f"{g.axis}:{g.id}",
                           shape=tuple(arr.shape), dtype=arr.dtype,
                           extra={"nbytes": int(getattr(arr, "nbytes", 0)
                                                or 0)})
    k = arr.shape[1] // n
    chunked = arr.reshape((n, n, k) + arr.shape[2:])
    out = jnp.swapaxes(chunked, 0, 1).reshape(arr.shape)
    out_tensor._data = out
    _fr.record_complete(rec)
    return out_tensor


# -- p2p (host mailbox single-controller; TCPStore multi-controller) ------

_mailbox: dict = {}


def _store():
    from . import env as _env
    return getattr(_env, "_global_store", None)


def send(tensor, dst=0, group=None, sync_op=True):
    """Reference: communication/send.py. Single-controller SPMD has every
    rank in-process (mailbox move); multi-controller routes bytes through
    the TCPStore side channel, the reference's Gloo-equivalent path."""
    from .env import get_rank, get_world_size
    rec = _fr.record_issue("send", group="p2p",
                           shape=tuple(tensor._data.shape),
                           dtype=tensor._data.dtype,
                           extra={"dst": dst,
                                  "nbytes": int(getattr(
                                      tensor._data, "nbytes", 0) or 0)})
    if get_world_size() > 1 and _store() is not None:
        key = f"p2p/{get_rank()}->{dst}"
        _store().set(key, pickle.dumps(np.asarray(tensor._data)))
    else:
        _mailbox.setdefault(dst, []).append(np.asarray(tensor._data))
    _fr.record_complete(rec)
    return _Task(None)


def recv(tensor, src=0, group=None, sync_op=True):
    from .env import get_rank, get_world_size
    rec = _fr.record_issue("recv", group="p2p",
                           shape=tuple(tensor._data.shape),
                           dtype=tensor._data.dtype,
                           extra={"src": src,
                                  "nbytes": int(getattr(
                                      tensor._data, "nbytes", 0) or 0)})
    if get_world_size() > 1 and _store() is not None:
        key = f"p2p/{src}->{get_rank()}"
        _store().wait([key])
        arr = pickle.loads(_store().get(key))
    else:
        box = _mailbox.get(get_rank() if get_world_size() > 1 else 0) or \
            _mailbox.get(0) or []
        if not box:
            raise RuntimeError(f"recv: no message pending from rank {src}")
        arr = box.pop(0)
    tensor._data = jnp.asarray(arr)
    _fr.record_complete(rec)
    return _Task(tensor)


class _Task:
    """Reference: the async task handle returned by isend/irecv."""

    def __init__(self, result):
        self._result = result

    def wait(self):
        return True

    def is_completed(self):
        return True


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group, sync_op=False)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src, group, sync_op=False)


def wait(tensor, group=None, use_calc_stream=True):
    """Reference: communication/wait.py — stream sync."""
    jax.block_until_ready(tensor._data)
    return tensor


# -- object collectives ---------------------------------------------------

def all_gather_object(object_list, obj, group=None):
    """Reference: all_gather_object — pickle over the store (multi-proc)
    or direct append (single-controller: one process holds all ranks)."""
    from .env import get_rank, get_world_size
    world = get_world_size()
    rec = _fr.record_issue("all_gather_object", group="object")
    if world > 1 and _store() is not None:
        st = _store()
        st.set(f"ago/{get_rank()}", pickle.dumps(obj))
        st.wait([f"ago/{r}" for r in range(world)])
        for r in range(world):
            object_list.append(pickle.loads(st.get(f"ago/{r}")))
    else:
        object_list.append(obj)
    _fr.record_complete(rec)
    return object_list


def broadcast_object_list(object_list, src=0, group=None):
    from .env import get_rank, get_world_size
    world = get_world_size()
    rec = _fr.record_issue("broadcast_object_list", group="object",
                           extra={"src": src})
    if world > 1 and _store() is not None:
        st = _store()
        if get_rank() == src:
            st.set("bol/payload", pickle.dumps(object_list))
        st.wait(["bol/payload"])
        got = pickle.loads(st.get("bol/payload"))
        object_list[:] = got
    _fr.record_complete(rec)
    return object_list


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    from .env import get_rank, get_world_size
    world = get_world_size()
    rec = _fr.record_issue("scatter_object_list", group="object",
                           extra={"src": src})
    if world > 1 and _store() is not None:
        st = _store()
        if get_rank() == src:
            for r in range(world):
                st.set(f"sol/{r}", pickle.dumps(in_object_list[r]))
        st.wait([f"sol/{get_rank()}"])
        out_object_list.append(pickle.loads(st.get(f"sol/{get_rank()}")))
    else:
        out_object_list.append((in_object_list or [None])[0])
    _fr.record_complete(rec)
    return out_object_list


# -- launch helpers -------------------------------------------------------

def _spawn_entry(rank, nprocs, func, args):
    import os
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ.setdefault(
        "PADDLE_TRAINER_ENDPOINTS",
        ",".join(f"127.0.0.1:{6170 + i}" for i in range(nprocs)))
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Reference: spawn.py — start nprocs python processes with the
    distributed env wired (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
    PADDLE_TRAINER_ENDPOINTS, same contract as the launch module)."""
    import multiprocessing as mp
    if nprocs <= 0:
        nprocs = max(1, len(jax.devices()))
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_spawn_entry,
                        args=(rank, nprocs, func, args), daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        bad = [p.exitcode for p in procs if p.exitcode]
        if bad:
            raise RuntimeError(f"spawn: worker exit codes {bad}")
    return procs


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    """Reference: parallel_with_gloo.py — CPU-only bootstrap barrier
    membership over the TCPStore (the reference uses a Gloo HTTP store)."""
    from .tcp_store import TCPStore
    host, port = server_endpoint.rsplit(":", 1)
    from . import env as _env
    _env._global_store = TCPStore(host, int(port),
                                  is_master=(rank_id == 0),
                                  world_size=rank_num)
    _env._gloo_world = rank_num
    _env._gloo_rank = rank_id


def gloo_barrier():
    """Store-backed CPU barrier. The barrier key now comes from the flight
    recorder's per-group seq registry, namespaced by incarnation
    (``flight_recorder.store_scope()``): the old process-global
    ``_gloo_barrier_seq`` counter was never reset on
    ``destroy_process_group``/``gloo_release`` and restarted from zero in
    a relaunched incarnation, colliding with the stale keys the previous
    incarnation left in the store."""
    from . import env as _env
    st = getattr(_env, "_global_store", None)
    if st is None:
        raise RuntimeError("call gloo_init_parallel_env first")
    n = getattr(_env, "_gloo_world", 1)
    seq = _fr.next_group_seq("gloo_barrier")
    rec = _fr.record_issue("gloo_barrier", group="gloo",
                           extra={"gloo_seq": seq})
    st.barrier(f"{_fr.store_scope()}/gloo_barrier/{seq}", n)
    _fr.record_complete(rec)


def gloo_release():
    from . import env as _env
    _env._global_store = None
    _fr.reset_seqs("gloo_barrier")  # next gloo env starts a fresh lineage


# -- TP split helper ------------------------------------------------------

def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Reference: collective.split — build a model-parallel linear or
    embedding whose weight is partitioned across the mp group. GSPMD
    collapse: annotate the weight sharded on the mesh 'model' axis and let
    XLA insert the collectives; returns the layer's output for input x."""
    from . import fleet
    if operation == "linear":
        in_f, out_f = size
        if axis == 1:
            layer = fleet.ColumnParallelLinear(in_f, out_f,
                                               gather_output=gather_out)
        else:
            layer = fleet.RowParallelLinear(in_f, out_f,
                                            input_is_parallel=False)
        return layer(x)
    if operation == "embedding":
        n_emb, dim = size
        layer = fleet.VocabParallelEmbedding(n_emb, dim)
        return layer(x)
    raise ValueError(f"split supports 'linear'/'embedding', got "
                     f"{operation!r}")


# -- auto-parallel long tail ----------------------------------------------

class ReduceType:
    """Reference: auto_parallel ReduceType for Partial placements."""

    kRedSum = 0
    kRedMax = 1
    kRedMin = 2
    kRedProd = 3
    kRedAvg = 4


class Placement:
    """Reference: placement base type (Shard/Replicate/Partial extend)."""

    def is_shard(self, dim=None):
        return False

    def is_replicated(self):
        return False

    def is_partial(self):
        return False


class DistAttr:
    """Reference: DistAttr(mesh, sharding_specs) — the static-graph
    tensor annotation carrier."""

    def __init__(self, mesh, sharding_specs):
        self.process_mesh = mesh
        self.sharding_specs = list(sharding_specs)

    def __repr__(self):
        return (f"DistAttr(mesh={self.process_mesh}, "
                f"specs={self.sharding_specs})")


def dtensor_from_fn(fn, mesh, placements, *args, **kwargs):
    """Reference: api.py dtensor_from_fn — build with fn then shard."""
    from .auto_parallel.api import shard_tensor
    t = fn(*args, **kwargs)
    return shard_tensor(t, mesh, placements)


def shard_optimizer(optimizer, shard_fn=None):
    """Reference: api.py shard_optimizer (ZeRO over DTensor): shard every
    optimizer accumulator. TPU-native: accumulators follow their
    parameter's sharding automatically under GSPMD, so the explicit
    reshard is only applied when a shard_fn is given; otherwise the
    optimizer is returned with lazy state marked for sharded creation."""
    if shard_fn is not None:
        optimizer.materialize()
        for name, per in optimizer._accumulators.items():
            for pid, arr in list(per.items()):
                per[pid] = shard_fn(name, None, Tensor(arr))._data
    return optimizer


class Strategy:
    """Reference: auto_parallel Strategy — dataclass of knob groups."""

    class _Cfg(dict):
        __getattr__ = dict.get

        def __setattr__(self, k, v):
            self[k] = v

    def __init__(self, config=None):
        cfg = config or {}
        self.sharding = self._Cfg(cfg.get("sharding", {}))
        self.fused_passes = self._Cfg(cfg.get("fused_passes", {}))
        self.gradient_merge = self._Cfg(cfg.get("gradient_merge", {}))
        self.pipeline = self._Cfg(cfg.get("pipeline", {}))
        self.amp = self._Cfg(cfg.get("amp", {}))


class DistModel:
    """Reference: api.py DistModel — the to_static product: a callable
    train/eval step over the sharded program."""

    def __init__(self, layer, loader=None, loss=None, optimizer=None,
                 strategy=None):
        self._layer = layer
        self._loss = loss
        self._opt = optimizer
        self._mode = "train"
        from ..jit.api import StaticFunction
        cap = [layer] + ([optimizer] if optimizer is not None else [])

        def step(*batch):
            x, y = batch if len(batch) == 2 else (batch[0], None)
            out = layer(x)
            if loss is None:
                return out
            l = loss(out, y) if y is not None else loss(out)
            if self._mode == "train" and optimizer is not None:
                l.backward()
                optimizer.step()
                optimizer.clear_grad()
            return l

        self._step = StaticFunction(step, capture=cap)

    def train(self):
        self._mode = "train"

    def eval(self):
        self._mode = "eval"

    def __call__(self, *batch):
        return self._step(*batch)

    def state_dict(self, *a, **k):
        return self._layer.state_dict(*a, **k)


def to_static(layer, loader=None, loss=None, optimizer=None, strategy=None):
    """Reference: auto_parallel api.to_static — wrap into a DistModel."""
    return DistModel(layer, loader, loss, optimizer, strategy)


# -- PS datasets + sparse-table entry configs -----------------------------

class InMemoryDataset:
    """Reference: distributed/fleet/dataset InMemoryDataset — host
    dataset pool with load_into_memory/shuffle for PS training."""

    def __init__(self):
        self._files = []
        self._samples = []
        self._parser = None

    def init(self, **kwargs):
        self._parser = kwargs.get("pipe_command")

    def set_filelist(self, files):
        self._files = list(files)

    def load_into_memory(self):
        self._samples = []
        for f in self._files:
            with open(f) as fh:
                self._samples.extend(line.rstrip("\n") for line in fh)

    def local_shuffle(self):
        np.random.shuffle(self._samples)

    def global_shuffle(self, fleet=None, thread_num=12):
        self.local_shuffle()

    def get_memory_data_size(self, fleet=None):
        return len(self._samples)

    def release_memory(self):
        self._samples = []

    def __iter__(self):
        return iter(self._samples)


class QueueDataset(InMemoryDataset):
    """Reference: QueueDataset — streaming variant (no global shuffle)."""

    def global_shuffle(self, fleet=None, thread_num=12):
        raise RuntimeError("QueueDataset streams; it cannot be shuffled")


class _Entry:
    def __init__(self, kind, *args):
        self.kind = kind
        self.args = args

    def __repr__(self):
        return f"{type(self).__name__}{self.args}"


class CountFilterEntry(_Entry):
    """Reference: ps entry config — admit a sparse feature only after it
    has been seen ``count`` times."""

    def __init__(self, count):
        super().__init__("count_filter_entry", count)


class ShowClickEntry(_Entry):
    """Reference: ps entry config — track show/click statistics columns."""

    def __init__(self, show_name, click_name):
        super().__init__("show_click_entry", show_name, click_name)


class ProbabilityEntry(_Entry):
    """Reference: ps entry config — probabilistic feature admission."""

    def __init__(self, probability):
        super().__init__("probability_entry", probability)
