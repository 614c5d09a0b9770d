"""Host-side span tracing — Chrome-trace/Perfetto JSON export.

The xplane trace answers "what did the DEVICE do"; this module answers
"what did the HOST do around it": ``span("fwd")`` context managers in the
fit/pipeline paths become ``ph: "X"`` complete events, completed
flight-recorder collectives become ``cat: "collective"`` events, and the
export loads directly in chrome://tracing / ui.perfetto.dev. Merge with a
device timeline via ``python -m paddle_tpu.tools.merge_profiles`` (which
also accepts xplane log dirs).

Gating mirrors the metrics core: ``PADDLE_TPU_TRACE=1`` (export path from
``PADDLE_TPU_TRACE_PATH``, default ``trace.<rank>.json`` under
``PADDLE_TPU_WORKERLOG_DIR``; ``PADDLE_TPU_TRACE=/path.json`` sets both),
or programmatic :func:`start` / :func:`stop`. Disabled (the default),
``span()`` yields immediately off one module-global ``None`` check and
event feeds return without allocating.

Two clocks, stated plainly:

* Every event in the buffer is stamped ``time.time()`` µs — the same wall
  clock the flight recorder stamps, so collective events, request spans
  and host spans of several processes line up in one waterfall. Nesting
  needs no explicit parent ids: Perfetto nests same-thread "X" events by
  interval containment.
* The serving round and its phases (:class:`phase`) are ALSO recorded as
  ``jax.profiler.TraceAnnotation`` (TraceMe level 1). While a profile is
  being taken they land on ``/host:CPU`` of the same ``.xplane.pb`` as
  the device's ops, on the profiler's clock, so a device idle gap can be
  laid against the host phase that overlaps it. With no profile running
  the annotation is one flag test; the buffer copy is there either way
  (``PADDLE_TPU_TRACE=1`` alone, for hunting a slow round over many
  untraced runs).

The phase spans, named once, here (``serving/engine.py`` ``_step_ragged``
and ``_serve_loop`` open them; ``cat`` is ``serving``):

``decode_round``
    one whole scheduler round. Args: ``round`` (the engine's step
    counter), ``pad`` (the launch's padded token count), ``tokens`` (valid
    tokens in it), ``row_lens`` / ``kv_lens`` (per launched row: query
    tokens, context length after them; at most ``max_slots`` each),
    ``decode_rows``, ``prefill_rows``, ``prefill_tokens``.
``round.schedule``
    ``scheduler.schedule()``, ``ensure_decode_capacity()``, admission and
    eviction bookkeeping.
``round.assemble``
    the plan (decode rows, prefill chunks) and the numpy metadata.
``round.launch``
    the six host-to-device uploads and the call of the round's program.
``round.fetch``
    the token (and logit) fetch: the host blocked on the device.
``round.emit``
    sampling, ``complete_step`` (``on_token`` / ``on_done`` callbacks run
    here, a closed loop's resubmits among them), prefill bookkeeping,
    metrics hooks.
``serve.idle_wait``
    one ``_wake.wait(0.02)`` of the serve loop: no work pending.

The five ``round.*`` phases follow one another inside their
``decode_round`` and carry its ``round``, which ties a phase to its round
and to the program launch it caused. A round that finds nothing to launch
records no ``decode_round`` in the buffer.

One event comes from a kernel, at trace time and not per step (``cat``
``kernels``; ``ops/pallas/flash_attention.py`` behind the same one gate):

``flash_attention.schedule``
    the tile schedule one traced call of the flash kernels was compiled
    with. Args: ``shape`` ([B, S, H, D]), ``sk``, ``dtype``, ``causal``,
    ``padded`` (both lengths), ``fwd`` / ``bwd_dq`` / ``bwd_dkv`` (each
    ``[block_q, block_k, chunk]``), ``steps_live`` / ``steps_dead`` (grid
    steps a kernel's call takes, and those the causal mask empties).

Request tracing (ISSUE 20): :func:`mint_context` mints a trace context
(``{"tid": <hex id>, "ps": <parent span, 0 = root>}``) that rides the
fleet wire; every process feeds that request's spans through
:func:`req_event` into a per-trace pending buffer, and the terminal
:func:`finish_request` applies TAIL-BASED sampling — the trace is
retained (flushed onto the main buffer, on its own per-request lane)
only when the request erred, hedged, evicted, aborted, was slow
(``PADDLE_TPU_TRACE_SLOW_MS``), or hits the deterministic sample
(``PADDLE_TPU_TRACE_SAMPLE=<rate>``, hashed from the trace id so every
process makes the SAME decision without extra wire bits). Everything
else is dropped before export. Undecided traces still pending at export
time are flushed as-is so a shutdown mid-request stays visible.

Stdlib-only at import time (``jax`` is imported by the first
:class:`phase` that opens).
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import sys
import threading
import time
import zlib

__all__ = ["TraceBuffer", "span", "phase", "add_complete",
           "collective_event",
           "mint_context", "req_event", "finish_request",
           "enabled", "get_buffer", "start", "stop", "export",
           "_reset_state"]

_MAX_EVENTS = 200_000  # runaway guard: ~40MB of JSON at most
_DECIDED_CAP = 4096    # remembered tail-sampling verdicts (FIFO)
_PENDING_CAP = 1024    # simultaneously-undecided request traces

_SAMPLE_ENV = "PADDLE_TPU_TRACE_SAMPLE"
_SLOW_ENV = "PADDLE_TPU_TRACE_SLOW_MS"


def _env_float(name):
    raw = os.environ.get(name, "")
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


def _tid_bucket(tid):
    """Deterministic 32-bit hash of a trace id — identical in every
    process, so the sampling verdict needs no coordination."""
    return zlib.crc32(str(tid).encode("utf-8", "replace"))


def _metric_drop(n=1):
    try:
        from .metrics import counter
        c = counter("trace_events_dropped_total")
        if c is not None:
            c.inc(n)
    except Exception:
        pass


class TraceBuffer:
    """Append-only buffer of chrome-trace events for ONE process."""

    def __init__(self, rank=None, path=None):
        from .metrics import env_rank
        self.rank = env_rank() if rank is None else int(rank)
        self.path = path
        self.events = []
        self._lock = threading.Lock()
        self.dropped = 0
        # -------- request tracing (tail-based sampling) state
        self._req = {}              # tid -> pending event list
        self._decided = {}          # tid -> kept? (post-terminal verdict)
        self._decided_order = collections.deque()
        self._named_lanes = set()   # tids whose lane got a thread_name
        self.req_traces_dropped = 0
        self.sample_rate = _env_float(_SAMPLE_ENV)
        self.slow_ms = _env_float(_SLOW_ENV)

    def _append_locked(self, ev):
        """Append under self._lock; at the cap the FIRST drop leaves one
        over-cap metadata marker so a truncated export never silently
        looks complete. Returns False when the event was dropped."""
        if len(self.events) >= _MAX_EVENTS:
            if self.dropped == 0:
                self.events.append({
                    "name": "trace_truncated", "ph": "M",
                    "pid": self.rank,
                    "args": {"at_events": _MAX_EVENTS,
                             "wall_us": time.time() * 1e6}})
            self.dropped += 1
            return False
        self.events.append(ev)
        return True

    def add(self, name, ts_s, dur_s, cat="host", tid=None, args=None):
        ev = {"name": str(name), "ph": "X", "pid": self.rank,
              "tid": tid if tid is not None else threading.get_ident(),
              "ts": ts_s * 1e6, "dur": max(0.0, dur_s) * 1e6, "cat": cat}
        if args:
            ev["args"] = dict(args)
        with self._lock:
            ok = self._append_locked(ev)
        if not ok:
            _metric_drop()

    # ---------------------------------------------- request-trace feeds

    def _lane(self, tid):
        return _tid_bucket(tid)

    def _name_lane_locked(self, tid):
        if tid in self._named_lanes:
            return
        self._named_lanes.add(tid)
        self._append_locked({
            "name": "thread_name", "ph": "M", "pid": self.rank,
            "tid": self._lane(tid), "args": {"name": f"req {tid}"}})

    def req_add(self, tid, name, ts_s, dur_s, cat="request", args=None):
        """Buffer one span for request ``tid`` pending its tail-sampling
        verdict; post-verdict events append (kept) or vanish (dropped)
        directly."""
        a = {"trace": tid}
        if args:
            a.update(args)
        ev = {"name": str(name), "ph": "X", "pid": self.rank,
              "tid": self._lane(tid), "ts": ts_s * 1e6,
              "dur": max(0.0, dur_s) * 1e6, "cat": cat, "args": a}
        dropped = False
        with self._lock:
            verdict = self._decided.get(tid)
            if verdict is False:
                return
            if verdict is True:
                dropped = not self._append_locked(ev)
            else:
                pend = self._req.get(tid)
                if pend is None:
                    if len(self._req) >= _PENDING_CAP:
                        dropped = True    # overflow: runaway guard
                    else:
                        self._req[tid] = pend = []
                if pend is not None:
                    pend.append(ev)
        if dropped:
            _metric_drop()

    def req_finish(self, tid, keep):
        """Apply the tail-sampling verdict for ``tid``: flush (keep) or
        discard its pending spans. A later ``keep`` upgrades an earlier
        drop verdict for FUTURE events (the already-dropped ones are
        gone). Returns the effective verdict."""
        lost = 0
        with self._lock:
            pending = self._req.pop(tid, None)
            prior = self._decided.get(tid)
            if prior is True:
                keep = True
            elif prior is None:
                self._decided[tid] = bool(keep)
                self._decided_order.append(tid)
                while len(self._decided_order) > _DECIDED_CAP:
                    old = self._decided_order.popleft()
                    self._decided.pop(old, None)
                    self._named_lanes.discard(old)
            elif keep:
                self._decided[tid] = True
            if not keep:
                if pending:
                    self.req_traces_dropped += 1
                return False
            if pending:
                self._name_lane_locked(tid)
                for ev in pending:
                    if not self._append_locked(ev):
                        lost += 1
        if lost:
            _metric_drop(lost)
        return True

    def _flush_pending_locked(self):
        """Export-time flush of still-undecided traces (process exiting
        mid-request): keep them so the shutdown stays visible."""
        for tid, pending in list(self._req.items()):
            self._name_lane_locked(tid)
            for ev in pending:
                self._append_locked(ev)
        self._req.clear()

    def to_dict(self):
        with self._lock:
            self._flush_pending_locked()
            events = list(self.events)
            dropped = self.dropped
        meta = [{"name": "process_name", "ph": "M", "pid": self.rank,
                 "args": {"name": f"rank_{self.rank} host"}},
                # clock provenance for the merge tool's --align: host
                # spans stamp time.time() µs (the same wall clock the
                # flight recorder uses), so device lanes from another
                # clock domain can be shifted onto this one
                {"name": "clock_domain", "ph": "M", "pid": self.rank,
                 "args": {"domain": "wall", "export_wall_us":
                          time.time() * 1e6}}]
        d = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        if dropped:
            d["droppedEvents"] = dropped
        return d

    def export(self, path=None):
        path = path or self.path
        if not path:
            return None
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


# ------------------------------------------------- module-level singleton

_state_lock = threading.Lock()
_TR: TraceBuffer | None = None
_loaded = False
_atexit_armed = False


def _default_path(rank):
    d = os.environ.get("PADDLE_TPU_WORKERLOG_DIR") or "."
    return os.path.join(d, f"trace.{rank}.json")


def _load():
    global _TR, _loaded
    with _state_lock:
        if _loaded:
            return _TR
        raw = os.environ.get("PADDLE_TPU_TRACE", "")
        if raw in ("", "0", "false", "False"):
            _TR = None
        else:
            buf = TraceBuffer()
            if raw not in ("1", "true", "True"):
                buf.path = raw  # PADDLE_TPU_TRACE=/path.json
            else:
                buf.path = (os.environ.get("PADDLE_TPU_TRACE_PATH")
                            or _default_path(buf.rank))
            _TR = buf
            _arm_atexit()
        _loaded = True
        return _TR


def _arm_atexit():
    global _atexit_armed
    if not _atexit_armed:
        _atexit_armed = True
        atexit.register(_atexit_export)


def _atexit_export():
    buf = _TR
    if buf is not None and buf.path:
        try:
            buf.export()
        except Exception:
            pass


def get_buffer() -> TraceBuffer | None:
    return _TR if _loaded else _load()


def enabled() -> bool:
    return get_buffer() is not None


def start(path=None, rank=None) -> TraceBuffer:
    """Programmatic gate (tests / bench) — replaces the singleton."""
    global _TR, _loaded
    with _state_lock:
        _TR = TraceBuffer(rank=rank, path=path)
        _loaded = True
        _arm_atexit()
        return _TR


def stop(path=None):
    """Export (when a path is known) and disable; returns the path."""
    global _TR, _loaded
    with _state_lock:
        buf = _TR
        _TR = None
        _loaded = True
    if buf is None:
        return None
    try:
        return buf.export(path)
    except Exception as e:
        print(f"[trace] export failed: {e}", file=sys.stderr, flush=True)
        return None


def export(path=None):
    buf = _TR if _loaded else _load()
    return buf.export(path) if buf is not None else None


def _reset_state():
    """Test hook: back to the unresolved env-gated state."""
    global _TR, _loaded
    with _state_lock:
        _TR = None
        _loaded = False


# ------------------------------------------------------------------ feeds

@contextlib.contextmanager
def span(name, cat="host", **args):
    """Trace one host scope; a constant-time no-op when tracing is off."""
    buf = _TR if _loaded else _load()
    if buf is None:
        yield None
        return
    t0 = time.time()
    try:
        yield buf
    finally:
        buf.add(name, t0, time.time() - t0, cat=cat, args=args or None)


_ANNOTATION = None    # jax.profiler.TraceAnnotation; False: no jax here


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except Exception:
            _ANNOTATION = False
    return _ANNOTATION


def _stats(args):
    """An annotation's stats: a list rides as space-separated text (the
    profiler splits an annotation's arguments on commas)."""
    return {k: " ".join(map(str, v)) if isinstance(v, (list, tuple)) else v
            for k, v in args.items()}


class phase:
    """One named host phase, recorded twice from one call: into ``buf``
    (``time.time()``, as every event here) and as a
    ``jax.profiler.TraceAnnotation``, which a running profile puts on
    ``/host:CPU`` of its ``.xplane.pb`` on the device ops' clock.

    The caller has passed the gate and hands in the buffer, so the off
    path never reaches this class: ``with phase(buf, "serve.idle_wait"):``,
    or ``p = phase(buf, name, round=n).open()`` ... ``p.set(pad=T)`` ...
    ``p = p.then("round.launch")`` ... ``p.close()`` where the phases of
    one round follow one another."""

    __slots__ = ("buf", "name", "cat", "args", "t0", "_ann")

    def __init__(self, buf, name, cat="serving", **args):
        self.buf, self.name, self.cat, self.args = buf, name, cat, args
        self._ann = None

    def open(self):
        ann = _ANNOTATION or _annotation()
        if ann and ann.is_enabled():      # a profile is being taken
            self._ann = ann(self.name, **_stats(self.args))
            self._ann.__enter__()
        self.t0 = time.time()
        return self

    def set(self, **args):
        """Arguments known only once the phase is under way."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**_stats(args))

    def close(self, record=True):
        """``record=False`` leaves the buffer as it was (a round that
        launched nothing); the annotation, once opened, is closed."""
        dur = time.time() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if record:
            self.buf.add(self.name, self.t0, dur, cat=self.cat,
                         args=self.args)

    def then(self, name):
        """Close this phase and open the next one of the same round."""
        self.close()
        return phase(self.buf, name, self.cat, **self.args).open()

    __enter__ = open

    def __exit__(self, *exc):
        self.close()
        return False


def add_complete(name, ts_s, dur_s, cat="host", tid=None, args=None):
    buf = _TR if _loaded else _load()
    if buf is not None:
        buf.add(name, ts_s, dur_s, cat=cat, tid=tid, args=args)


# -------------------------------------------------- request-trace feeds
#
# Hot-path discipline (the standing contract): tracing off, a request
# never gets a context minted, so every hook in scheduler/engine/router
# gates on ``req.trace is not None`` — one attribute check, no
# allocation, no call into this module.

def mint_context():
    """-> a fresh trace context ``{"tid", "ps"}`` (``ps`` 0 = root) when
    tracing is on, else None. The None is what makes the off path free:
    downstream hooks check the attribute, not this module."""
    buf = _TR if _loaded else _load()
    if buf is None:
        return None
    return {"tid": os.urandom(8).hex(), "ps": 0}


def _ctx_tid(ctx):
    if type(ctx) is dict:
        tid = ctx.get("tid")
        return str(tid) if tid else None
    return None


def req_event(ctx, name, ts_s, dur_s, cat="request", args=None):
    """Feed one span for the request identified by trace context ``ctx``
    into the tail-sampling pending buffer. No-op off / ctx-less."""
    buf = _TR if _loaded else _load()
    if buf is None or ctx is None:
        return
    tid = _ctx_tid(ctx)
    if tid is not None:
        buf.req_add(tid, name, ts_s, dur_s, cat=cat, args=args)


def sampled(tid, rate):
    """Deterministic head-of-trace sample: every process hashes the same
    trace id to the same verdict — no coordination, no wire bits."""
    if not rate or rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return (_tid_bucket(tid) % 100_000) / 100_000.0 < rate


def finish_request(ctx, dur_s=None, error=False, hedged=False,
                   evicted=False, aborted=False, migrated=False):
    """Terminal-state tail-sampling decision for one request trace:
    retain when interesting (errored / hedged / evicted / aborted /
    migrated), slow (``PADDLE_TPU_TRACE_SLOW_MS``), or explicitly
    sampled (``PADDLE_TPU_TRACE_SAMPLE``); else drop the pending spans
    before they ever reach the export. Returns the verdict."""
    buf = _TR if _loaded else _load()
    if buf is None or ctx is None:
        return False
    tid = _ctx_tid(ctx)
    if tid is None:
        return False
    keep = bool(error or hedged or evicted or aborted or migrated)
    if not keep and buf.slow_ms is not None and dur_s is not None \
            and dur_s * 1e3 >= buf.slow_ms:
        keep = True
    if not keep:
        keep = sampled(tid, buf.sample_rate)
    return buf.req_finish(tid, keep)


def collective_event(entry):
    """Feed one completed flight-recorder entry as a trace event. Ring
    bookkeeping markers (``step`` group) are skipped; pipeline
    micro-batch entries keep their own category so the collective lane
    stays collectives-only."""
    buf = _TR if _loaded else _load()
    if buf is None or entry is None:
        return
    group = entry.get("group")
    if group == "step" or entry.get("aborted"):
        return
    t0, t1 = entry.get("t_issue"), entry.get("t_complete")
    if t0 is None or t1 is None:
        return
    cat = "pipeline" if group == "pipe" else "collective"
    args = {"group": group, "seq": entry.get("seq"),
            "gseq": entry.get("gseq")}
    if entry.get("shape") is not None:
        args["shape"] = str(entry["shape"])
    buf.add(entry.get("kind", "?"), t0, t1 - t0, cat=cat, args=args)
