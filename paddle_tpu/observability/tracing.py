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
* The serving round, the training step and their phases
  (:class:`phase`) are ALSO recorded as ``jax.profiler.TraceAnnotation``
  (TraceMe level 1). While a profile is being taken they land on
  ``/host:CPU`` of the same ``.xplane.pb`` as the device's ops, on the
  profiler's clock, so a device idle gap can be laid against the host
  phase that overlaps it. With no profile running the annotation is one
  flag test; the buffer copy is there either way (``PADDLE_TPU_TRACE=1``
  alone, for hunting a slow round over many untraced runs).
* A :class:`phase`'s record in the buffer (every ``decode_round``,
  ``round.*``, ``serve.*``, ``train_step`` and ``step.*``; no other
  event, and never the annotation) carries a third reading, ``cpu_us``:
  the CPU time of the thread that ran it (``time.thread_time_ns()``)
  between the same two instants as ``ts`` and ``dur``. ``dur`` minus
  ``cpu_us`` is time off the CPU: waiting for the device, the interpreter
  lock, another thread's lock or the OS. Phases that meet on a thread
  share ONE reading of both clocks, so they tile its time with no hole.
  A sandboxed kernel may move a thread's CPU clock in ticks (gVisor, the
  benchmark's chip host: 10 ms a tick, 6 us a reading): one record then
  reads 0 or a tick, and only sums over many records say what a phase
  costs.

The phase spans, named once, here (``serving/engine.py`` ``_step_ragged``,
``_end_round`` and ``_serve_loop`` open them; ``cat`` is ``serving``).
With tracing on, every instant of the serve thread from ``start()`` to
``stop()`` lies in exactly one of ``decode_round``, ``serve.idle_wait``
and ``serve.turn``:

``decode_round``
    one whole scheduler round. Args: ``round`` (the engine's step
    counter), ``pad`` (the launch's padded token count), ``tokens`` (valid
    tokens in it), ``row_lens`` / ``kv_lens`` (per launched row: query
    tokens, context length after them; at most ``max_slots`` each),
    ``decode_rows``, ``prefill_rows``, ``prefill_tokens``, and the rows
    of the cache a layer of each page group has to read (the sum of
    ``kv_lens``, or of ``min(kv_len, window + row_len - 1)`` for a group
    with a window) under the name of what the group keeps: ``kv_rows``
    for keys and values by head, ``latent_rows`` for a latent cache,
    ``window_rows`` for keys and values behind a window (the benchmark's
    ``mla_roofline_pct.serve`` and ``windowed_roofline_pct.serve`` check
    their counts against them). Where layers keep a state a request and
    no rows a token, ``state_rows``: the launched rows times those layers,
    the states the round has to read and write (``kda_roofline_pct.serve``
    checks it, ``state_read_pct.serve`` weighs it against
    ``latent_rows``), and ``state_chunk_tokens``: the tokens of the rows
    longer than one token times those layers (prompt chunks; 0 in a
    decode-only round), defined by the rows alone: it is also what
    ``ops/pallas/kda_ragged.py`` takes in blocks through the matrix unit.
    No metric reads it yet. Where layers keep keys and values by head,
    ``attn_items`` and ``attn_page_blocks``: the work items of
    ``ops/pallas/ragged_attention.py`` that read a page and the page
    blocks they fetch, a layer, defined by the rows and the token pad
    alone (``item_plan``). No metric reads them.
``round.schedule``
    ``scheduler.schedule()``, ``ensure_decode_capacity()``, admission and
    eviction bookkeeping.
``round.assemble``
    the plan (decode rows, prefill chunks) and the numpy metadata.
``round.launch``
    the upload of the round's plan (ONE int32 message, one
    ``jax.device_put``) and the call of the round's program. Args:
    ``uploads``, the host-to-device transfers the round made (1).
``round.fetch``
    the round's one blocking fetch: the host blocked on the device for
    the tokens (their copy was asked for at the launch), the emitted
    logits beside them and, when asked, the logit rows and the layers'
    reports in the same ``jax.device_get``. Args: ``fetches``, the
    blocking fetches the round made (1).
``round.emit``
    sampling, ``complete_step`` (``on_token`` / ``on_done`` callbacks run
    here, a closed loop's resubmits among them), prefill bookkeeping,
    metrics hooks.
``round.account``
    the engine's accounting after the last token went out: the pool's
    occupancy and peak, ``group.note``, ``metrics.sample_state``, the step
    counter. The round closes where it closes.
``serve.idle_wait``
    one ``_wake.wait(0.02)`` of the serve loop: no work pending.
``serve.turn``
    the serve loop between two of the others, from the close of a round
    or a wait to the opening of the next: the loop's condition and fault
    site, ``scheduler.has_work()`` (the scheduler's lock, shared with
    ``submit``) and the wait for ``_step_lock``. A round driven by
    ``step()`` from another thread has none.

``cache.window_release``
    one zero-length event a round of an engine with a windowed page
    group, args ``round`` and ``pages``: by group name, the pages that
    slid out of the window since the last round and went back to the
    allocator before this round's admissions
    (``scheduler.release_slid_pages``).

``moe.route`` (and any other name a layer reports)
    what the layers of the round's program put under ``cache["aux"]``:
    small arrays the program returns beside the tokens whether tracing is
    on or off (so both launch the same program), fetched and recorded
    only when it is on. One zero-length event a round and name, args
    ``round`` and ``layers``: one entry a reporting layer, for
    ``moe.route`` ``[pairs, experts_idle, max_load]`` of the experts the
    layer holds (token-expert pairs that fell to them, held experts with
    no token, the fullest one's pairs). Read by
    ``moe_gmm_roofline_pct.serve`` and ``experts_idle_pct.serve``.

The six ``round.*`` phases tile their ``decode_round`` (which opens once
``step()`` holds ``_step_lock`` and closes when ``step()`` is done) and
carry its ``round``, which ties a phase to its round and to the program
launch it caused. A round that finds nothing to launch is a
``decode_round`` with no ``pad`` and the three phases it went through
(``round.schedule``, ``round.assemble``, ``round.account``), in the
buffer as on the profiler's clock.

The training step's spans (``jit/api.py`` ``_exec_whole_step``; ``cat``
``step``; the gate is the buffer OR a profile being taken, and with the
profile alone they go to the annotation alone):

``train_step``
    one call of a whole-step ``to_static`` function. Args: ``step``, a
    counter of that function. The time between two of them is the
    caller's (the loss fetch, the next batch).
``step.gather``
    the rng spec, the learning rates, the list of state arrays.
``step.launch``
    the call of the jitted step (a first call traces, lowers and
    compiles in here).
``step.rebind``
    the write-back of parameters, buffers and optimizer slots, and the
    outputs' wrapping.

What a stall was, while the buffer is on (these nest inside phases, so
they carry neither prefix; buffer only):

``host.gc``
    one collection of Python's collector, on the thread it ran in (``cat``
    ``host``). Args: ``generation``, ``collected``.
``jit.trace`` / ``jit.lower`` / ``jit.compile``
    what ``jax.monitoring`` reports when a jaxpr trace, a lowering to an
    MLIR module or a backend compile (a load from the compile cache
    included) ends; the event ends there and starts its duration earlier
    (``cat`` ``jit``). Args: ``fun_name`` (jax's own: ``round_step`` for
    the trace of the serving round's program, ``jit(round_step)`` for the
    rest). An inner jit's trace lies inside its caller's; events shorter
    than a millisecond (thousands of those a program) are not recorded.

Beside the ``jit.*`` events and ALWAYS on (jax calls the listener only
when it traces, lowers or compiles): :func:`compile_log`, the newest
``_COMPILE_LOG_CAP`` of ``(t_end on time.perf_counter(), kind, fun_name,
seconds)`` that took a millisecond or more (an inner jit's trace of a
tenth of one is counted in the totals and not kept) since
:func:`listen_compiles` was first called
(``ServingEngine.__init__``, ``to_static``'s first build, and the buffer
coming on), and :func:`compile_totals`
(``ServingEngine.stats()["compile"]``: ``trace_s``, ``lower_s``,
``compile_s``, ``events``).

At ``_MAX_EVENTS`` the buffer keeps the NEWEST events and drops the
oldest (an eighth of the cap at a time); an export then holds one
``trace_truncated`` metadata event (``at_events``, ``dropped``,
``wall_us`` of the first drop) and ``droppedEvents``.

One event comes from a kernel, at trace time and not per step (``cat``
``kernels``; ``ops/pallas/flash_attention.py`` behind the same one gate):

``flash_attention.schedule``
    the tile schedule one traced call of the flash kernels was compiled
    with. Args: ``shape`` ([B, S, H, D]), ``sk``, ``dtype``, ``causal``,
    ``padded`` (both lengths), ``fwd`` / ``bwd_dq`` / ``bwd_dkv`` (each
    ``[block_q, block_k, chunk]``), ``steps_live`` / ``steps_dead`` (grid
    steps a kernel's call takes, and those the causal mask empties).

One event comes from the training step, also at trace time (``cat``
``step``; ``models/gpt.py`` ``GPTModel._keep_plan``):

``recompute.keep``
    what the blocks' backward keeps instead of recomputing, decided from
    the device's free memory when the step is traced. Args: ``names``
    (the tagged tensors kept somewhere, in order of worth), ``blocks``
    (name -> in how many blocks), ``layers``, ``bytes`` (kept on a
    device, all blocks), ``budget`` (free bytes less the step's reserve; 0
    where the platform reports no memory) and ``name_bytes`` (one block's
    tensor of each name on a device). ``names`` empty: full recompute.

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
:class:`phase` that opens and by :func:`listen_compiles`).
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import gc
import json
import os
import sys
import threading
import time
import zlib

__all__ = ["TraceBuffer", "span", "phase", "add_complete",
           "collective_event", "listen_compiles", "compile_log",
           "compile_totals",
           "mint_context", "req_event", "finish_request",
           "enabled", "get_buffer", "start", "stop", "export",
           "_reset_state"]

_MAX_EVENTS = 200_000  # the buffer's cap: ~40MB of JSON at most
_COMPILE_LOG_CAP = 10_000   # compile_log() keeps the newest this many ...
_COMPILE_LOG_FLOOR_S = 1e-3  # ... of those that took at least this long
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
    """Buffer of chrome-trace events for ONE process: append-only up to
    ``_MAX_EVENTS``, from there on the newest that many."""

    def __init__(self, rank=None, path=None):
        from .metrics import env_rank
        self.rank = env_rank() if rank is None else int(rank)
        self.path = path
        self.events = []
        # re-entrant: a collection can start inside an append, and its
        # ``host.gc`` event is appended from the same thread
        self._lock = threading.RLock()
        self.dropped = 0            # events the cap pushed out, oldest first
        self._truncated_us = None   # when the first of them went
        # -------- request tracing (tail-based sampling) state
        self._req = {}              # tid -> pending event list
        self._decided = {}          # tid -> kept? (post-terminal verdict)
        self._decided_order = collections.deque()
        self._named_lanes = set()   # tids whose lane got a thread_name
        self.req_traces_dropped = 0
        self.sample_rate = _env_float(_SAMPLE_ENV)
        self.slow_ms = _env_float(_SLOW_ENV)

    def _append_locked(self, ev):
        """Append under self._lock. At the cap the OLDEST events go, so a
        process left tracing still holds the minutes before a stall; they
        go an eighth of the cap at a time (a full buffer does not shift
        its every entry for one event). Returns how many went: an export
        says so (``trace_truncated``, ``droppedEvents``)."""
        lost = 0
        over = len(self.events) + 1 - _MAX_EVENTS
        if over > 0:
            lost = min(len(self.events), max(over, _MAX_EVENTS // 8))
            if self.dropped == 0:
                self._truncated_us = time.time() * 1e6
            del self.events[:lost]
            self.dropped += lost
        self.events.append(ev)
        return lost

    def _put(self, name, ts_s, dur_s, cat, tid=None, args=None,
             cpu_us=None):
        """Build and append one complete event; -> how many the cap
        pushed out (no call outside this buffer: the collector's hook
        uses it from inside a collection)."""
        ev = {"name": str(name), "ph": "X", "pid": self.rank,
              "tid": tid if tid is not None else threading.get_ident(),
              "ts": ts_s * 1e6, "dur": max(0.0, dur_s) * 1e6, "cat": cat}
        if cpu_us is not None:
            ev["cpu_us"] = cpu_us
        if args:
            ev["args"] = dict(args)
        with self._lock:
            return self._append_locked(ev)

    def add(self, name, ts_s, dur_s, cat="host", tid=None, args=None,
            cpu_us=None):
        lost = self._put(name, ts_s, dur_s, cat, tid, args, cpu_us)
        if lost:
            _metric_drop(lost)

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
        lost = 0
        with self._lock:
            verdict = self._decided.get(tid)
            if verdict is False:
                return
            if verdict is True:
                lost = self._append_locked(ev)
            else:
                pend = self._req.get(tid)
                if pend is None:
                    if len(self._req) >= _PENDING_CAP:
                        lost = 1          # overflow: runaway guard
                    else:
                        self._req[tid] = pend = []
                if pend is not None:
                    pend.append(ev)
        if lost:
            _metric_drop(lost)

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
                    lost += self._append_locked(ev)
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
            truncated_us = self._truncated_us
        meta = [{"name": "process_name", "ph": "M", "pid": self.rank,
                 "args": {"name": f"rank_{self.rank} host"}},
                # clock provenance for the merge tool's --align: host
                # spans stamp time.time() µs (the same wall clock the
                # flight recorder uses), so device lanes from another
                # clock domain can be shifted onto this one
                {"name": "clock_domain", "ph": "M", "pid": self.rank,
                 "args": {"domain": "wall", "export_wall_us":
                          time.time() * 1e6}}]
        if dropped:
            # never silently complete: the cap, how many of the oldest
            # events it pushed out, and when the first went
            meta.append({"name": "trace_truncated", "ph": "M",
                         "pid": self.rank,
                         "args": {"at_events": _MAX_EVENTS,
                                  "dropped": dropped,
                                  "wall_us": truncated_us}})
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
            _watch(True)
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
        _watch(True)
        return _TR


def stop(path=None):
    """Export (when a path is known) and disable; returns the path."""
    global _TR, _loaded
    with _state_lock:
        buf = _TR
        _TR = None
        _loaded = True
        _watch(False)
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
        _watch(False)


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


def _now(buf):
    """One instant on a phase's two clocks: ``time.time()`` and, where
    there is a buffer to hold it, the calling thread's CPU time."""
    return time.time(), (time.thread_time_ns() if buf is not None else 0)


class phase:
    """One named host phase, recorded twice from one call: into ``buf``
    (``time.time()``, as every event here, and ``cpu_us``, the thread's
    CPU time between the same two instants) and as a
    ``jax.profiler.TraceAnnotation``, which a running profile puts on
    ``/host:CPU`` of its ``.xplane.pb`` on the device ops' clock.

    The caller has passed the gate and hands in the buffer, so the off
    path never reaches this class: ``with phase(buf, "serve.idle_wait"):``,
    or ``rnd = phase(buf, name, round=n).open()`` ... ``p =
    rnd.inner("round.schedule")`` ... ``p.set(pad=T)`` ... ``p =
    p.then("round.launch")`` ... ``rnd.close(p.close())`` where the phases
    of one round follow one another: phases that meet share ONE reading
    of the clocks, so they tile their thread's time with no hole. ``buf``
    may be ``None`` (a profile is being taken and the buffer is off): the
    phase then goes to the annotation alone."""

    __slots__ = ("buf", "name", "cat", "args", "t0", "c0", "_ann",
                 "_opened")

    def __init__(self, buf, name, cat="serving", **args):
        self.buf, self.name, self.cat, self.args = buf, name, cat, args
        self._ann = None
        self._opened = args
        self.t0 = None                    # None: not open

    def open(self, at=None):
        """``at``: the instant another phase closed at (its ``close()``),
        where this one takes over from it."""
        ann = _ANNOTATION or _annotation()
        if ann and ann.is_enabled():      # a profile is being taken
            self._ann = ann(self.name, **_stats(self.args))
            self._ann.__enter__()
        self.t0, self.c0 = at or _now(self.buf)
        return self

    def set(self, **args):
        """Arguments known only once the phase is under way: this
        phase's own, not carried to the next by :meth:`then`."""
        self.args = {**self.args, **args}
        if self._ann is not None:
            self._ann.set_metadata(**_stats(args))

    def close(self, at=None):
        """-> the instant it closed at, for the phase that ends or starts
        with it."""
        at = at or _now(self.buf)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.buf is not None:
            self.buf.add(self.name, self.t0, at[0] - self.t0, cat=self.cat,
                         args=self.args, cpu_us=(at[1] - self.c0) / 1e3)
        self.t0 = None
        return at

    def then(self, name, **args):
        """Close this phase and open the next one at the same instant
        (with what this one was opened with, its ``round``, and
        ``args``)."""
        return phase(self.buf, name, self.cat, **self._opened,
                     **args).open(self.close())

    def inner(self, name):
        """The first phase INSIDE this one: opens at this one's opening,
        with what it was opened with."""
        return phase(self.buf, name, self.cat,
                     **self._opened).open((self.t0, self.c0))

    __enter__ = open

    def __exit__(self, *exc):
        self.close()
        return False


# ------------------------------------------- what a stall was: gc, compiles
#
# While the buffer is on, a collection and a trace / lowering / compile
# are events of their own (``host.gc``, ``jit.*``), nested inside whatever
# phase they held up. The compile log beside them is always on: jax calls
# its listener only when it traces, lowers or compiles.

_gc_t0 = None      # a collection under way (they never nest or overlap)


def _on_gc(when, info):
    global _gc_t0
    if when == "start":
        _gc_t0 = time.time()
        return
    buf, t0, _gc_t0 = _TR, _gc_t0, None
    if buf is not None and t0 is not None:
        # not through add(): no call into the metrics registry from
        # inside a collection (whatever the thread held when it began)
        buf._put("host.gc", t0, time.time() - t0, "host",
                 args={"generation": info["generation"],
                       "collected": info["collected"]})


def _watch(on):
    """The buffer's own hooks, put in when it comes on and taken out
    when it goes."""
    if on:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        if "jax" in sys.modules:    # never the importer (a launcher)
            listen_compiles()
    elif _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jit.trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jit.lower", "lower_s"),
    "/jax/core/compile/backend_compile_duration":
        ("jit.compile", "compile_s"),
}
_compiles = collections.deque(maxlen=_COMPILE_LOG_CAP)
_compile_totals = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                   "events": 0}
_compile_lock = threading.Lock()
_listening = False


def _on_duration(event, seconds, **kw):
    """jax.monitoring's duration listener: fires when a trace, a lowering
    or a backend compile (a load from the compile cache included) ENDS."""
    kind = _JIT_EVENTS.get(event)
    if kind is None:
        return
    name, total = kind
    # a program's trace holds thousands of inner jits' traces of a tenth
    # of a millisecond (9,900 events a GPT serving start): they are
    # counted, and the log and the buffer keep what can be told apart
    kept = seconds >= _COMPILE_LOG_FLOOR_S
    fun = str(kw.get("fun_name", ""))
    with _compile_lock:
        if kept:
            _compiles.append((time.perf_counter(), name, fun, seconds))
        _compile_totals[total] += seconds
        _compile_totals["events"] += 1
    buf = _TR
    if kept and buf is not None:
        buf.add(name, time.time() - seconds, seconds, cat="jit",
                args={"fun_name": fun})


def listen_compiles():
    """Start the compile log (idempotent; ``ServingEngine.__init__`` and
    ``to_static``'s first build call it, and the buffer when it comes on
    in a process that has jax)."""
    global _listening
    with _compile_lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)


def compile_log():
    """-> ``[(t_end, kind, fun_name, seconds)]``, oldest first: every
    ``jit.trace`` / ``jit.lower`` / ``jit.compile`` of at least
    ``_COMPILE_LOG_FLOOR_S`` since the log began (the newest
    ``_COMPILE_LOG_CAP``), ``t_end`` on ``time.perf_counter()``. An inner
    jit's trace lies inside its caller's, so plain sums count nested time
    twice."""
    with _compile_lock:
        return list(_compiles)


def compile_totals():
    """-> ``{"trace_s", "lower_s", "compile_s", "events"}`` of the whole
    log, whatever the cap dropped (``ServingEngine.stats()["compile"]``)."""
    with _compile_lock:
        return dict(_compile_totals)


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
