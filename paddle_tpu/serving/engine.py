"""Continuous-batching serving engine: one ragged launch per round.

The millions-of-users tier (ROADMAP item 3; SURVEY layer 11). A
:class:`ServingEngine` wraps a decoder (``forward(input_ids, caches,
pos_offset)`` and ``cache_spec()``: what each layer keeps for a token)
and runs it as a concurrent serving loop. It knows no model: page pools
are allocated from the declaration, and its ``kind`` picks the attention
that reads them (``serving/attention.py``).

* **the round** — every scheduler round is ONE launch of one jitted
  program (Ragged Paged Attention, arxiv 2604.15464): single-token
  decode rows, budgeted prefill chunks and prefix-hit prompt tails
  flatten into a ``[total_tokens]`` token stream with per-row metadata
  (``row_starts``/``row_lens``/``kv_lens``/block tables); K/V scatter
  into pages and causal ragged attention happen in the same program.
  Only ``total_tokens`` is padded (power-of-two schedule, or the
  ``token_pads`` ladder), so the one callable has a handful of
  shape-specializations, counted by ``serving_compiles_total`` /
  ``serving_distinct_programs``. Params, the plan and pools are
  arguments, pools are donated on TPU; greedy argmax runs on device
  (host-side temperature/top-k sampling per request when asked). A
  round crosses the host-device boundary once each way: the plan goes
  up as ONE int32 message and is taken apart inside the program, the
  tokens come back in one blocking fetch.
* **chunked prefill** (ISSUE 9) — ``prefill_chunk=C`` splits prompts
  into C-token chunks advanced at most ``prefill_token_budget`` tokens
  per scheduler round beside the decode rows, so a long prompt arriving
  mid-stream never stalls in-flight decodes (ITL p99 is bounded by the
  budget). Without it every pending prompt rides the round whole.
* **prefix caching** (ISSUE 9, on by default) — full prompt pages are
  indexed in a page-granular trie (:class:`~.prefix_cache.PrefixCache`);
  an admission hit takes the shared head by refcounted reference
  (skipping its prefill compute AND page writes — only the tail rides
  the round), shared pages are copy-on-write read-only, and
  reclamation drains only refcount-0 cached pages, LRU-first.
* **scheduling** — between rounds the
  :class:`~.scheduler.ContinuousBatchingScheduler` finishes / evicts /
  admits, so a request arriving mid-stream joins the next round without
  stalling in-flight rows (the no-decode-gap acceptance test).

The attention backend is A/B gated (``serving/ragged_attention.py``):
Pallas only where it measurably beats the XLA reference at the serving
shape; ``PADDLE_TPU_SERVING_ATTN`` overrides. Pass ``mesh=`` to shard the
round along KV heads over the fleet mesh's ``model`` axis for multi-chip
serving.

Metrics flow through the PR-5 registry via :class:`~.metrics.
ServingMetrics`.
"""
from __future__ import annotations

import contextlib
import math
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import no_grad
from ..core.tensor import Tensor
from ..observability import tracing as _trc
from . import attention as _attention
from . import ragged_attention as _ragged
from .ragged_attention import pad_total_tokens as _pad_total_tokens
from .kv_cache import PagedKVCache, pages_for
from .metrics import ServingMetrics
from .prefix_cache import PrefixCache
from .scheduler import (ContinuousBatchingScheduler, EngineClosed,
                        EngineShuttingDown, GenerationRequest)

__all__ = ["ServingEngine"]


@contextlib.contextmanager
def _swap_params(params, arrays):
    """Temporarily back the model's Parameters with (traced) arrays so the
    round jits with weights as real arguments — no giant closure
    constants, donation-friendly."""
    olds = [p._data for p in params]
    for p, a in zip(params, arrays):
        p._data = a
    try:
        yield
    finally:
        for p, o in zip(params, olds):
            p._data = o


def _plan_parts(message, rows, bt_shape, slots=False):
    """The round's plan inside its ONE int32 message, in this order:
    ``tokens[T]``, ``row_starts[R]``, ``row_lens[R]``, ``kv_lens[R]``,
    where the model keeps a state a request (``slots``) ``row_slots[R]``,
    and the block tables (``bt_shape``); ``T`` is what is left of the
    length. Basic slices either way: of the host's numpy buffer they are
    the views the round writes its plan through, of the traced array the
    static slices the program takes it apart with, so the two sides cannot
    disagree on the layout. -> the parts in that order, the tables last."""
    n_bt = math.prod(bt_shape)
    cuts = [0, message.shape[0] - _message_len(0, rows, bt_shape, slots)]
    for n in (rows,) * (3 + bool(slots)) + (n_bt,):
        cuts.append(cuts[-1] + n)
    *parts, bt = (message[a:b] for a, b in zip(cuts, cuts[1:]))
    return (*parts, bt.reshape(bt_shape))


def _message_len(T, rows, bt_shape, slots=False):
    """Length of the message :func:`_plan_parts` lays out, at token pad
    ``T``."""
    return T + (3 + bool(slots)) * rows + math.prod(bt_shape)


def _select_token(logits_row, req):
    """Host-side sampling for one request: greedy at temperature 0, else
    temperature + optional top-k from the request's own seeded RNG (the
    decode batch stays deterministic per request, not per step)."""
    if req.temperature <= 0.0:
        return int(np.argmax(logits_row))
    z = logits_row.astype(np.float64) / max(req.temperature, 1e-6)
    if req.top_k is not None:
        kth = np.partition(z, -int(req.top_k))[-int(req.top_k)]
        z = np.where(z < kth, -np.inf, z)
    z = z - z.max()
    p = np.exp(z)
    p /= p.sum()
    return int(req.rng().choice(len(p), p=p))


class ServingEngine:
    """Continuous-batching inference over a paged KV cache.

    Synchronous use (tests, batch jobs)::

        eng = ServingEngine(model, page_size=16, num_pages=64, max_slots=4)
        tokens = eng.generate([1, 2, 3], max_new_tokens=8)

    Concurrent serving (streaming callbacks + backpressure)::

        with ServingEngine(model, ...) as eng:
            eng.start()
            req = eng.submit(prompt, on_token=lambda r, t, fin: push(t))
            req.result(timeout=30)
    """

    def __init__(self, model, page_size=16, num_pages=64, max_slots=4,
                 max_queue=256, attn_backend=None, mesh=None,
                 mesh_axis="model", jit=True, registry=None,
                 prefill_chunk=None, prefill_token_budget=None,
                 prefix_cache=True, engine_id=None,
                 page_share=None, token_pads=None, emit_logits=False):
        cfg = model.config
        self.model = model
        self.model.eval()
        self.cfg = cfg
        # fleet identity: labels this engine's metric rows (two engines in
        # one job used to collide in one registry family) and names it in
        # the router/registry; None keeps the legacy unlabeled rows
        self.engine_id = engine_id
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_pages = pages_for(cfg.max_seq_len, self.page_size)
        # the model declares what each layer keeps for a token; the pools
        # are allocated from that declaration (GQA pools carry only the KV
        # heads, a latent cache one row for all heads) and its kind picks
        # the attention that reads them
        # Layers that keep the same, as far back, form a page group with
        # its own pages and its own block table a request; ``num_pages``
        # is one count for every group or a dict by group name
        specs = list(model.cache_spec())
        # A layer may keep one state a request instead (held by the
        # request's slot, no pages): the round's message then says which
        # slot each row is
        self.kv = PagedKVCache(specs, num_pages, self.page_size,
                               max_slots=self.max_slots)
        self._attentions = [_attention.for_kind(specs[g.layers[0]])
                            for g in self.kv.groups]
        self._attention = self._attentions[0]
        self._state = _attention.for_kind(specs[self.kv.state_layers[0]]) \
            if self.kv.state_layers else None
        self.num_layers = len(specs)
        # models with keys and values by head say how many KV heads
        self.num_kv_heads = specs[0].rows["k"][0] \
            if "k" in specs[0].rows else None
        # prefix cache: content-addressed page sharing across requests
        # with a common prompt head (hits skip prefill compute AND page
        # writes; pages are refcounted with page-granular copy-on-write).
        # With a fleet PageShareClient attached the trie becomes fleet-
        # wide: a local miss consults the store-published index and
        # imports the hot pages (system prompts prefill once per FLEET)
        if prefix_cache:
            self.kv.require_one_unwindowed_group("the prefix cache")
        if not prefix_cache:
            self.prefix = None
        elif page_share is not None:
            from .fleet.page_share import SharedPrefixCache
            self.prefix = SharedPrefixCache(self.kv, self.page_size,
                                            page_share)
        else:
            self.prefix = PrefixCache(self.kv.allocator, self.page_size)
        self.metrics = ServingMetrics(registry=registry,
                                      prefix_enabled=self.prefix
                                      is not None, engine=engine_id)
        self.metrics.on_cache_spec(self._attention.kind,
                                   sum(self.kv.bytes_per_token()))
        # chunked prefill: split prompts into prefill_chunk-token chunks
        # and interleave at most prefill_token_budget chunk-tokens per
        # scheduler round beside the decode rows — a long prompt arriving
        # mid-stream no longer stalls in-flight decodes (ITL p99 becomes
        # bounded by the budget, not the longest prompt)
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if prefill_token_budget and self.prefill_chunk is None:
            raise ValueError(
                "prefill_token_budget only bounds CHUNKED prefill — pass "
                "prefill_chunk= as well (without it, prompts prefill "
                "whole and the budget would be silently ignored)")
        self._prefill_budget = int(prefill_token_budget) \
            if prefill_token_budget else (self.prefill_chunk or 0)
        self.scheduler = ContinuousBatchingScheduler(
            self.kv.groups, self.max_slots, self.page_size,
            cfg.max_seq_len, max_queue=max_queue,
            prefix_cache=self.prefix, prefill_chunk=self.prefill_chunk)
        self._prefilling: list = []     # FIFO of mid-prefill requests
        # the round's token pads: the power-of-two schedule, or an
        # explicit ladder (every round pads up to its next entry)
        self._token_pads = sorted(int(p) for p in token_pads) \
            if token_pads else None
        self.emit_logits = bool(emit_logits)
        # ---- attention backend (A/B gated; standing kernel rule)
        requested = _ragged.resolve_backend(attn_backend)
        self.attn_ab = self.state_ab = None
        if requested == "auto":
            self.attn_ab = self._run_ab_gate_ragged(self._attention)
            self.attn_backend = self.attn_ab["backend"]
        else:
            self.attn_backend = requested
        # the state layers' recurrence is gated on its own
        self.state_backend = None
        if self._state is not None:
            if requested == "auto":
                self.state_ab = self._run_ab_gate_ragged(self._state)
            self.state_backend = self.state_ab["backend"] \
                if self.state_ab else requested
        if mesh is not None and int(mesh.shape.get(mesh_axis, 1)) > 1:
            self._attention.check_mesh(int(mesh.shape[mesh_axis]),
                                       mesh_axis)
        # what the round's program hands each layer beside its pools:
        # the attention of the layer's page group
        self._attn_impls = [a.impls(self.attn_backend, mesh=mesh,
                                    mesh_axis=mesh_axis)
                            for a in self._attentions]
        self._state_impl = self._state.impls(self.state_backend) \
            if self._state is not None else None
        self._params = list(model.parameters())
        self._param_arrays = [p._data for p in self._params]
        self._jit = bool(jit)
        # the round: ONE callable; jax.jit shape-specializes it per padded
        # total_tokens only. The pads it has served live in
        # _ragged_shapes; every installed program lands in _programs,
        # feeding serving_compiles_total / serving_distinct_programs
        self._ragged_fn = self._build_round()
        _trc.listen_compiles()       # stats()["compile"], always on
        self._ragged_shapes: set = set()
        self._programs: set = set()
        # one host buffer a token pad holds the round's plan (_plan)
        self._plans: dict = {}
        self._steps = 0
        # host-to-device transfers and blocking fetches the rounds made
        self._uploads = 0
        self._fetches = 0
        self._decode_tokens = 0
        self._chunk_tokens = 0
        self.capture_logits = None   # tests: a list collects per-step
        # [S, V] decode logits (forces a host fetch; leave None in prod)
        self._peak_occupancy = 0.0
        self._thread = None
        self._stop_evt = threading.Event()
        self._wake = threading.Event()
        self._closed = False
        self._draining = False
        self._loop_error = None  # terminal serve-loop crash (unhealthy)
        self._shutdown_lock = threading.Lock()
        # serializes actual scheduler rounds: the engine contract is one
        # driving thread, but a SIGTERM drain (watcher thread) can land
        # while a foreground generate()/run_until_idle() is mid-step —
        # without this, two steppers pop the same slot / double-alloc
        # pages. Re-entrant so the serve loop's own step nests freely.
        self._step_lock = threading.RLock()

    # ------------------------------------------------------------ A/B gate
    def _run_ab_gate_ragged(self, attention):
        """Measure XLA vs Pallas at this engine's ragged launch shape
        (a full round: every slot a decode row, padded to the schedule)
        on the pools of the first layer ``attention`` reads; 'auto'
        resolves to the winner (Pallas never wins off-TPU)."""
        layer = next(l for l, spec in enumerate(self.kv.specs)
                     if spec.kind == attention.kind)
        return attention.gate_ragged(
            self.kv.pools[layer], self.max_slots,
            self._pad(self.max_slots + self._prefill_budget),
            self.page_size, self.max_pages, self.cfg.max_seq_len)

    def _pad(self, total):
        """The round's padded token count: the next entry of the explicit
        ladder, or the power-of-two schedule."""
        if self._token_pads is None:
            return _pad_total_tokens(total)
        for p in self._token_pads:
            if p >= total:
                return p
        raise ValueError(f"a round of {total} tokens exceeds the largest "
                         f"token pad {self._token_pads[-1]}")

    def _note_program(self, key):
        """Record the installation of a new shape-specialized program
        (one a token pad) — the bounded-compile contract as a measured
        number."""
        if key in self._programs:
            return
        self._programs.add(key)
        self.metrics.on_compile(len(self._programs))

    # -------------------------------------------------------- ragged round
    def _layer_caches(self, pools, bt, row_slots=None, **shared):
        """One cache dict a layer: its own pools (as Tensors, by the row
        names it declared), its page group's block table (``bt`` is the
        one table of a one-group model, else one a group, stacked) and
        attention, or for a layer that keeps a state a request the rows'
        slots and the recurrence, beside what the whole round shares."""
        tables = [Tensor(bt)] if len(self.kv.groups) == 1 \
            else [Tensor(bt[g]) for g in range(len(self.kv.groups))]
        by_group = [dict(shared, block_tables=t, attn_impl=impl)
                    for t, impl in zip(tables, self._attn_impls)]
        # group None: a layer that keeps a state a request
        by_group.append(None if row_slots is None else dict(
            shared, row_slots=Tensor(row_slots), attn_impl=self._state_impl))
        return [dict(by_group[-1 if g is None else g],
                     pools={n: Tensor(a) for n, a in p.items()})
                for p, g in zip(pools, self.kv.group_of)]

    @staticmethod
    def _pools_out(caches):
        return [{n: t._data for n, t in c["pools"].items()} for c in caches]

    def _ragged_body(self):
        """The whole scheduler round as one plain function of seven
        arguments (and ``row_slots`` for a model with a state a request):
        embed the flat token stream at per-token positions,
        scatter every row's K/V into its pages, run ragged paged
        attention, and hand back one next-token + logit row per batch row
        (the row's LAST valid token's logits — a decode row's next token,
        a completing prefill row's first token). Params are real
        arguments (no giant closure constants)."""
        model, params = self.model, self._params
        emit_logits = self.emit_logits
        from ..ops.pallas.ragged_attention import ragged_row_index

        def rstep(arrays, tokens, row_starts, row_lens, kv_lens, bt,
                  pools, row_slots=None):
            if row_slots is None and self._state is not None:
                raise ValueError("the model keeps a state a request: the "
                                 "round needs row_slots, each row's slot")
            with no_grad(), _swap_params(params, arrays):
                T = tokens.shape[0]
                _, pos, valid = ragged_row_index(row_starts, row_lens,
                                                 kv_lens, T)
                positions = jnp.where(valid, pos, 0).astype(jnp.int32)
                caches = self._layer_caches(
                    pools, bt, row_slots, ragged=True,
                    row_starts=Tensor(row_starts),
                    row_lens=Tensor(row_lens), kv_lens=Tensor(kv_lens))
                logits = model(Tensor(tokens[None, :]), caches=caches,
                               pos_offset=Tensor(positions[None, :]))
                # each row's last valid token carries the round's output
                # logit; unused rows clip to garbage the host ignores
                last = jnp.clip(row_starts + row_lens - 1, 0, T - 1)
                row_logits = logits._data[0, last]
                nxt = jnp.argmax(row_logits, axis=-1).astype(jnp.int32)
                # what the layers report of this round beside the tokens
                # (small arrays under cache["aux"], e.g. a router's
                # loads), and the emitted token's own logit where asked:
                # part of the program whether tracing is on or off
                aux = [c["aux"] for c in caches if "aux" in c]
                extras = {"aux": {
                    name: jnp.stack([a[name] for a in aux if name in a])
                    for name in sorted({n for a in aux for n in a})}}
                if emit_logits:
                    extras["top"] = jnp.max(row_logits, axis=-1)
                return nxt, row_logits, self._pools_out(caches), extras

        return rstep

    def _build_ragged_step(self):
        """The round's body as a program of its own, with the seven
        arguments ``(arrays, tokens, row_starts, row_lens, kv_lens, bt,
        pools)``: what the compile tools and tests lower. The engine
        calls :meth:`_build_round`'s entry, which holds the same body."""
        return self._jitted(self._ragged_body(), donate=6)

    def _jitted(self, fn, donate):
        """``fn`` as this engine runs it: jitted unless the engine was
        built with ``jit=False``, the pools (argument ``donate``) donated
        where that works."""
        if not self._jit:
            return fn
        # donation saves the pool double-buffer on TPU; CPU/older
        # backends warn and ignore it, so only ask where it works
        if _ragged.on_tpu():
            return jax.jit(fn, donate_argnums=(donate,))
        return jax.jit(fn)

    def _build_round(self):
        """The entry a round calls, ``(arrays, message, pools)``: a thin
        wrapper around :meth:`_ragged_body` in ONE program, jitted once
        and specialized per padded total_tokens ONLY. (Around the plain
        body, not a jitted one: a jit inside a jit tripled the time a
        pad's lowering takes at 24 layers.) The plan arrives as one int32
        message (one upload) and is taken apart by static slices
        (:func:`_plan_parts`); what the host reads every round leaves as
        one int32 array (one fetch): the next tokens and, in an
        ``emit_logits`` engine, their logits bit-cast beside them (the
        host views them back as float32). -> ``(out, row_logits, pools,
        aux)``."""
        step = self._ragged_body()
        R, bt_shape = self.max_slots, self._bt_shape()
        slots = self._state is not None

        def round_step(arrays, message, pools):
            *plan, bt = _plan_parts(message, R, bt_shape, slots)
            out, row_logits, pools, extras = step(
                arrays, *plan[:4], bt, pools, *plan[4:])
            if "top" in extras:
                out = jnp.concatenate([out, jax.lax.bitcast_convert_type(
                    extras["top"].astype(jnp.float32), jnp.int32)])
            return out, row_logits, pools, extras["aux"]

        return self._jitted(round_step, donate=2)

    def _plan(self, T):
        """The host's buffer for the plan of a round at token pad ``T``,
        reset to a round of no rows (every token padding, unused rows at
        the sentinel ``T``): one a pad, kept, so a round allocates nothing.
        -> ``(message, its parts as views)``: a row's slot, where the
        message carries it, starts at the scrap slot 0."""
        plan = self._plans.get(T)
        if plan is None:
            R, bt_shape = self.max_slots, self._bt_shape()
            slots = self._state is not None
            message = np.empty(_message_len(T, R, bt_shape, slots),
                               np.int32)
            plan = self._plans[T] = (
                message, _plan_parts(message, R, bt_shape, slots))
        message, parts = plan
        message[:] = 0
        parts[1][:] = T
        return plan

    def warm_ragged(self, max_tokens=None):
        """Pre-compile the ragged program at every token pad up to
        ``max_tokens``. A pad first seen mid-run costs one XLA compile
        inside a serving round — an ITL spike the schedule makes rare
        but warmup makes impossible. The default covers the engine's
        true worst-case round: every slot decoding plus one prefill
        budget of chunk tokens when chunking is on, or every slot
        carrying a whole max-length prompt when it is off (unchunked
        engines serving known-short prompts should pass a tighter
        ``max_tokens`` rather than compile the full ladder). The warm
        launches carry zero valid rows: every token is padding, so the
        writes land on the reserved scrap page and no request state is
        touched. Serialized against concurrent rounds — the launches
        consume (and on TPU donate) the live pools. -> the list of pads
        compiled."""
        if max_tokens is None:
            if self.prefill_chunk is not None:
                # a round carries max(1, budget // chunk) prefill rows of
                # up to chunk tokens EACH — with budget < chunk that one
                # row still takes a whole chunk, so the worst case is the
                # row count times the chunk, not the budget itself
                rows = max(1, self._prefill_budget // self.prefill_chunk)
                max_tokens = self.max_slots + rows * self.prefill_chunk
            else:
                max_tokens = self.max_slots * self.cfg.max_seq_len
        max_tokens = min(int(max_tokens),
                         self.max_slots * self.cfg.max_seq_len)
        pads, t = [], 1
        while True:
            p = self._pad(t)
            pads.append(p)
            if p >= max_tokens:
                break
            t = p + 1
        with self._step_lock:
            for p in pads:
                if p in self._ragged_shapes:
                    continue
                self._ragged_shapes.add(p)
                self._note_program(("ragged", p))
                _, _, self.kv.pools, _ = self._ragged_fn(
                    self._param_arrays, jax.device_put(self._plan(p)[0]),
                    self.kv.pools)
        return pads

    def _bt_shape(self):
        """The round's block tables: ``[rows, max_pages]`` of a one-group
        model, one such a group stacked otherwise."""
        G = len(self.kv.groups)
        return (self.max_slots, self.max_pages) if G == 1 \
            else (G, self.max_slots, self.max_pages)

    def _step_ragged(self, turn=None):
        """One scheduler round: admit, grow/evict, then assemble decode
        rows + prefill chunks (budget-bounded FIFO: a row advances by one
        chunk a round and emits its first token in the round that
        completes its prompt) into ONE flat launch, then the round's
        accounting. -> decode tokens emitted. ``turn`` is the serve
        loop's open ``serve.turn`` (tracing on): it closes where the round
        opens and opens again where the round closes."""
        # the ONE tracing gate of the round (standing contract: off =
        # one check, no allocation, no call). On, the round and its six
        # phases are spans in the buffer and annotations on the
        # profiler's clock (observability/tracing.py lists them)
        tr = _trc._TR if _trc._loaded else _trc._load()
        rnd = ph = None
        if tr is not None:
            if turn is not None and turn.buf is not tr:
                turn = None           # tracing was restarted under the loop
            rnd = turn.then("decode_round", round=self._steps) \
                if turn is not None else \
                _trc.phase(tr, "decode_round", round=self._steps).open()
            ph = rnd.inner("round.schedule")
        # pages that slid out of a windowed group's window since the last
        # round go back first: this round's admissions may take them
        freed = self.scheduler.release_slid_pages()
        admitted = self.scheduler.schedule()
        for req in admitted:
            self.metrics.on_admit(req)
            req.state = "prefilling"
            self._prefilling.append(req)
        # prefill rows: FIFO, at most budget // chunk rows per round each
        # contributing one chunk (ITL stays bounded by the budget);
        # unchunked mode takes every pending row's whole remaining tail
        if self.prefill_chunk is not None:
            n_rows = max(1, self._prefill_budget // self.prefill_chunk)
            prefill_rows = self._prefilling[:n_rows]
        else:
            prefill_rows = list(self._prefilling)
        chunks, prompts = [], {}
        for req in prefill_rows:
            p = req.effective_prompt()
            prompts[req.request_id] = p
            take = len(p) - req.num_cached
            if self.prefill_chunk is not None:
                take = min(take, self.prefill_chunk)
            chunks.append((req, take))
        _, evicted = self.scheduler.ensure_decode_capacity(chunks)
        for req in evicted:
            self.metrics.on_evict(req)
        self._prefilling = [r for r in self._prefilling
                            if r.state == "prefilling"]
        if ph is not None:
            ph = ph.then("round.assemble")
        decode_rows = sorted(
            (r for r in self.scheduler.active.values()
             if r.state == "active"), key=lambda r: r.slot)
        plan = [(req, 1, req.generated[-1:]) for req in decode_rows]
        for req, take in chunks:
            if req.state == "prefilling":
                p = prompts[req.request_id]
                plan.append((req, take,
                             p[req.num_cached:req.num_cached + take]))
        if not plan:
            return self._end_round(rnd, ph, turn, 0)
        total = sum(take for _, take, _ in plan)
        T = self._pad(total)
        message, parts = self._plan(T)
        tokens, row_starts, row_lens, kv_lens = parts[:4]
        bt = parts[-1]
        tables = bt[None] if bt.ndim == 2 else bt       # one a page group
        cursor = 0
        for i, (req, take, seg) in enumerate(plan):
            tokens[cursor:cursor + take] = seg
            row_starts[i] = cursor
            row_lens[i] = take
            kv_lens[i] = req.num_cached + take
            for table, pages in zip(tables, req.group_pages):
                table[i, :len(pages)] = pages
            cursor += take
        if self._state is not None:
            # the state pools' index of each row's request: its slot, past
            # the scrap slot. A row at the start of its context
            # (kv_len == row_len) starts from zero whatever the slot held
            parts[4][:len(plan)] = [req.slot + 1 for req, _, _ in plan]
        if T not in self._ragged_shapes:
            self._ragged_shapes.add(T)
            self._note_program(("ragged", T))
        if rnd is not None:
            n = len(plan)
            # rows of the cache a layer of each page group has to read
            # this round, under the name of what it keeps (``kv_rows`` /
            # ``latent_rows`` / ``window_rows``)
            rows_read = {}
            for a in self._attentions:
                name, rows = a.rows_read(row_lens[:n], kv_lens[:n])
                rows_read[name] = rows_read.get(name, 0) + rows
            if self._state is not None:
                # ... and the states it reads and writes: one a row and
                # state layer
                name, rows = self._state.rows_read(row_lens[:n],
                                                   kv_lens[:n])
                layers = len(self.kv.state_layers)
                rows_read[name] = rows * layers
                # ... and the tokens of its rows of several tokens
                # (prompt chunks), a state layer each
                rows_read["state_chunk_tokens"] = \
                    self._state.chunk_tokens(row_lens[:n]) * layers
            rnd.set(pad=T, tokens=total, row_lens=row_lens[:n].tolist(),
                    kv_lens=kv_lens[:n].tolist(), **rows_read)
            if freed:
                tr.add("cache.window_release", rnd.t0, 0.0, cat="serving",
                       args={"round": self._steps, "pages": freed})
            ph = ph.then("round.launch")
        # the round crosses to the device once: the plan as ONE message
        out, row_logits, self.kv.pools, aux = self._ragged_fn(
            self._param_arrays, jax.device_put(message), self.kv.pools)
        self._uploads += 1
        # ... and back once: the tokens' copy is asked for now, behind the
        # program, and the round blocks on it in round.fetch
        out.copy_to_host_async()
        completing = [req for req, take, _ in plan[len(decode_rows):]
                      if req.num_cached + take
                      >= len(prompts[req.request_id])]
        any_sampling = any(r.temperature > 0.0
                           for r in decode_rows + completing)
        # what a round reads only sometimes rides the same fetch: the
        # logit rows when a request samples (or a test captures them),
        # what the layers reported when tracing is on (never fetched off)
        fetch = [out,
                 row_logits if any_sampling
                 or self.capture_logits is not None else None,
                 aux if rnd is not None else None]
        if ph is not None:
            ph.set(uploads=1)
            ph = ph.then("round.fetch")
        # tpu-lint: ok[HS001] designed sync: ONE batched fetch per ragged round (tokens, their logits, and the logit rows / the layers' reports when asked) feeds host-side scheduling/sampling
        out, logits_np, aux = jax.device_get(fetch)
        self._fetches += 1
        R = self.max_slots
        nxt = out[:R]
        top = out[R:].view(np.float32) if self.emit_logits else None
        if rnd is not None:
            ph.set(fetches=1)
            # what the layers reported of this round (one event a name,
            # one entry a reporting layer)
            for name, arr in aux.items():
                tr.add(name, rnd.t0, 0.0, cat="serving",
                       args={"round": self._steps, "layers": arr.tolist()})
            ph = ph.then("round.emit")
        if self.capture_logits is not None and decode_rows:
            cap = np.zeros((self.max_slots,) + logits_np.shape[1:],
                           logits_np.dtype)
            for i, req in enumerate(decode_rows):
                cap[req.slot] = logits_np[i]
            self.capture_logits.append(
                (dict((r.slot, r.request_id) for r in decode_rows), cap))
        # decode rows: account through the scheduler (num_cached
        # advance, emit, finish)
        by_slot = {}
        for i, req in enumerate(decode_rows):
            if req.temperature > 0.0:
                by_slot[req.slot] = _select_token(logits_np[i], req)
            else:
                by_slot[req.slot] = int(nxt[i])
        # a sampled token's logit is not the row's top: emitted for greedy
        # rows only
        finished = self.scheduler.complete_step(
            by_slot, None if top is None else
            {req.slot: top[i] for i, req in enumerate(decode_rows)
             if req.temperature <= 0.0})
        for req in decode_rows:
            tt = req.token_times
            self.metrics.on_token(
                req, tt[-1] - tt[-2] if len(tt) >= 2 else None)
        for req in finished:
            self.metrics.on_finish(req)
        # prefill rows: advance the cursor; a row whose prompt completed
        # emits its first token this round (TTFT ends here) and decodes
        # as a decode row from the NEXT round on
        spent = 0
        for j, (req, take, _) in enumerate(plan[len(decode_rows):]):
            i = len(decode_rows) + j
            prompt = prompts[req.request_id]
            req.num_cached += take
            spent += take
            if req.num_cached < len(prompt):
                continue
            tok = _select_token(logits_np[i], req) \
                if req.temperature > 0.0 else int(nxt[i])
            self._finish_prompt(
                req, prompt, tok,
                top[i] if top is not None and req.temperature <= 0.0
                else None)
        if spent:
            self._chunk_tokens += spent
            self.metrics.on_prefill_chunk(spent)
        if rnd is not None:
            # engine-lane round span: batched, ONE per round, row counts
            # in args (the waterfall's decode cadence)
            rnd.set(decode_rows=len(by_slot),
                    prefill_rows=len(plan) - len(decode_rows),
                    prefill_tokens=spent)
            t0, now = rnd.t0, time.time()
            for req, take, _ in plan[len(decode_rows):]:
                if req.trace is not None:
                    _trc.req_event(req.trace, "prefill_chunk", t0,
                                   now - t0,
                                   args={"tokens": take,
                                         "cached": req.num_cached})
        self._decode_tokens += len(by_slot)
        return self._end_round(rnd, ph, turn, len(by_slot))

    def _end_round(self, rnd, ph, turn, emitted):
        """What every round ends with, one that launched nothing too: the
        engine's accounting (``round.account`` where tracing is on), then
        the round's close, where the serve loop's ``turn`` opens again.
        -> ``emitted``."""
        if rnd is not None:
            ph = ph.then("round.account")
        occ = self.kv.occupancy_pct()
        self._peak_occupancy = max(self._peak_occupancy, occ)
        for group, unreleased in zip(
                self.kv.groups, self.scheduler.unreleased_pages()):
            group.note(unreleased)
        alloc = self.kv.allocator
        share = getattr(self.prefix, "share", None)
        self.metrics.sample_state(
            len(self.scheduler.active), self.scheduler.queue_depth(),
            occ,
            shared_pages=alloc.shared_pages() if self.prefix else None,
            cached_pages=alloc.cached_pages if self.prefix else None,
            remote_hits=share.remote_hits if share else None,
            remote_hit_tokens=share.remote_hit_tokens
            if share else None)
        self._steps += 1
        if rnd is not None:
            at = rnd.close(ph.close())
            if turn is not None:
                turn.open(at)
        return emitted

    # ------------------------------------------------------------- prefill
    def _finish_prompt(self, req, prompt, tok, logit=None):
        """Prompt-completion protocol: emit the first generated token (TTFT
        ends here), flip the row to decoding, index the PRE-emit
        prompt's pages for prefix sharing, and finish if the budget is
        already met. ``prompt`` MUST be the pre-emit prompt:
        ``effective_prompt()`` after emit includes the generated token,
        whose KV is only written by the NEXT decode step — indexing it
        would let a (prompt+1)-page-multiple request publish a page with
        an unwritten slot (garbage KV for any future hit if this request
        finishes or evicts before that step runs)."""
        first = not req.generated
        req.emit(tok, logit)
        if first:
            self.metrics.on_first_token(req)
            if req.trace is not None:
                _trc.req_event(req.trace, "first_token", time.time(), 0.0,
                               args={"ttft_ms": (req.t_first_token -
                                                 req.t_submit) * 1e3})
        self.metrics.on_token(req)
        req.state = "active"
        if req in self._prefilling:
            self._prefilling.remove(req)
        if self.prefix is not None:
            self.prefix.insert(prompt, req.pages)
        if req.hit_stop():
            self.scheduler.finish(req)
            self.metrics.on_finish(req)
            return
        hook = req.migrate_hook
        if hook is not None:
            # prefill/decode disaggregation (fleet): the prompt is done
            # but the budget has more to go — hand the request (and its
            # KV pages) to a decode-designated engine. The hook owns the
            # release/adopt; True means the request left this engine. A
            # failed hook degrades gracefully: the row keeps decoding
            # here, never stranding the caller.
            try:
                if hook(self, req):
                    self.metrics.on_migrate_out(req)
            except Exception as e:
                print(f"[serving] migrate hook failed for request "
                      f"{req.request_id}: {type(e).__name__}: {e} — "
                      "decoding locally", file=sys.stderr, flush=True)

    # ------------------------------------------------------------ stepping
    def step(self, _turn=None):
        """One scheduler round -> decode tokens emitted (0 when idle):
        admission, budgeted prefill chunks and every active row's decode
        token ride ONE flat launch of one program. A newcomer prefilling
        never stalls in-flight rows — the gap between two decode tokens
        is bounded by the chunk budget, not by the longest prompt in the
        queue. (``_turn`` is the serve loop's own: its open
        ``serve.turn`` span.)"""
        if self._loop_error is not None:
            raise EngineClosed(
                f"engine unhealthy: serve loop crashed with "
                f"{type(self._loop_error).__name__}: {self._loop_error}"
            ) from self._loop_error
        if self._closed:
            raise EngineClosed("engine is closed")
        with self._step_lock:
            return self._step_ragged(_turn)

    def run_until_idle(self, max_steps=100000):
        steps = 0
        while self.scheduler.has_work():
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"run_until_idle exceeded {max_steps} steps")
        return steps

    # ------------------------------------------------------------- serving
    def submit(self, prompt_ids, max_new_tokens=16, eos_token_id=None,
               temperature=0.0, top_k=None, on_token=None, block=True,
               timeout=10.0, on_done=None):
        """Queue one request (backpressure: blocks up to ``timeout`` for
        queue space, then raises :class:`~.scheduler.QueueFull`)."""
        req = GenerationRequest(prompt_ids, max_new_tokens=max_new_tokens,
                                eos_token_id=eos_token_id,
                                temperature=temperature, top_k=top_k,
                                on_token=on_token, on_done=on_done)
        return self.submit_request(req, block=block, timeout=timeout)

    def _check_accepting(self):
        if self._draining:
            raise EngineShuttingDown("engine is shutting down")
        if self._loop_error is not None:
            raise EngineClosed(
                f"engine unhealthy: serve loop crashed with "
                f"{type(self._loop_error).__name__}: {self._loop_error}"
            ) from self._loop_error
        if self._closed:
            raise EngineClosed("engine is closed")

    def submit_request(self, req, block=True, timeout=10.0):
        """Queue an already-built :class:`~.scheduler.GenerationRequest`
        (the fleet router builds its own legs so it can wire ``on_done``
        re-dispatch before the engine ever sees them)."""
        self._check_accepting()
        self.scheduler.submit(req, block=block, timeout=timeout)
        self._wake.set()
        return req

    # --------------------------------------------------- fleet migration
    def snapshot_kv(self, req):
        """Host copy of one request's cached state (``req.num_cached``
        tokens): ``(layers, length)`` with each layer a dict of the rows
        it declared, ``name -> [length, *row_shape]`` numpy array.
        Read-only on the pools (shared prefix pages included), serialized
        against rounds — the page migration payload of the disaggregated
        fleet."""
        self.kv.require_one_unwindowed_group("page migration")
        with self._step_lock:
            length = int(req.num_cached)
            # tpu-lint: ok[HS002] page migration IS the designed host roundtrip: one gather per pool moves this request's state off-device
            layers = [{n: np.asarray(self.kv.gather(l, req.pages, length, n))
                       for n in spec.rows}
                      for l, spec in enumerate(self.kv.specs)]
        return layers, length

    def release_request(self, req):
        """Detach a migrating request from this engine: free its slot and
        pages (a deref — shared prefix pages keep their other readers)
        WITHOUT finishing it. The caller adopts it elsewhere."""
        with self._step_lock:
            self.scheduler.release_for_migration(req)
            if req in self._prefilling:
                self._prefilling.remove(req)

    def adopt_request(self, req, layers, length):
        """Admit a migrated request with its KV pages pre-populated: the
        block-table rebind half of fleet page migration. Allocates pages
        for ``length`` tokens, writes the payload into this engine's
        pools, and joins the decode batch directly — the continuation
        consumes ``req.generated[-1]`` at position ``length``, exactly
        the step the source engine would have run next. Raises
        :class:`~.kv_cache.OutOfPages` / :class:`~.scheduler.OutOfSlots`
        when this pool/batch cannot take it (caller falls back to
        :meth:`readmit_request`)."""
        self.kv.require_one_unwindowed_group("page migration")
        with self._step_lock:
            self._check_accepting()
            pages = self.kv.allocator.alloc(
                max(1, pages_for(length, self.page_size)))
            try:
                for layer, rows in enumerate(layers):
                    self.kv.write_rows(layer, rows, pages, length)
                req.pages = pages
                req.num_cached = int(length)
                self.scheduler.admit_prepared(req)
            except Exception:
                self.kv.allocator.free(pages)
                req.pages = []
                raise
            self.metrics.on_adopt(req)
        self._wake.set()
        return req

    def readmit_request(self, req):
        """Recompute fallback for a migrated request: re-queue it at the
        front — admission re-prefills ``effective_prompt()`` (greedy
        continuation is token-identical, same contract as eviction)."""
        self._check_accepting()
        self.scheduler.readmit(req)
        self._wake.set()
        return req

    def abort_request(self, req):
        """Cancel one leg without firing its waiters (ISSUE 16 hedging:
        the router duplicated this request on another engine and the
        duplicate won — the loser's slot + pages free immediately, its
        ``on_done`` never fires, and the winning leg owns the caller's
        done event). Serialized against rounds so a mid-step slot/page
        assignment can never be torn. Returns False when the leg already
        reached a terminal state first (it finished fair and square —
        its completion is the one the router keeps)."""
        with self._step_lock:
            if not self.scheduler.abort_request(req):
                return False
            if req in self._prefilling:
                self._prefilling.remove(req)
        return True

    def prefetch_prefix(self, tokens):
        """Warm this engine's prefix cache with a prompt head published
        elsewhere in the fleet (router prefetch-on-affinity-spill): walk
        the shared trie, import the remote pages into the LOCAL pool,
        then drop the lookup references so the pages park indexed +
        reclaimable — the session's next request here prefix-hits
        locally instead of paying the import on its admission path.
        -> number of pages imported (0 without a share client)."""
        share = getattr(self.prefix, "share", None)
        if share is None or self._closed or self._draining:
            return 0
        with self._step_lock:
            t0 = share.remote_hit_tokens
            # tpu-lint: ok[LK002] the store fetch is bounded by the share client's fetch timeout and the lock is required: lookup mutates allocator refcounts and imports pages into the pools, exactly like the admission-path lookup step() runs under this same lock
            pages, _n = self.prefix.lookup(tokens)
            if pages:
                # lookup took one reader ref per page for an admission
                # that is not happening — release them; the trie keeps
                # the pages indexed (reclaimable, hit-ready)
                self.kv.allocator.free(pages)
            imported = (share.remote_hit_tokens - t0) // self.page_size
        if imported:
            self.metrics.on_prefetch_pages(imported)
        return imported

    def generate(self, prompt_ids, timeout=120.0, **kw):
        """Synchronous helper: submit + drive (foreground when no serve
        thread is running) + wait. -> generated token list."""
        req = self.submit(prompt_ids, **kw)
        if self._thread is None:
            self.run_until_idle()
        return req.result(timeout=timeout)

    def start(self):
        """Background serve loop (idempotent)."""
        if self._thread is not None:
            return
        self._stop_evt.clear()
        self._thread = threading.Thread(target=self._serve_loop,
                                        name="paddle-tpu-serving",
                                        daemon=True)
        self._thread.start()

    def _serve_loop(self):
        from ..distributed.fault import maybe_inject as _inject
        # serving chaos (ISSUE 16): PADDLE_TPU_FAULT_ENGINE narrows the
        # serve_loop site to ONE engine id so a multi-engine process
        # kills a chosen replica deterministically (the trigger counter
        # is process-global; without the filter, whichever serve thread
        # hit the site Nth would die)
        target = os.environ.get("PADDLE_TPU_FAULT_ENGINE")
        honored = target in (None, "") or target == str(self.engine_id)
        # tracing on, the thread's time is tiled: every instant lies in a
        # ``decode_round``, a ``serve.idle_wait`` or the ``serve.turn``
        # between them, which the round closes and opens again itself
        turn = None
        while not self._stop_evt.is_set():
            try:
                # the loop's one tracing gate a turn, as the round's
                tr = _trc._TR if _trc._loaded else _trc._load()
                if tr is None:
                    turn = None
                elif turn is None or turn.buf is not tr:
                    turn = _trc.phase(tr, "serve.turn").open()
                if honored and _inject("serve_loop") == "engine_die":
                    raise RuntimeError(
                        "injected fault: engine_die@serve_loop")
                if self.scheduler.has_work():
                    self.step(turn)
                else:
                    if tr is None:
                        self._wake.wait(0.02)
                    else:
                        idle = turn.then("serve.idle_wait")
                        self._wake.wait(0.02)
                        turn.open(idle.close())
                    self._wake.clear()
            except Exception as e:
                # a broken step is terminal, not a silent hang: fail every
                # queued + in-flight waiter with the ACTUAL error and mark
                # the engine unhealthy so later submit()s fail fast naming
                # it (graceful degradation — callers can route elsewhere)
                self._loop_error = e
                self._closed = True
                self.scheduler.close(error=e)
                print(f"[serving] serve loop crashed; engine unhealthy: "
                      f"{type(e).__name__}: {e}", file=sys.stderr,
                      flush=True)
                break
        if turn is not None and turn.t0 is not None:
            turn.close()

    def stop(self, timeout=10.0):
        self._stop_evt.set()
        self._wake.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout)

    def close(self):
        """Stop the loop and fail everything still queued or in flight —
        same contract as ``BatchingPredictor.close``."""
        if self._closed:
            return
        self._closed = True
        self.stop()
        self.scheduler.close()

    # -------------------------------------------------- graceful shutdown
    def shutdown(self, drain_s=30.0):
        """SIGTERM-grade graceful shutdown (ISSUE 10 satellite), the
        serving twin of the training tier's exit-75 preemption save:

        1. stop admitting — later ``submit``\\ s and every QUEUED request
           fail with the named :class:`~.scheduler.EngineShuttingDown`
           status (they never started; safe to retry elsewhere), not the
           indiscriminate bare close;
        2. drain in-flight decodes up to ``drain_s`` seconds — requests
           mid-generation finish normally;
        3. fail whatever missed the deadline, then flush the serving
           metrics JSONL so the final counters land on disk before exit.

        Idempotent; returns a summary dict. ``close()`` afterwards is a
        no-op. Serialized: a concurrent second call (the SIGTERM watcher
        racing a user-initiated shutdown) blocks until the in-progress
        drain finishes, then sees ``_closed`` and returns the empty
        summary — two threads must never both drive ``step()``."""
        with self._shutdown_lock:
            return self._shutdown_locked(drain_s)

    def _shutdown_locked(self, drain_s):
        if self._closed:
            return {"drained_tokens": 0, "failed_queued": 0,
                    "failed_inflight": 0}
        self._draining = True
        self.stop()  # join the serve loop; we drive the drain inline
        queued = self.scheduler.begin_shutdown()
        for req in queued:
            # rejected-at-queue is a terminal state too: the flushed
            # counters must show these requests, not a clean drain
            self.metrics.on_finish(req)
        deadline = time.monotonic() + max(0.0, float(drain_s))
        drained = 0
        # drain on has_work, not just active: KV pressure can EVICT an
        # in-flight request back onto the waiting queue mid-drain, and it
        # deserves its remaining budget (schedule() re-admits it — the
        # shutdown gate closed submit(), not the internal readmit path)
        while self.scheduler.has_work() and time.monotonic() < deadline:
            drained += self.step()
        missed = [r for r in self.scheduler.active.values()
                  if r.state in ("active", "prefilling")]
        # evicted mid-drain and never re-admitted: close out the pending
        # queue-wait segment (same honesty rule as begin_shutdown) before
        # close() stamps them failed
        now = time.perf_counter()
        for req in self.scheduler.waiting:
            req.queue_wait_s += now - req.t_enqueue
        missed += list(self.scheduler.waiting)
        self._closed = True
        self.scheduler.close(error=EngineShuttingDown(
            f"engine shut down before this request finished "
            f"(drain deadline {drain_s:.0f}s)"))
        for req in missed:
            self.metrics.on_finish(req)
        reg = self.metrics._reg
        if reg is not None:
            try:
                reg.flush()
            except Exception:
                pass
        out = {"drained_tokens": drained, "failed_queued": len(queued),
               "failed_inflight": len(missed)}
        print(f"[serving] graceful shutdown: {out}", flush=True)
        return out

    def install_sigterm(self, drain_s=None):
        """Wire SIGTERM to the training-tier convention: graceful drain
        (:meth:`shutdown`), then exit ``EXIT_PREEMPT`` (75) so the same
        launcher/orchestrator policy that resumes preempted trainers
        treats a drained server as resumable, not failed. ``drain_s``
        defaults to ``PADDLE_TPU_SERVING_DRAIN_S`` (30). Returns True if
        the handler was installed (main thread only).

        The handler itself only sets the preemption flag (the fault
        module's safe flag-only mode); the drain runs on a dedicated
        watcher thread. Running ``shutdown()`` inside the signal frame
        would self-deadlock if SIGTERM lands while the interrupted main
        thread holds the scheduler's (non-reentrant) admission lock —
        the exact hazard ``install_preemption_handler``'s docstring
        names for mid-collective saves."""
        from ..distributed import fault as _fault
        if drain_s is None:
            drain_s = float(os.environ.get(
                "PADDLE_TPU_SERVING_DRAIN_S", "30"))
        if not _fault.install_preemption_handler():
            return False

        def _watch():
            while not self._closed:
                if _fault.preempted():
                    # the exit must happen even if the drain raises (a
                    # racing close(), a decode error): a dead watcher
                    # thread would swallow the SIGTERM entirely and the
                    # orchestrator's grace window would end in SIGKILL
                    # with no metrics flush and no exit-75 classification
                    try:
                        self.shutdown(drain_s=drain_s)
                    finally:
                        sys.stdout.flush()
                        sys.stderr.flush()
                        os._exit(_fault.EXIT_PREEMPT)
                time.sleep(0.1)

        threading.Thread(target=_watch, daemon=True,
                         name="serving-sigterm-drain").start()
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # --------------------------------------------------------------- stats
    def compiled_text(self, total_tokens=None):
        """Optimized-HLO text of the ragged round program at one token
        pad (default: the smallest pad served so far) — the serving twin
        of ``StaticFunction.compiled_text``: a caller can assert which
        attention the round really compiled (``tpu_custom_call`` for the
        Pallas kernel) instead of trusting ``attn_backend``."""
        if not self._jit:
            raise RuntimeError(
                "compiled_text() reads the jitted round program; this "
                "engine runs un-jitted")
        if total_tokens is None:
            if not self._ragged_shapes:
                raise RuntimeError(
                    "no ragged round has run yet — call warm_ragged() or "
                    "step() before compiled_text()")
            total_tokens = min(self._ragged_shapes)
        from ..jit.api import _aval
        return self._ragged_fn.lower(
            [_aval(a) for a in self._param_arrays],
            jax.ShapeDtypeStruct((_message_len(
                int(total_tokens), self.max_slots, self._bt_shape(),
                self._state is not None),), jnp.int32),
            jax.tree_util.tree_map(_aval, self.kv.pools)
            ).compile().as_text()

    def stats(self):
        out = {
            "engine_id": self.engine_id,
            "steps": self._steps,
            "decode_tokens": self._decode_tokens,
            "evictions": self.scheduler.total_evictions,
            "kv_occupancy_pct": round(self.kv.occupancy_pct(), 2),
            "kv_occupancy_peak_pct": round(self._peak_occupancy, 2),
            "active": len(self.scheduler.active),
            "queued": self.scheduler.queue_depth(),
            "attn_backend": self.attn_backend,
            "attn_ab": self.attn_ab,
            "num_kv_heads": self.num_kv_heads,
            # the state declaration: what kind of rows a layer keeps for
            # a token, and the bytes they take in each layer
            "cache_kind": self._attention.kind,
            "cache_bytes_per_token_layer": self.kv.bytes_per_token(),
            "cache_pool_bytes_per_token_layer":
                self.kv.bytes_per_token(padded=True),
            # by page group: its pages, those held now and at the fullest
            # (beside what the same requests' tables spanned then) and
            # those a windowed group gave back as they slid out
            "page_groups": {g.name: g.stats() for g in self.kv.groups},
            # what the layers that keep a state a request hold: a slot is
            # one request's states over those layers, whatever its length
            "state": None if self._state is None else {
                "layers": len(self.kv.state_layers),
                "slots": self.kv.state_slots,
                "bytes_per_slot": self.kv.state_bytes_per_slot(),
                "bytes": (self.kv.state_slots + 1)
                * self.kv.state_bytes_per_slot(),
                "backend": self.state_backend, "ab": self.state_ab},
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunk_tokens": self._chunk_tokens,
            "distinct_programs": len(self._programs),
            "ragged_token_pads": sorted(self._ragged_shapes),
            # what the rounds ("steps", those that launched) moved across
            # the host-device boundary: one upload and one blocking fetch
            # each, so either over the rounds reads 1.0
            "round_uploads": self._uploads,
            "round_fetches": self._fetches,
            # seconds this process has spent tracing, lowering and
            # compiling (cache loads included) since its compile log began
            # (observability/tracing.py compile_log), and the events
            "compile": _trc.compile_totals(),
        }
        if self.prefix is not None:
            out.update({
                "prefix_hits": self.prefix.hits,
                "prefix_misses": self.prefix.misses,
                "prefix_hit_rate": round(self.prefix.hit_rate(), 4),
                "prefix_hit_tokens": self.prefix.hit_tokens,
                "prefix_cached_pages": self.kv.allocator.cached_pages,
                "prefix_shared_pages": self.kv.allocator.shared_pages(),
                "prefix_reclaimed_pages": self.prefix.reclaimed_pages,
            })
            share = getattr(self.prefix, "share", None)
            if share is not None:
                out.update({
                    "prefix_remote_hits": share.remote_hits,
                    "prefix_remote_hit_tokens": share.remote_hit_tokens,
                    "prefix_published_pages": share.published,
                })
        return out
