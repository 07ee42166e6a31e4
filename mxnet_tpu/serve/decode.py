"""Continuous batching: token-level decode scheduling over a slot arena.

``ModelServer`` schedules at whole-batch granularity — fine for
one-shot forwards, hostile to autoregressive decode, where one long
sequence holds every co-batched request hostage until it finishes.
:class:`DecodeServer` schedules at TOKEN granularity instead
(iteration-level scheduling, the vLLM/Orca idea) while keeping the
serve tier's closed-compile-surface discipline:

- The decode state is a fixed-capacity **slot arena**: per-model
  KV-cache buffers of shape ``(max_slots, max_len, ...)`` plus host
  cursors, last-token ids, and an active mask.  The per-token step is
  ONE pre-warmed executable (fixed shapes; cache buffers donated across
  iterations on accelerator backends; inactive slots masked), no matter
  how many requests are live — steady traffic does zero XLA compiles.
- New requests are **admitted between tokens** into free slots: the
  group's prompts run through the AOT-warmed prefill :class:`BucketSpec`
  grid with the slot-scatter FUSED into the same executable — ONE
  device dispatch per admission group, however many requests it admits.
  Finished, expired, and cancelled requests free their slot at the next
  token boundary instead of waiting for batch stragglers.
- The serve substrate is reused end to end: the bounded
  :class:`~.batcher.Batcher` admission queue with
  ``ServerOverloadedError`` backpressure (slot exhaustion queues, queue
  exhaustion rejects), per-request deadlines checked at token
  boundaries, graceful drain, hot ``reload_weights()`` between tokens,
  per-request streaming via a :class:`DecodeHandle` token iterator plus
  the usual ``Future`` for the full sequence, and
  ``ServerStats``/telemetry integration (TTFT + per-token latency
  windows, slot-occupancy, the ``decodeServe`` profiler section, and
  ``serve.decode.request`` async spans with prefill/decode phase
  attribution).

Decode model contract (``TinyDecoder`` below is the runnable
reference; docs/serving.md documents it)::

    model.prefill(prompts, lengths) -> (first_tokens, *cache_rows)
        prompts : (batch, L) int32 NDArray, padded to a prefill bucket
        lengths : (batch,) int32 NDArray of real prompt lengths
        first_tokens : (batch,) int32 — the first generated token
        cache_rows   : one or more (batch, L, ...) NDArrays, the
                       per-position state to seed the slot cache with

    model.decode_step(tokens, cursors, active, *cache)
        -> (next_tokens, *new_cache)
        tokens  : (max_slots,) int32 — each slot's last emitted token
        cursors : (max_slots,) int32 — position the incoming token's
                  cache row is written at
        active  : (max_slots,) bool — inactive slots carry garbage and
                  MUST be masked out of writes / kept NaN-safe
        cache   : (max_slots, max_len, ...) buffers

Both methods run under graph capture (``traced_apply``), so parameters
are runtime inputs of the compiled step — a hot reload needs no
recompile — and the step is compiled ONCE via
:class:`~..gluon.block.CachedStepOp` with the cache buffers donated.

**Paged mode** (``page_tokens > 0``): the cache buffers become
``(num_pages + 1, page_tokens, ...)`` pools and each slot's logical
``[0, pages_per_slot * page_tokens)`` range maps onto physical pages
through a per-slot page table — a ``(max_slots, pages_per_slot)``
int32 input of the SAME fixed-shape executables (the gather to the
logical view, the model step, and the scatter back all live inside the
trace), so capacity scales with tokens in flight instead of
``max_slots x max_len`` while the 1-dispatch-per-token and
0-post-warmup-compile gates survive untouched.  Admission hashes the
prompt at page granularity (``serve.paging.PrefixIndex``): hits map
the new slot onto existing pages with a refcount, and the first write
into a still-shared page triggers copy-on-write — the page copy is
folded into the step executable (a host-computed (src, dst) pair per
slot), never a separate dispatch.  Admission is a token-budget check
against free pages (worst-case pages committed up front, shared full
pages credited), replacing the contiguous per-slot worst-case bound.

**Speculative decoding** (paged mode + ``draft=``): a draft model
proposes ``spec_k - 1`` tokens per scheduling round (one cheap
dispatch each), and the target verifies the whole block in ONE
multi-token step (``static_kwargs={"k": spec_k}`` on the verify
CachedStepOp).  Acceptance is a pure function of the draft and target
logits — greedy: accept while the draft token equals the target
argmax, then emit the target's correction — so speculative greedy
output is BIT-identical to non-speculative greedy and the
continuous-vs-whole-batch parity contract survives.  The draft carries
a position-free running state row per slot (``TinyDraft`` is the
reference; drafts with positional KV state are out of contract —
docs/serving.md has the bypass matrix), re-synced to the committed
tokens inside the verify executable itself.
"""
from __future__ import annotations

import queue as _queue_mod
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout

import numpy as np

from .. import engine, profiler
from ..base import MXNetError, getenv
from ..gluon.block import Block, CachedStepOp
from ..ndarray.ndarray import NDArray, _wrap, array as _nd_array
from ..telemetry import tracer as _tracer
from .batcher import (Batcher, DeadlineExceededError, _Request,
                      ServerClosedError, ServerOverloadedError)
from .buckets import BucketSpec
from .paging import PageAllocator, PrefixIndex, chunk_keys, pages_spanned
from .server import _int8_batch_hook
from .stats import LatencyWindow, ServerStats

#: counter set for the decode tier (same ServerStats machinery as
#: ModelServer, token-granular names; ``batches`` counts admission
#: groups — each is ONE fused prefill+slot-write dispatch — and is
#: what ``record_batch`` tallies).  The ``page_*`` family only moves in
#: paged mode, the ``spec_*`` family only with a draft model attached;
#: ``decode_steps`` counts VERIFY dispatches under speculation (one per
#: scheduling round) and ``spec_draft_steps`` the draft proposal
#: dispatches, so exact dispatch accounting stays
#: ``decode_steps + spec_draft_steps + batches``.
DECODE_COUNTERS = ("submitted", "served", "rejected_overload",
                   "expired_deadline", "failed", "cancelled", "admitted",
                   "batches", "decode_steps", "tokens",
                   "warmup_batches", "reloads",
                   "page_allocs", "page_frees", "page_cow",
                   "page_prefix_hits", "spec_rounds", "spec_draft_steps")

_DONE = object()          # stream sentinel: generation finished cleanly
#: public alias — sink callbacks (the control plane's stream
#: multiplexer) compare their terminal item against this
STREAM_DONE = _DONE


# ---------------------------------------------------------------------------
# window-scoped module counters: the profiler's `decodeServe` section
# (registered below; exported to /metrics as
# mxtpu_decode_serve_* gauges by the section collector)

_sec_lock = threading.Lock()
_sec = {"steps": 0, "tokens": 0, "prefill_batches": 0, "admitted": 0,
        "finished": 0, "expired_deadlines": 0, "occ_ratio_sum": 0.0,
        "pages_in_flight": 0, "cow_copies": 0, "prefix_hit_pages": 0,
        "draft_steps": 0, "spec_proposed": 0, "spec_accepted": 0}


def _sec_bump(live_ratio=None, pages_in_flight=None, **deltas):
    with _sec_lock:
        for k, n in deltas.items():
            _sec[k] += n
        if live_ratio is not None:
            _sec["occ_ratio_sum"] += live_ratio
        if pages_in_flight is not None:
            # a level gauge, not a counter: the latest observed number
            # of live (refcounted) pages in the pool
            _sec["pages_in_flight"] = pages_in_flight


def decode_serve_stats():
    """Window snapshot of the continuous-batching counters;
    ``slot_occupancy`` is the token-step-weighted mean live/max_slots,
    ``accept_rate`` the window's accepted/proposed draft-token ratio
    (0.0 when no speculation ran)."""
    with _sec_lock:
        d = dict(_sec)
    occ = d.pop("occ_ratio_sum")
    d["slot_occupancy"] = round(occ / d["steps"], 4) if d["steps"] else 0.0
    d["accept_rate"] = (round(d["spec_accepted"] / d["spec_proposed"], 4)
                        if d["spec_proposed"] else 0.0)
    return d


def reset_decode_serve_stats():
    with _sec_lock:
        for k in _sec:
            _sec[k] = 0.0 if k == "occ_ratio_sum" else 0


profiler.register_section(
    "decodeServe", decode_serve_stats, reset_decode_serve_stats,
    profiler.rows_table(
        "Decode Serving (continuous batching)",
        (("decode steps", "steps"),
         ("tokens generated", "tokens"),
         ("prefill batches", "prefill_batches"),
         ("requests admitted", "admitted"),
         ("requests finished", "finished"),
         ("deadline expiries", "expired_deadlines"),
         ("slot occupancy (mean live/max)", "slot_occupancy"),
         ("pages in flight", "pages_in_flight"),
         ("copy-on-write page copies", "cow_copies"),
         ("prefix pages shared (hits)", "prefix_hit_pages"),
         ("draft proposal steps", "draft_steps"),
         ("draft tokens proposed", "spec_proposed"),
         ("draft tokens accepted", "spec_accepted"))))


_donate_ok = None


def _decode_donate_ok():
    """Donate the cache arena to the step/writer executables (XLA
    updates the KV buffers in place).  Off on CPU — PjRt:CPU has no
    donation and would warn per token; MXTPU_DECODE_DONATE forces it
    either way."""
    global _donate_ok
    if _donate_ok is None:
        forced = getenv("DECODE_DONATE", None)
        if forced is not None:
            _donate_ok = forced not in ("0", "false", "False", "")
        else:
            import jax

            _donate_ok = jax.default_backend() != "cpu"
    return _donate_ok


# ---------------------------------------------------------------------------
# request / handle


class _DecodeRequest(_Request):
    __slots__ = ("max_new_tokens", "generated", "slot", "stream",
                 "cancelled", "admitted_at", "sinks", "sink_lock",
                 "terminal")

    def __init__(self, prompt, length, future, max_new_tokens,
                 deadline_ms=None):
        super().__init__(prompt, length, future, deadline_ms=deadline_ms)
        self.max_new_tokens = int(max_new_tokens)
        self.generated = []
        self.slot = None
        self.stream = _queue_mod.Queue()
        self.cancelled = False
        self.admitted_at = None
        self.sinks = []               # multiplexing taps (add_sink)
        self.sink_lock = threading.Lock()
        self.terminal = None          # STREAM_DONE or the terminal exc

    def fanout(self, item):
        """Deliver one stream item (token / STREAM_DONE / exception) to
        every registered sink.  Only the decode loop thread emits, so
        per-request ordering holds; the lock serializes against a
        concurrent ``add_sink`` replay (snapshotting the sink list in
        the same critical section as the ``generated`` append keeps
        replay + live delivery exactly-once)."""
        with self.sink_lock:
            if item is not _DONE and not isinstance(item, BaseException):
                self.generated.append(item)
            else:
                self.terminal = item
            sinks = list(self.sinks)
        for s in sinks:
            try:
                s(item)
            except Exception:  # noqa: BLE001 — a broken tap (dead
                # connection) must never kill the decode loop
                with self.sink_lock:
                    if s in self.sinks:
                        self.sinks.remove(s)


class DecodeHandle:
    """Per-request streaming handle: iterate tokens as they are
    generated, or wait on :attr:`future` for the full sequence.

    Iteration yields each token id (int) the moment its boundary
    completes; it ends with ``StopIteration`` on clean finish and
    re-raises the terminal error (deadline, cancellation, shutdown,
    model failure) otherwise — the same error the future carries.
    """

    def __init__(self, req):
        self._req = req
        self.future = req.future

    def __iter__(self):
        return self

    def __next__(self):
        item = self._req.stream.get()
        if item is _DONE:
            # terminal sentinels stay consumable: a second iteration
            # pass (or an iterator copy) must also terminate
            self._req.stream.put(_DONE)
            raise StopIteration
        if isinstance(item, BaseException):
            self._req.stream.put(item)
            raise item
        return item

    def result(self, timeout=None):
        """The full generated token sequence (np.int32 array)."""
        return self.future.result(timeout)

    def cancel(self):
        """Give up on this request: voided at dequeue if still queued,
        freed at the next token boundary if mid-decode."""
        self._req.cancelled = True
        self._req.future.cancel()

    def add_sink(self, sink):
        """Register a callable receiving every stream item of THIS
        request — each token id as it is emitted, then exactly one
        terminal: :data:`STREAM_DONE` (clean finish, after the future
        resolved) or the terminal exception.

        Already-emitted history is replayed first, inside the emission
        lock, so a sink attached mid-generation still sees the full
        item sequence exactly once — the hook the control plane's RPC
        endpoint multiplexes per-request token streams with.  A
        raising sink is dropped, never fatal to the decode loop."""
        req = self._req
        with req.sink_lock:
            for t in req.generated:
                sink(t)
            if req.terminal is not None:
                sink(req.terminal)
            req.sinks.append(sink)


# ---------------------------------------------------------------------------
# graph adapters: the fused admission body and the decode step, each
# behind the gluon capture machinery so the compile surface is counted
# (cached_graph_stats) and parameters stay runtime inputs


class _AdmitAdapter(Block):
    """CachedStepOp body for one admission group: ``model.prefill`` PLUS
    the scatter of every admitted request's cache rows into its slot,
    fused into ONE executable per prefill bucket shape (with the arena
    buffers donated).  A split prefill-then-write design costs
    ``1 + group_size`` dispatches per admission; on a dispatch-bound
    host that overhead eats the scheduling win continuous batching
    exists for — fused, admission is exactly one dispatch."""

    def __init__(self, model, n_cache):
        super().__init__()
        self.model = model
        self._n_cache = int(n_cache)

    def forward(self, prompts, lengths, slots, *cache):
        out = self.model.prefill(prompts, lengths)
        if not isinstance(out, (tuple, list)) or len(out) < 2:
            raise MXNetError(
                "model.prefill must return (first_tokens, *cache_rows)")
        first, rows = out[0], out[1:self._n_cache + 1]
        from jax import lax

        s = slots._data                       # (b,) int32
        outs = []
        for c_nd, r_nd in zip(cache, rows):
            c, r = c_nd._data, r_nd._data
            b = r.shape[0]
            # unrolled per-row scatter, REVERSED: padding rows beyond
            # the real group carry slots[i] == slots[0], so their
            # garbage lands on slot[0] FIRST and row 0's own write
            # (last) fully overwrites it — dead rows never touch a
            # live slot and no per-row mask/select is needed
            for i in reversed(range(b)):
                blk = lax.dynamic_slice_in_dim(r, i, 1, axis=0)
                start = (s[i],) + (0,) * (c.ndim - 1)
                c = lax.dynamic_update_slice(c, blk.astype(c.dtype),
                                             start)
            outs.append(_wrap(c))
        return (first,) + tuple(outs)


class _StepAdapter(Block):
    """CachedStepOp body for ``model.decode_step`` (ONE fixed-shape
    executable for the whole serving lifetime)."""

    def __init__(self, model):
        super().__init__()
        self.model = model

    def forward(self, tokens, cursors, active, *cache):
        out = self.model.decode_step(tokens, cursors, active, *cache)
        if not isinstance(out, (tuple, list)) or len(out) < 2:
            raise MXNetError(
                "model.decode_step must return (next_tokens, *new_cache)")
        return tuple(out)


class _PagedAdmitAdapter(Block):
    """Fused admission for the PAGED arena: ``model.prefill`` plus the
    scatter of every admitted request's cache rows into its page-table
    pages, one executable per prefill bucket shape.

    The host passes an ``admit_pt`` (batch, pages_per_slot) page table
    holding only the FRESHLY allocated pages (prefix-sharing hits are
    redirected to the trash page): resident shared pages keep their
    bytes — that's the dedup — and never see a duplicate-index scatter
    of recomputed values.  Padding rows beyond the real group carry an
    all-trash row, so dead rows land on the sink page by construction.
    With a draft model attached, ``draft.prefill`` runs in the SAME
    executable and each row's last real-position state row seeds the
    slot's position-free draft state — admission stays exactly one
    dispatch per group."""

    def __init__(self, model, n_cache, page_tokens, draft=None,
                 n_draft=0):
        super().__init__()
        self.model = model
        self._n_cache = int(n_cache)
        self._t = int(page_tokens)
        self.draft = draft
        self._n_draft = int(n_draft)

    def forward(self, prompts, lengths, admit_pt, *rest):
        import jax.numpy as jnp
        from jax import lax

        if self.draft is not None:
            slots, rest = rest[0], rest[1:]
        pools = rest[:self._n_cache]
        dstate = rest[self._n_cache:]
        out = self.model.prefill(prompts, lengths)
        if not isinstance(out, (tuple, list)) or len(out) < 2:
            raise MXNetError(
                "model.prefill must return (first_tokens, *cache_rows)")
        first, rows = out[0], out[1:self._n_cache + 1]
        pt = admit_pt._data                    # (b, P) int32
        outs = []
        for c_nd, r_nd in zip(pools, rows):
            c, r = c_nd._data, r_nd._data
            b, lb = r.shape[0], r.shape[1]
            nb = -(-lb // self._t)
            pad = nb * self._t - lb
            if pad:
                r = jnp.pad(r, [(0, 0), (0, pad)]
                            + [(0, 0)] * (r.ndim - 2))
            pages = r.reshape((b * nb, self._t) + r.shape[2:])
            idx = pt[:, :nb].reshape(-1)
            outs.append(_wrap(c.at[idx].set(pages.astype(c.dtype))))
        douts = []
        if self.draft is not None:
            dout = self.draft.prefill(prompts, lengths)
            if not isinstance(dout, (tuple, list)) or len(dout) < 2:
                raise MXNetError(
                    "draft.prefill must return (first_tokens, "
                    "*state_rows)")
            drows = dout[1:self._n_draft + 1]
            ln = lengths._data
            s = slots._data
            for a_nd, r_nd in zip(dstate, drows):
                a, r = a_nd._data, r_nd._data
                b = r.shape[0]
                idx = jnp.clip(ln - 1, 0).reshape(
                    (b,) + (1,) * (r.ndim - 1))
                last = jnp.take_along_axis(r, idx, axis=1)  # (b,1,...)
                # same reversed unrolled scatter as the contiguous
                # admit: padding rows target slots[0] and are
                # overwritten last by row 0's real state
                for i in reversed(range(b)):
                    blk = lax.dynamic_slice_in_dim(last, i, 1, axis=0)
                    start = (s[i],) + (0,) * (a.ndim - 1)
                    a = lax.dynamic_update_slice(
                        a, blk.astype(a.dtype), start)
                douts.append(_wrap(a))
        return (first,) + tuple(outs) + tuple(douts)


class _PagedStepAdapter(Block):
    """The paged decode/verify step: ONE fixed-shape executable that
    (1) performs any pending copy-on-write page duplication, (2)
    gathers each slot's logical view through its page table, (3)
    unrolls ``k`` chained ``model.decode_step`` calls over the block of
    candidate tokens (k == 1 is plain paged decode), (4) scatters the
    logical views back through the page table, and (5) with a draft
    attached, folds the ACCEPTED tokens into the draft's running state
    — acceptance recomputed in-trace as the same pure function of
    draft/target tokens the host applies.

    Write-masking: lane ``j`` of the unroll is active for a slot only
    while ``j < depths[slot]``, so a slot whose generation budget ends
    mid-block never writes past its committed page span.  Shared pages
    are never written (COW redirects the write-frontier page first), so
    the duplicate-index scatter-back only ever rewrites identical
    bytes."""

    def __init__(self, model, n_cache, page_tokens, draft=None,
                 n_draft=0):
        super().__init__()
        self.model = model
        self._n_cache = int(n_cache)
        self._t = int(page_tokens)
        self.draft = draft
        self._n_draft = int(n_draft)

    def forward(self, tok_block, cursors, depths, active, page_table,
                cow_src, cow_dst, *cache, k=1):
        import jax.numpy as jnp

        pools = [c._data for c in cache[:self._n_cache]]
        dstate = [c._data for c in cache[self._n_cache:]]
        tb = tok_block._data                   # (S, k) int32
        cur0 = cursors._data
        dep = depths._data
        act = active._data
        pt = page_table._data                  # (S, P) int32
        src, dst = cow_src._data, cow_dst._data
        s_n, p_n = pt.shape
        length = p_n * self._t
        # (1) COW: duplicate shared write-frontier pages into private
        # ones; no-op lanes carry dst == trash with src == 0, so their
        # identical values keep the duplicate-index scatter
        # deterministic
        pools = [p.at[dst].set(jnp.take(p, src, axis=0)) for p in pools]
        # (2) logical gather
        flat = pt.reshape(-1)
        state = [jnp.take(p, flat, axis=0)
                 .reshape((s_n, length) + p.shape[2:]) for p in pools]
        # (3) k chained model steps over the candidate block
        outs = []
        cur = cur0
        for j in range(k):
            lane = act & (j < dep)
            o = self.model.decode_step(
                _wrap(tb[:, j]), _wrap(cur), _wrap(lane),
                *[_wrap(x) for x in state])
            if not isinstance(o, (tuple, list)) or len(o) < 2:
                raise MXNetError(
                    "model.decode_step must return "
                    "(next_tokens, *new_cache)")
            outs.append(o[0]._data.astype(jnp.int32))
            state = [x._data for x in o[1:]]
            # masked-off lanes may run the cursor past the logical
            # range; the model only compares against it, but keep it
            # indexable regardless
            cur = jnp.minimum(cur + 1, length - 1)
        ob = jnp.stack(outs, axis=1)           # (S, k)
        # (4) scatter back
        out_pools = []
        for p, lg in zip(pools, state):
            pages = lg.reshape((s_n * p_n, self._t) + p.shape[2:])
            out_pools.append(p.at[flat].set(pages.astype(p.dtype)))
        # (5) draft running-state resync on the accepted prefix
        douts = []
        if self.draft is not None:
            if k > 1:
                m = (tb[:, 1:] == ob[:, :-1])
                jidx = jnp.arange(1, k)[None, :]
                m = m & (jidx < dep[:, None])
                acc = 1 + jnp.sum(
                    jnp.cumprod(m.astype(jnp.int32), axis=1), axis=1)
            else:
                acc = jnp.ones((s_n,), jnp.int32)
            nd = self.draft.accept(
                _wrap(tb), _wrap(acc), _wrap(act),
                *[_wrap(x) for x in dstate])
            nd = nd if isinstance(nd, (tuple, list)) else (nd,)
            douts = [_wrap(x._data) for x in nd]
        return (_wrap(ob),) + tuple(_wrap(p) for p in out_pools) \
            + tuple(douts)


# ---------------------------------------------------------------------------
# the server


class DecodeServer:
    """Continuous-batching autoregressive decode server.

    Parameters
    ----------
    model : Block implementing the decode model contract (module doc).
    spec : BucketSpec
        The closed prefill grid: ``example_shape=(None,)`` int token
        prompts, ``lengths`` = allowed padded prompt lengths.  Every
        length bucket must fit ``max_len``.
    max_slots : int, optional
        Arena capacity (concurrent sequences); default
        ``MXTPU_DECODE_SLOTS`` (8).
    max_len : int, optional
        Cache length per slot; default ``MXTPU_DECODE_MAX_LEN`` (128).
        A request needs ``prompt_len + max_new_tokens <= max_len``.
    eos_id : int, optional
        Token id that terminates a sequence early (None = run to
        ``max_new_tokens``).
    max_new_tokens : int
        Default generation budget per request (``submit()`` overrides).
    max_queue : int
        Bound on queued admissions before submit() fails fast.
    admission : "continuous" | "batch"
        ``"continuous"`` (the point of this class) backfills free slots
        between tokens.  ``"batch"`` only admits when the arena is
        EMPTY — whole-batch decode semantics, every sequence waits for
        the batch's straggler — kept as the baseline of the parity
        tests.
    ctx : Context, optional
    checkpoint : CheckpointManager or str, optional
        Source for ``reload_weights()``.
    page_tokens : int, optional
        ``> 0`` switches the arena to PAGED mode with this many tokens
        per physical cache page; default ``MXTPU_DECODE_PAGE_TOKENS``
        (0 = contiguous).  Admission becomes a token-budget check
        against free pages and identical prompt prefixes share pages
        copy-on-write (module doc).
    num_pages : int, optional
        Physical page-pool size; default ``MXTPU_DECODE_NUM_PAGES`` or
        ``max_slots * ceil(max_len / page_tokens)`` (capacity parity
        with the contiguous arena — size it SMALLER to spend less HBM
        than worst-case).
    draft : Block, optional
        Draft model for speculative decoding (same prefill/decode_step
        contract, position-free per-slot state rows; ``TinyDraft`` is
        the reference).  Requires paged mode and ``spec_k >= 2``.
    spec_k : int, optional
        Speculation block size: the draft proposes ``spec_k - 1``
        tokens per round and the target verifies the block in ONE
        step.  Default ``MXTPU_DECODE_SPEC_K`` (1 = off).
    """

    def __init__(self, model, spec, max_slots=None, max_len=None,
                 eos_id=None, max_new_tokens=32, max_queue=256,
                 admission="continuous", ctx=None, checkpoint=None,
                 page_tokens=None, num_pages=None, draft=None,
                 spec_k=None):
        if not isinstance(spec, BucketSpec):
            raise MXNetError("spec must be a serve.BucketSpec")
        if spec.var_axis is None or len(spec.example_shape) != 1:
            raise MXNetError(
                "DecodeServer prompts are 1-D token sequences: use "
                "BucketSpec(example_shape=(None,), lengths=...)")
        if admission not in ("continuous", "batch"):
            raise MXNetError(
                f"admission must be 'continuous' or 'batch', "
                f"got {admission!r}")
        self._model = model
        self._spec = spec
        # an int8-quantized decode model (quantize_net output) books
        # its prefill groups and token steps into the `quantize`
        # profiler section; reload_weights() re-quantizes fp32
        # checkpoints
        self._int8 = bool(getattr(model, "_int8_quantized", False))
        self._note_int8 = _int8_batch_hook(model)
        if self._int8:
            # the decode path requires CALIBRATED quantization: a
            # dynamic range is a jnp.min/max over the whole slot arena,
            # so one request's quantization would depend on co-resident
            # (including garbage inactive) slots — silently breaking
            # the per-slot independence / continuous==batch parity
            # contract.  Fail at construction, not per-token.
            from ..contrib.quantization import _iter_quantized

            uncal = [w.name for _, w in _iter_quantized(model)
                     if not w._calibrated]
            if uncal:
                raise MXNetError(
                    f"DecodeServer needs CALIBRATED quantization: "
                    f"layer(s) {uncal} quantize with dynamic per-batch "
                    "ranges, which reduce over the whole slot arena "
                    "and couple independent requests; re-run "
                    "quantize_net with calib_data= "
                    "(docs/quantization.md)")
        self._slots = int(max_slots if max_slots is not None
                          else getenv("DECODE_SLOTS", 8, int))
        self._max_len = int(max_len if max_len is not None
                            else getenv("DECODE_MAX_LEN", 128, int))
        if self._slots < 1 or self._max_len < 2:
            raise MXNetError("max_slots must be >= 1 and max_len >= 2")
        if spec.lengths[-1] > self._max_len:
            raise MXNetError(
                f"prefill bucket length {spec.lengths[-1]} exceeds the "
                f"slot cache max_len {self._max_len}")
        # -- paged arena / speculative decoding config ------------------
        self._page_tokens = int(
            page_tokens if page_tokens is not None
            else getenv("DECODE_PAGE_TOKENS", 0, int))
        self._paged = self._page_tokens > 0
        self._draft = draft
        self._spec_k = int(spec_k if spec_k is not None
                           else getenv("DECODE_SPEC_K", 1, int))
        if self._spec_k < 1:
            raise MXNetError("spec_k must be >= 1")
        if self._paged:
            self._pages_per_slot = pages_spanned(self._max_len,
                                                 self._page_tokens)
            self._num_pages = int(
                num_pages if num_pages is not None
                else (getenv("DECODE_NUM_PAGES", 0, int)
                      or self._slots * self._pages_per_slot))
            if self._num_pages < 1:
                raise MXNetError("num_pages must be >= 1")
            self._alloc = PageAllocator(self._num_pages,
                                        self._page_tokens)
            self._prefix = PrefixIndex()
            self._page_table = np.full(
                (self._slots, self._pages_per_slot), self._alloc.trash,
                np.int32)
            self._slot_pages = [[] for _ in range(self._slots)]
            self._slot_commit = [0] * self._slots
            self._committed = 0
        elif self._draft is not None or self._spec_k > 1:
            raise MXNetError(
                "speculative decoding needs the paged arena: pass "
                "page_tokens= (or MXTPU_DECODE_PAGE_TOKENS) alongside "
                "draft=/spec_k=")
        if self._draft is not None:
            if self._spec_k < 2:
                raise MXNetError(
                    "a draft model without spec_k >= 2 proposes "
                    "nothing: pass spec_k= (or MXTPU_DECODE_SPEC_K)")
            tv = getattr(model, "vocab", None)
            dv = getattr(draft, "vocab", None)
            if tv is not None and dv is not None and int(tv) != int(dv):
                raise MXNetError(
                    f"draft/target vocab mismatch ({int(dv)} vs "
                    f"{int(tv)}): speculative acceptance compares "
                    "token ids, so draft and target must share one "
                    "tokenizer (docs/serving.md bypass matrix)")
            if bool(getattr(draft, "_int8_quantized", False)):
                from ..contrib.quantization import _iter_quantized

                uncal = [w.name for _, w in _iter_quantized(draft)
                         if not w._calibrated]
                if uncal:
                    raise MXNetError(
                        f"draft model layer(s) {uncal} quantize with "
                        "dynamic per-batch ranges; the draft runs over "
                        "the whole slot arena, so it needs CALIBRATED "
                        "quantization for the same per-slot "
                        "independence reason as the target "
                        "(docs/quantization.md)")
        elif self._spec_k > 1:
            raise MXNetError(
                "spec_k > 1 needs a draft= model to propose tokens")
        self._overflow = []        # paged: admissions deferred on pages
        self._eos_id = None if eos_id is None else int(eos_id)
        self._default_mnt = int(max_new_tokens)
        self._admission = admission
        self._ctx = ctx
        self._batcher = Batcher(max_queue=max_queue, linger_ms=0.0)
        self._stats = ServerStats(counters=DECODE_COUNTERS)
        self._ttft = LatencyWindow()
        self._token_lat = LatencyWindow()
        self._occ_lock = threading.Lock()
        self._occ_sum = 0.0
        self._occ_steps = 0
        self._exec_lock = threading.Lock()   # token step XOR reload
        self._admit_op = None                # built at start() (need
        self._step_op = None                 # the cache layout first)
        self._draft_op = None                # spec: proposal step
        self._n_cache = None
        self._cache_meta = None              # [(tail shape, dtype)]
        self._cache = None                   # list of raw device arrays
        self._draft_meta = None              # [(tail shape, dtype)]
        self._draft_cache = []               # draft state (S, 1, ...)
        self._n_draft = 0
        self._spec_proposed = 0              # window-scoped, _occ_lock
        self._spec_accepted = 0
        self._tokens = np.zeros(self._slots, np.int32)
        self._cursors = np.zeros(self._slots, np.int32)
        self._active = np.zeros(self._slots, bool)
        self._slot_req = [None] * self._slots
        self._step_count = 0
        self._donate = False                 # resolved at _warmup()
        self._started = False
        self._closing = False
        self._abort = False
        self._worker = None
        self._warmup_compiles = 0
        self._metrics_collector = None
        if isinstance(checkpoint, str):
            from ..checkpoint import CheckpointManager

            checkpoint = CheckpointManager(checkpoint)
        self._ckpt = checkpoint

    # -- lifecycle ----------------------------------------------------------

    def start(self):
        """Warm the whole compile surface (one fused prefill+write
        executable per prompt bucket, the ONE decode step), then start
        the token loop.  A drained server restarts with zero new
        compiles."""
        if self._started:
            raise MXNetError("DecodeServer already started")
        self._abort = False
        self._batcher.reopen()
        if self._cache is None:
            self._warmup()
        self._warmup_compiles = self._graph_stats_raw()["compiles"]
        self._started = True
        self._closing = False
        if self._metrics_collector is None:
            from ..telemetry import metrics as _metrics

            self._metrics_collector = _metrics.register_decode_server(self)
        self._worker = threading.Thread(target=self._loop,
                                        name="mxtpu-decode-loop",
                                        daemon=True)
        self._worker.start()
        return self

    def _warmup(self):
        if self._paged:
            self._warmup_paged()
            return
        with profiler.op_scope("serve.decode.warmup", cat="serve"):
            # ONE eager probe call discovers the model's cache layout
            # (buffer count, per-position tail shapes, dtypes) before
            # any arena or executable exists
            min_len = self._spec.lengths[0]
            probe = self._model.prefill(
                _nd_array(np.zeros((1, min_len), np.int32),
                          ctx=self._ctx),
                _nd_array(np.full(1, min_len, np.int32), ctx=self._ctx))
            rows = [o for o in probe[1:] if isinstance(o, NDArray)]
            if not rows:
                raise MXNetError("model.prefill returned no cache rows")
            self._cache_meta = [(r.shape[2:], r.dtype) for r in rows]
            self._n_cache = n = len(self._cache_meta)
            self._cache = self._zero_arena()
            # decided once, on the start() thread; the loop thread only
            # reads the cached flag
            donate = self._donate = _decode_donate_ok()
            self._admit_op = CachedStepOp(
                _AdmitAdapter(self._model, n),
                donate_inputs=tuple(range(3, 3 + n)) if donate else ())
            self._step_op = CachedStepOp(
                _StepAdapter(self._model),
                donate_inputs=tuple(range(3, 3 + n)) if donate else ())
            # one fused prefill+write executable per prompt bucket
            # shape — the whole admission surface, compiled up front
            for shape in self._spec.bucket_shapes():
                b, length = shape[0], shape[1]
                outs = self._admit_op(
                    np.zeros((b, length), np.int32),
                    np.full(b, length, np.int32),
                    np.zeros(b, np.int32), *self._cache)
                np.asarray(outs[0])  # fail in warmup, not mid-token
                self._cache = list(outs[1:])
                self._stats.incr("warmup_batches")
            # the decode step: ONE executable, compiled before traffic
            outs = self._step_op(self._tokens, self._cursors,
                                 self._active, *self._cache)
            self._cache = list(outs[1:])
            # warmup scribbled zero-rows into slot 0; hand traffic a
            # clean arena (committed, same jit key as executed outputs)
            self._cache = self._zero_arena()

    def _warmup_paged(self):
        """Warm the PAGED compile surface: one fused prefill+page-write
        executable per prompt bucket, the one multi-token verify step,
        and (with a draft) the one proposal step — all compiled before
        traffic, so steady state does zero XLA compiles no matter the
        page churn (page tables are runtime int32 inputs)."""
        with profiler.op_scope("serve.decode.warmup", cat="serve"):
            min_len = self._spec.lengths[0]
            zeros = _nd_array(np.zeros((1, min_len), np.int32),
                              ctx=self._ctx)
            lens = _nd_array(np.full(1, min_len, np.int32),
                             ctx=self._ctx)
            probe = self._model.prefill(zeros, lens)
            rows = [o for o in probe[1:] if isinstance(o, NDArray)]
            if not rows:
                raise MXNetError("model.prefill returned no cache rows")
            self._cache_meta = [(r.shape[2:], r.dtype) for r in rows]
            self._n_cache = n = len(self._cache_meta)
            nd = 0
            if self._draft is not None:
                dprobe = self._draft.prefill(zeros, lens)
                drows = [o for o in dprobe[1:] if isinstance(o, NDArray)]
                if not drows:
                    raise MXNetError(
                        "draft.prefill returned no state rows")
                self._draft_meta = [(r.shape[2:], r.dtype)
                                    for r in drows]
                self._n_draft = nd = len(self._draft_meta)
            self._cache = self._zero_arena()
            self._draft_cache = self._zero_draft()
            donate = self._donate = _decode_donate_ok()
            base = 3 if self._draft is None else 4
            self._admit_op = CachedStepOp(
                _PagedAdmitAdapter(self._model, n, self._page_tokens,
                                   self._draft, nd),
                donate_inputs=tuple(range(base, base + n + nd))
                if donate else ())
            self._step_op = CachedStepOp(
                _PagedStepAdapter(self._model, n, self._page_tokens,
                                  self._draft, nd),
                donate_inputs=tuple(range(7, 7 + n + nd))
                if donate else (),
                static_kwargs={"k": self._spec_k})
            if self._draft is not None:
                # proposal steps deliberately DON'T donate: the
                # persistent draft state must survive the k-1 chained
                # proposals untouched — only the verify step (which
                # recomputes acceptance in-trace) owns and advances it
                self._draft_op = CachedStepOp(_StepAdapter(self._draft))
            trash = self._alloc.trash
            p_n = self._pages_per_slot
            for shape in self._spec.bucket_shapes():
                b, length = shape[0], shape[1]
                args = [np.zeros((b, length), np.int32),
                        np.full(b, length, np.int32),
                        np.full((b, p_n), trash, np.int32)]
                if self._draft is not None:
                    args.append(np.zeros(b, np.int32))
                outs = self._admit_op(*args, *self._cache,
                                      *self._draft_cache)
                np.asarray(outs[0])  # fail in warmup, not mid-token
                self._cache = list(outs[1:1 + n])
                self._draft_cache = list(outs[1 + n:])
                self._stats.incr("warmup_batches")
            if self._draft_op is not None:
                outs = self._draft_op(self._tokens, self._cursors,
                                      self._active, *self._draft_cache)
                np.asarray(outs[0])  # undonated; state not adopted
            outs = self._step_op(
                np.zeros((self._slots, self._spec_k), np.int32),
                self._cursors, np.zeros(self._slots, np.int32),
                self._active,
                np.full((self._slots, p_n), trash, np.int32),
                np.zeros(self._slots, np.int32),
                np.full(self._slots, trash, np.int32),
                *self._cache, *self._draft_cache)
            np.asarray(outs[0])
            # hand traffic clean pools (committed, same jit key as
            # executed outputs — see _zero_arena)
            self._cache = self._zero_arena()
            self._draft_cache = self._zero_draft()

    def __enter__(self):
        if not self._started:
            self.start()
        return self

    def __exit__(self, *exc):
        self.shutdown(drain=exc == (None, None, None))
        return False

    def drain(self, timeout=None):
        """Stop admissions and block until every admitted sequence has
        finished decoding; ends with zero queued work and zero live
        slots."""
        self._closing = True
        self._batcher.close()
        if self._worker is not None:
            self._worker.join(timeout)
            if self._worker.is_alive():
                raise MXNetError("drain timed out with live decode slots")
            self._worker = None
        self._started = False

    def shutdown(self, drain=True, timeout=None):
        if not self._started and self._worker is None:
            return
        if drain:
            self.drain(timeout)
            return
        self._closing = True
        self._abort = True
        self._batcher.close()
        if self._worker is not None:
            self._worker.join(timeout)
            self._worker = None
        self._started = False
        # fail live slots, then sweep the deferred list and the queue
        for slot in np.flatnonzero(self._active):
            self._finish_slot(int(slot), "cancelled",
                              ServerClosedError("server shut down"))
        for req in self._overflow:
            self._resolve_error(req, "cancelled",
                                ServerClosedError("server shut down"))
        self._overflow = []
        while True:
            group, expired = self._batcher.next_group(self._slots,
                                                      timeout=0)
            if not group and not expired:
                break
            for req in group + expired:
                self._resolve_error(req, "cancelled",
                                    ServerClosedError("server shut down"))

    # -- request path -------------------------------------------------------

    def submit(self, prompt, max_new_tokens=None, deadline_ms=None):
        """Queue one prompt (1-D int token array); returns a
        :class:`DecodeHandle` (stream iterator + ``.future``)."""
        if not self._started or self._closing:
            raise ServerClosedError(
                "DecodeServer is not accepting requests (not started, "
                "draining, or shut down)")
        if isinstance(prompt, NDArray):
            prompt = prompt.asnumpy()
        prompt = np.asarray(prompt, dtype=np.int32)
        length = self._spec.validate(prompt)
        mnt = int(max_new_tokens if max_new_tokens is not None
                  else self._default_mnt)
        if mnt < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if self._paged:
            # token-budget admission: a request fits if its worst-case
            # page span fits BOTH the per-slot logical range and the
            # physical pool — not the contiguous per-slot worst case
            span = pages_spanned(length + mnt, self._page_tokens)
            logical = self._pages_per_slot * self._page_tokens
            pool = self._num_pages * self._page_tokens
            if length + mnt > logical or span > self._num_pages:
                raise MXNetError(
                    f"prompt_len {length} + max_new_tokens {mnt} "
                    f"({span} pages of {self._page_tokens} tokens) can "
                    f"NEVER fit: per-slot logical budget is {logical} "
                    f"tokens ({self._pages_per_slot} pages, from "
                    f"max_len={self._max_len}) and the page pool holds "
                    f"{pool} tokens ({self._num_pages} pages); "
                    f"truncate the prompt, lower the budget, or raise "
                    f"MXTPU_DECODE_MAX_LEN / MXTPU_DECODE_NUM_PAGES")
        elif length + mnt > self._max_len:
            raise MXNetError(
                f"prompt_len {length} + max_new_tokens {mnt} exceeds the "
                f"slot cache max_len {self._max_len}; truncate the "
                f"prompt, lower the budget, or raise MXTPU_DECODE_MAX_LEN")
        req = _DecodeRequest(prompt, length, Future(), mnt,
                             deadline_ms=deadline_ms)
        req.trace_id = _tracer.request_begin(
            "serve.decode.request", cat="serve", prompt_len=length,
            max_new_tokens=mnt,
            deadline_ms=deadline_ms if deadline_ms is not None else -1)
        self._stats.incr("submitted")
        try:
            self._batcher.put(req)
        except MXNetError as e:
            self._stats.incr("submitted", -1)
            if isinstance(e, ServerOverloadedError):
                self._stats.incr("rejected_overload")
            _tracer.request_end("serve.decode.request", req.trace_id,
                                cat="serve", outcome="rejected")
            raise
        return DecodeHandle(req)

    def generate(self, prompt, max_new_tokens=None, deadline_ms=None,
                 timeout=None):
        """Synchronous convenience wrapper: the full token sequence."""
        handle = self.submit(prompt, max_new_tokens=max_new_tokens,
                             deadline_ms=deadline_ms)
        if timeout is None and deadline_ms is not None:
            # same contract as ModelServer.predict: a deadline-only
            # call never blocks indefinitely on a wedged server
            from .server import PREDICT_GRACE_S

            timeout = deadline_ms / 1e3 + PREDICT_GRACE_S
        try:
            return handle.result(timeout)
        except _FutureTimeout:
            # caller gave up: void the request so it stops consuming a
            # queue position / decode slot (same contract as
            # ModelServer.predict)
            handle.cancel()
            raise

    # -- the token loop -----------------------------------------------------

    def _loop(self):
        try:
            while not self._abort:
                live = int(self._active.sum())
                self._admit(timeout=0.05 if live == 0 else 0.0)
                live = int(self._active.sum())
                if live == 0:
                    if self._batcher.drained() and not self._overflow:
                        return
                    continue
                with self._exec_lock:
                    self._boundary_and_step()
        except Exception as e:  # noqa: BLE001 — a dead loop thread
            # would strand every future forever; fail loudly instead
            for slot in np.flatnonzero(self._active):
                self._finish_slot(int(slot), "failed", e)
            for req in self._overflow:
                self._resolve_error(req, "failed", e)
            self._overflow = []
            while True:
                group, expired = self._batcher.next_group(self._slots,
                                                          timeout=0)
                if not group and not expired:
                    return
                for req in group + expired:
                    self._resolve_error(req, "failed", e)

    def _free_slots(self):
        return [i for i in range(self._slots) if not self._active[i]]

    def _sweep_overflow(self):
        """Deadline/cancel sweep over page-deferred admissions — they
        left the batcher, so its dequeue sweep can't see them."""
        if not self._overflow:
            return
        now = time.monotonic()
        keep = []
        for req in self._overflow:
            if req.cancelled or req.future.cancelled():
                self._resolve_error(req, "cancelled",
                                    ServerClosedError("request cancelled"))
            elif req.expired(now):
                self._resolve_error(req, "expired",
                                    DeadlineExceededError(
                                        "deadline passed while queued"))
            else:
                keep.append(req)
        self._overflow = keep

    def _page_commit_bound(self, req):
        """Worst-case EXCLUSIVE pages this request may ever hold: the
        span of prompt + generation budget, minus full prompt pages
        already resident in the prefix index (a shared partial tail
        earns no credit — its first write copy-on-writes into a fresh
        private page)."""
        span = pages_spanned(req.length + req.max_new_tokens,
                             self._page_tokens)
        credit = 0
        for key in chunk_keys(req.example, req.length,
                              self._page_tokens):
            if key[0] == "F" and self._prefix.lookup(key) is not None:
                credit += 1
        return span - credit

    def _admit(self, timeout):
        self._sweep_overflow()
        free = self._free_slots()
        if not free:
            return
        if self._admission == "batch" and len(free) < self._slots:
            # whole-batch mode: no backfill until the arena is EMPTY
            return
        want = min(len(free), self._spec.max_batch)
        # page-deferred admissions keep their queue position ahead of
        # anything still in the batcher
        cand = self._overflow[:want]
        del self._overflow[:len(cand)]
        if len(cand) < want:
            group, expired = self._batcher.next_group(
                want - len(cand), timeout=0 if cand else timeout)
            for req in expired:
                self._resolve_error(req, "expired",
                                    DeadlineExceededError(
                                        "deadline passed while queued"))
            # void caller-side-cancelled requests at dequeue (they must
            # not consume a prefill row or a slot)
            for req in (group or ()):
                if req.cancelled or req.future.cancelled():
                    self._resolve_error(req, "cancelled",
                                        ServerClosedError(
                                            "request cancelled"))
                else:
                    cand.append(req)
        if not cand:
            return
        if self._paged:
            # token-budget gate: admit only what the page pool can
            # cover in the WORST case (prefix-sharing credit for full
            # pages already resident); the rest defers, never drops
            live, defer, promised = [], [], 0
            for req in cand:
                commit = self._page_commit_bound(req)
                if (self._committed + promised + commit
                        <= self._num_pages):
                    live.append(req)
                    promised += commit
                else:
                    defer.append(req)
            self._overflow = defer + self._overflow
            if not live:
                return
        else:
            live = cand
        try:
            self._prefill_group(live, free)
        except Exception as e:  # noqa: BLE001 — fail THIS group's
            # futures; the loop (and every live slot) must survive
            for req in live:
                if req.slot is not None:
                    continue   # already admitted before the failure
                self._resolve_error(req, "failed", e)
            if self._donate:
                # the failed admit op may have consumed the donated
                # arena buffers; every live sequence's cache state is
                # unknowable, so fail them too and start clean (a
                # deleted-buffer step would take them all down anyway,
                # with a far less diagnosable error)
                for slot in np.flatnonzero(self._active):
                    self._finish_slot(int(slot), "failed", e)
                self._reset_arena()

    def _prefill_group(self, group, free):
        spec = self._spec
        max_len = max(r.length for r in group)
        batch, length = spec.pick(len(group), max_len)
        key = spec.key(batch, length)
        slots = [free.pop(0) for _ in group]
        if self._paged:
            self._prefill_group_paged(group, slots, batch, length, key)
            return
        with profiler.op_scope("serve.decode.admit", cat="serve"):
            padded = spec.pad_batch([r.example for r in group], batch,
                                    length)
            lengths = np.ones(batch, np.int32)
            lengths[:len(group)] = [r.length for r in group]
            # padding rows beyond the group target slots[0]: the fused
            # scatter writes them first and overwrites with row 0's
            # real rows (see _AdmitAdapter), so they never touch a
            # live slot
            slot_vec = np.full(batch, slots[0], np.int32)
            slot_vec[:len(group)] = slots
            # the exec lock serializes this dispatch with
            # reload_weights(): the admit op fetches p.data() live, so
            # an unserialized restore could hand it a torn mix of old
            # and new parameters
            with self._exec_lock, \
                    profiler.op_scope("serve.prefill", cat="serve"):
                outs = self._admit_op(padded, lengths, slot_vec,
                                      *self._cache)
                first = np.asarray(outs[0])
                self._cache = list(outs[1:])
        self._stats.record_batch(
            key, n_real=len(group), n_rows=batch,
            real_elems=sum(r.length for r in group),
            padded_elems=batch * length)
        _sec_bump(prefill_batches=1)
        if self._int8:
            self._note_int8()
        now = time.monotonic()
        for i, req in enumerate(group):
            slot = slots[i]
            req.slot = slot
            req.admitted_at = now
            self._slot_req[slot] = req
            self._tokens[slot] = first[i]
            self._cursors[slot] = req.length
            self._active[slot] = True
            self._stats.incr("admitted")
            _sec_bump(admitted=1)
            _tracer.request_instant("serve.decode.admitted", req.trace_id,
                                    cat="serve", slot=slot,
                                    bucket=key)
            self._emit_token(req, int(first[i]), now)
            # a 1-token budget (or an immediate EOS) finishes at
            # admission without ever occupying a decode step
            self._maybe_finish(req, now)

    def _prefill_group_paged(self, group, slots, batch, length, key):
        """Paged admission: map every request's prompt onto pages
        (prefix-index hits retain the resident page, misses allocate
        fresh ones), then run the ONE fused prefill+page-write dispatch
        — freshly allocated pages receive the new cache rows, hit pages
        keep their resident bytes (the storage dedup)."""
        spec = self._spec
        trash = self._alloc.trash
        p_n = self._pages_per_slot
        t = self._page_tokens
        admit_pt = np.full((batch, p_n), trash, np.int32)
        mapped = []              # per-req (pages, commit)
        claimed = []             # undo log: every ref we took
        n_alloc0 = self._alloc.allocs
        n_hits = 0

        def _rollback():
            for pg in reversed(claimed):
                if self._alloc.release(pg):
                    self._prefix.drop_page(pg)

        try:
            for i, req in enumerate(group):
                pages, shared = [], []
                for ck in chunk_keys(req.example, req.length, t):
                    pg = self._prefix.lookup(ck)
                    if pg is not None:
                        self._alloc.retain(pg)
                        shared.append(True)
                    else:
                        pg = self._alloc.alloc()
                        self._prefix.register(ck, pg)
                        shared.append(False)
                        # fresh pages enter the fused scatter; hit
                        # pages stay redirected to trash so resident
                        # bytes survive and the duplicate-index scatter
                        # never sees them
                        admit_pt[i, len(pages)] = pg
                    pages.append(pg)
                    claimed.append(pg)
                full = req.length // t
                credit = sum(1 for j in range(min(full, len(pages)))
                             if shared[j])
                commit = pages_spanned(
                    req.length + req.max_new_tokens, t) - credit
                n_hits += sum(shared)
                mapped.append((pages, commit))
        except Exception:
            _rollback()
            raise
        with profiler.op_scope("serve.decode.admit", cat="serve"):
            padded = spec.pad_batch([r.example for r in group], batch,
                                    length)
            lengths = np.ones(batch, np.int32)
            lengths[:len(group)] = [r.length for r in group]
            args = [padded, lengths, admit_pt]
            if self._draft is not None:
                # draft-state rows scatter like the contiguous admit:
                # padding rows target slots[0] and are overwritten by
                # row 0's real state (reversed unrolled scatter)
                slot_vec = np.full(batch, slots[0], np.int32)
                slot_vec[:len(group)] = slots
                args.append(slot_vec)
            try:
                with self._exec_lock, \
                        profiler.op_scope("serve.prefill", cat="serve"):
                    outs = self._admit_op(*args, *self._cache,
                                          *self._draft_cache)
                    first = np.asarray(outs[0])
                    self._cache = list(outs[1:1 + self._n_cache])
                    if self._draft is not None:
                        self._draft_cache = \
                            list(outs[1 + self._n_cache:])
            except Exception:
                _rollback()
                raise
        self._stats.record_batch(
            key, n_real=len(group), n_rows=batch,
            real_elems=sum(r.length for r in group),
            padded_elems=batch * length)
        n_new = self._alloc.allocs - n_alloc0
        if n_new:
            self._stats.incr("page_allocs", n_new)
        if n_hits:
            self._stats.incr("page_prefix_hits", n_hits)
        _sec_bump(prefill_batches=1, prefix_hit_pages=n_hits,
                  pages_in_flight=self._alloc.live_count())
        if self._int8:
            self._note_int8()
        now = time.monotonic()
        for i, req in enumerate(group):
            slot = slots[i]
            pages, commit = mapped[i]
            self._page_table[slot, :] = trash
            self._page_table[slot, :len(pages)] = pages
            self._slot_pages[slot] = list(pages)
            self._slot_commit[slot] = commit
            self._committed += commit
            req.slot = slot
            req.admitted_at = now
            self._slot_req[slot] = req
            self._tokens[slot] = first[i]
            self._cursors[slot] = req.length
            self._active[slot] = True
            self._stats.incr("admitted")
            _sec_bump(admitted=1)
            _tracer.request_instant("serve.decode.admitted",
                                    req.trace_id, cat="serve",
                                    slot=slot, bucket=key)
            self._emit_token(req, int(first[i]), now)
            self._maybe_finish(req, now)

    def _emit_token(self, req, token, now):
        if not req.generated:
            ttft_ms = (now - req.enqueued_at) * 1e3
            # _occ_lock guards the ttft/token windows against a
            # concurrent stats(reset=True) rewind (LatencyWindow itself
            # is unlocked; ServerStats routes through its own lock)
            with self._occ_lock:
                self._ttft.record(ttft_ms)
            _tracer.request_instant("serve.decode.first_token",
                                    req.trace_id, cat="serve",
                                    ttft_ms=round(ttft_ms, 3))
        req.fanout(token)       # appends to req.generated + taps
        req.stream.put(token)
        self._stats.incr("tokens")
        _sec_bump(tokens=1)

    def _boundary_and_step(self):
        """One token boundary: expire/cancel live slots, then run the
        single fixed-shape decode step and fan its tokens out."""
        now = time.monotonic()
        for slot in np.flatnonzero(self._active):
            req = self._slot_req[int(slot)]
            if req.cancelled:
                self._finish_slot(int(slot), "cancelled",
                                  ServerClosedError("request cancelled"))
            elif req.expired(now):
                self._finish_slot(int(slot), "expired",
                                  DeadlineExceededError(
                                      "deadline passed mid-decode"))
        live = int(self._active.sum())
        if live == 0:
            return
        if self._paged:
            self._paged_round(live)
            return
        t0 = time.monotonic()
        try:
            engine.fault_point("serve.decode", step=self._step_count,
                               live=live)
            with profiler.op_scope("serve.decode.step", cat="serve"):
                outs = self._step_op(self._tokens, self._cursors,
                                     self._active, *self._cache)
                nxt = np.asarray(outs[0])
                self._cache = list(outs[1:])
        except Exception as e:  # noqa: BLE001 — fail every live
            # sequence (their cache state is gone if buffers were
            # donated), reset the arena, keep serving
            for slot in np.flatnonzero(self._active):
                self._finish_slot(int(slot), "failed", e)
            self._reset_arena()
            return
        now = time.monotonic()
        step_ms = (now - t0) * 1e3
        self._step_count += 1
        self._stats.incr("decode_steps")
        if self._int8:
            self._note_int8()
        with self._occ_lock:
            self._token_lat.record(step_ms)
            self._occ_sum += live / self._slots
            self._occ_steps += 1
        _sec_bump(live_ratio=live / self._slots, steps=1)
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            req = self._slot_req[slot]
            self._cursors[slot] += 1
            self._tokens[slot] = nxt[slot]
            self._emit_token(req, int(nxt[slot]), now)
            self._maybe_finish(req, now)

    def _paged_round(self, live):
        """One paged scheduling round: extend/COW the write-frontier
        pages, run ``spec_k - 1`` draft proposals (with a draft), then
        ONE verify/decode dispatch, then fan out the ACCEPTED tokens —
        greedy acceptance, the run of proposals matching the target's
        argmax plus the target's correction, so speculative greedy
        output is bit-identical to non-speculative greedy."""
        t0 = time.monotonic()
        k = self._spec_k
        t = self._page_tokens
        trash = self._alloc.trash
        depths = np.zeros(self._slots, np.int32)
        cow_src = np.zeros(self._slots, np.int32)
        cow_dst = np.full(self._slots, trash, np.int32)
        n_alloc0 = self._alloc.allocs
        n_cow = 0
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            req = self._slot_req[slot]
            remaining = req.max_new_tokens - len(req.generated)
            d = int(min(k, max(remaining, 1)))
            depths[slot] = d
            cur = int(self._cursors[slot])
            # every page the block [cur, cur+d-1] writes must be
            # PRIVATE before the dispatch: allocate unmapped frontier
            # pages, copy-on-write still-shared ones (the copy itself
            # rides inside the step executable via (src, dst))
            for pi in range(cur // t, (cur + d - 1) // t + 1):
                pte = int(self._page_table[slot, pi])
                if pte == trash:
                    pg = self._alloc.alloc()
                    self._page_table[slot, pi] = pg
                    self._slot_pages[slot].append(pg)
                elif self._alloc.ref(pte) > 1:
                    pg = self._alloc.alloc()
                    cow_src[slot] = pte
                    cow_dst[slot] = pg
                    self._alloc.release(pte)  # ref > 1: never frees
                    self._slot_pages[slot].remove(pte)
                    self._slot_pages[slot].append(pg)
                    self._page_table[slot, pi] = pg
                    n_cow += 1
        tok_block = np.zeros((self._slots, k), np.int32)
        tok_block[:, 0] = self._tokens
        draft_rounds = 0
        try:
            engine.fault_point("serve.decode", step=self._step_count,
                               live=live)
            if self._draft is not None and k > 1:
                # k-1 chained proposal dispatches; the persistent draft
                # state is NOT donated to them — only the verify step
                # advances it (by the accepted prefix, in-trace)
                dt = self._tokens.copy()
                dcur = self._cursors.copy()
                state = list(self._draft_cache)
                with profiler.op_scope("serve.decode.draft",
                                       cat="serve"):
                    for _ in range(1, k):
                        outs = self._draft_op(dt, dcur, self._active,
                                              *state)
                        dt = np.asarray(outs[0]).astype(np.int32)
                        state = list(outs[1:])
                        dcur = dcur + 1
                        tok_block[:, draft_rounds + 1] = dt
                        draft_rounds += 1
            with profiler.op_scope("serve.decode.step", cat="serve"):
                outs = self._step_op(tok_block, self._cursors, depths,
                                     self._active, self._page_table,
                                     cow_src, cow_dst, *self._cache,
                                     *self._draft_cache)
                ob = np.asarray(outs[0])
                self._cache = list(outs[1:1 + self._n_cache])
                if self._draft is not None:
                    self._draft_cache = list(outs[1 + self._n_cache:])
        except Exception as e:  # noqa: BLE001 — fail every live
            # sequence (their cache state is gone if buffers were
            # donated), reset the arena, keep serving
            for slot in np.flatnonzero(self._active):
                self._finish_slot(int(slot), "failed", e)
            self._reset_arena()
            return
        now = time.monotonic()
        step_ms = (now - t0) * 1e3
        self._step_count += 1
        self._stats.incr("decode_steps")
        if draft_rounds:
            self._stats.incr("spec_rounds")
            self._stats.incr("spec_draft_steps", draft_rounds)
        n_new = self._alloc.allocs - n_alloc0
        if n_new:
            self._stats.incr("page_allocs", n_new)
        if n_cow:
            self._stats.incr("page_cow", n_cow)
        if self._int8:
            self._note_int8()
        with self._occ_lock:
            self._token_lat.record(step_ms)
            self._occ_sum += live / self._slots
            self._occ_steps += 1
        _sec_bump(live_ratio=live / self._slots, steps=1,
                  draft_steps=draft_rounds, cow_copies=n_cow,
                  pages_in_flight=self._alloc.live_count())
        round_prop = 0
        round_acc = 0
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            req = self._slot_req[slot]
            d = int(depths[slot])
            emitted = 0
            for j in range(d):
                tok = int(ob[slot, j])
                self._cursors[slot] += 1
                self._tokens[slot] = tok
                self._emit_token(req, tok, now)
                emitted += 1
                self._maybe_finish(req, now)
                if not self._active[slot]:
                    break
                if j < d - 1 and int(tok_block[slot, j + 1]) != tok:
                    break   # proposal diverged: tok is the correction
            if draft_rounds:
                round_prop += d - 1
                round_acc += max(emitted - 1, 0)
        if draft_rounds:
            _sec_bump(spec_proposed=round_prop,
                      spec_accepted=round_acc)
            with self._occ_lock:
                self._spec_proposed += round_prop
                self._spec_accepted += round_acc

    def _maybe_finish(self, req, now):
        done = (len(req.generated) >= req.max_new_tokens
                or (self._eos_id is not None
                    and req.generated[-1] == self._eos_id))
        if done:
            self._finish_slot(req.slot, "served")

    def _finish_slot(self, slot, outcome, error=None):
        req = self._slot_req[slot]
        self._active[slot] = False
        self._tokens[slot] = 0
        self._cursors[slot] = 0
        self._slot_req[slot] = None
        if self._paged:
            # release every page reference; eviction (free + prefix
            # index drop) happens only when a page's refcount hits zero
            freed = 0
            for pg in self._slot_pages[slot]:
                if self._alloc.release(pg):
                    self._prefix.drop_page(pg)
                    freed += 1
            self._slot_pages[slot] = []
            self._page_table[slot, :] = self._alloc.trash
            self._committed -= self._slot_commit[slot]
            self._slot_commit[slot] = 0
            if freed:
                self._stats.incr("page_frees", freed)
            _sec_bump(pages_in_flight=self._alloc.live_count())
        self._resolve(req, outcome, error)

    def _resolve(self, req, outcome, error=None):
        now = time.monotonic()
        counter = {"served": "served", "expired": "expired_deadline",
                   "cancelled": "cancelled", "failed": "failed"}[outcome]
        self._stats.incr(counter)
        if outcome == "served":
            self._stats.record_latency((now - req.enqueued_at) * 1e3)
            _sec_bump(finished=1)
        elif outcome == "expired":
            _sec_bump(expired_deadlines=1)
        decode_ms = ((now - req.admitted_at) * 1e3
                     if req.admitted_at is not None else -1)
        _tracer.request_end(
            "serve.decode.request", req.trace_id, cat="serve",
            outcome=outcome, tokens=len(req.generated),
            slot=req.slot if req.slot is not None else -1,
            queue_ms=round(((req.admitted_at or now)
                            - req.enqueued_at) * 1e3, 3),
            decode_ms=round(decode_ms, 3))
        if error is None:
            req.stream.put(_DONE)
            if req.future.set_running_or_notify_cancel():
                req.future.set_result(np.asarray(req.generated, np.int32))
        else:
            req.stream.put(error)
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(error)
        # sinks see the terminal AFTER the future resolves, so a tap
        # (the RPC endpoint) can read future.result() without blocking
        req.fanout(_DONE if error is None else error)

    def _resolve_error(self, req, outcome, error):
        """Terminal path for requests that never reached a slot."""
        self._resolve(req, outcome, error)

    def _zero_arena(self):
        """Fresh zeroed cache buffers, COMMITTED to the serving device:
        every steady-state cache input is a committed executable
        output, so an uncommitted warmup arena would carve a second jit
        cache key for the first bucket's admit op — one phantom compile
        on first traffic (observed; the decode tests pin executable
        counts)."""
        import jax
        import jax.numpy as jnp

        dev = self._ctx.jax_device() if self._ctx is not None \
            else jax.devices()[0]
        if self._paged:
            # pools carry one extra TRASH page (index num_pages) that
            # unmapped page-table entries point at
            lead = (self._num_pages + 1, self._page_tokens)
        else:
            lead = (self._slots, self._max_len)
        return [jax.device_put(jnp.zeros(lead + tuple(tail),
                                         dtype=dtype), dev)
                for tail, dtype in self._cache_meta]

    def _zero_draft(self):
        """Fresh zeroed draft running-state rows, committed like
        :meth:`_zero_arena` (same phantom-compile reasoning)."""
        if self._draft is None:
            return []
        import jax
        import jax.numpy as jnp

        dev = self._ctx.jax_device() if self._ctx is not None \
            else jax.devices()[0]
        return [jax.device_put(jnp.zeros((self._slots, 1) + tuple(tail),
                                         dtype=dtype), dev)
                for tail, dtype in self._draft_meta]

    def _reset_arena(self):
        self._cache = self._zero_arena()
        self._tokens[:] = 0
        self._cursors[:] = 0
        self._active[:] = False
        if self._paged:
            self._alloc = PageAllocator(self._num_pages,
                                        self._page_tokens)
            self._prefix = PrefixIndex()
            self._page_table[:] = self._alloc.trash
            self._slot_pages = [[] for _ in range(self._slots)]
            self._slot_commit = [0] * self._slots
            self._committed = 0
            self._draft_cache = self._zero_draft()
            _sec_bump(pages_in_flight=0)

    # -- hot reload ---------------------------------------------------------

    def reload_weights(self, step=None):
        """Swap parameters from the checkpoint manager between token
        boundaries: in-flight sequences finish their current token on
        the old weights and continue on the new — no drops, no
        recompile (parameters are runtime inputs of the step)."""
        if self._ckpt is None:
            raise MXNetError(
                "no checkpoint manager: construct DecodeServer("
                "checkpoint=...) to enable reload_weights()")
        with self._exec_lock:
            with profiler.op_scope("serve.reload", cat="serve"):
                if self._int8:
                    # quantized decode model: int8-native checkpoints
                    # restore directly, fp32 training checkpoints
                    # re-quantize against the stored scales — either
                    # way zero recompiles (runtime graph inputs)
                    meta = self._ckpt.restore(step=step,
                                              restore_rng=False)
                    from ..contrib.quantization import \
                        load_serving_params

                    load_serving_params(self._model,
                                        meta.get("params") or {})
                else:
                    meta = self._ckpt.restore(step=step,
                                              params=self._model,
                                              restore_rng=False)
        self._stats.incr("reloads")
        return {"step": meta["step"], "epoch": meta.get("epoch")}

    # -- observability ------------------------------------------------------

    def _graph_stats_raw(self):
        agg = {"compiles": 0, "reuses": 0}
        for op in (self._admit_op, self._step_op, self._draft_op):
            if op is not None:
                agg["compiles"] += op.stats.get("compiles", 0)
                agg["reuses"] += op.stats.get("reuses", 0)
        return agg

    def live_slots(self):
        return int(self._active.sum())

    def pending(self):
        """Live load gauge for the router's least-loaded dispatch:
        queued admissions (including page-deferred ones) + occupied
        decode slots."""
        return len(self._batcher) + len(self._overflow) \
            + self.live_slots()

    def probe_example(self):
        """A minimal valid prompt (the smallest bucket's shape) — the
        router's health-probe payload (probed with
        ``max_new_tokens=1``)."""
        shape = self._spec.bucket_shapes()[0][1:]
        return np.full(shape, 0, dtype=self._spec.dtype)

    def stats(self, reset=False):
        """One snapshot of the decode tier, same window-scoping contract
        as ``ModelServer.stats`` — the quiescent invariant::

            submitted == served + expired_deadline + failed + cancelled
                         + queue_depth + live_slots
        """
        g = self._graph_stats_raw()
        graph = dict(g, post_warmup_compiles=g["compiles"]
                     - self._warmup_compiles)
        with self._occ_lock:
            occ = (round(self._occ_sum / self._occ_steps, 4)
                   if self._occ_steps else None)
            ttft = self._ttft.snapshot()
            token = self._token_lat.snapshot()
            proposed, accepted = self._spec_proposed, self._spec_accepted
            if reset:
                self._occ_sum = 0.0
                self._occ_steps = 0
                self._ttft.reset()
                self._token_lat.reset()
                self._spec_proposed = 0
                self._spec_accepted = 0
        extra = {"graph": graph, "buckets": repr(self._spec),
                 "slots": {"max": self._slots, "live": self.live_slots(),
                           "occupancy": occ,
                           "max_len": self._max_len},
                 "ttft": ttft, "token_latency": token}
        if self._paged:
            hbm = 0
            for tail, dtype in (self._cache_meta or ()):
                elems = (self._num_pages + 1) * self._page_tokens
                for s in tail:
                    elems *= int(s)
                hbm += elems * int(np.dtype(dtype).itemsize)
            extra["pages"] = {
                "num": self._num_pages,
                "page_tokens": self._page_tokens,
                "per_slot": self._pages_per_slot,
                "free": self._alloc.free_count(),
                "in_flight": self._alloc.live_count(),
                "committed": self._committed,
                "deferred": len(self._overflow),
                "hbm_bytes": hbm}
        if self._spec_k > 1:
            extra["spec"] = {
                "k": self._spec_k,
                "draft": self._draft is not None,
                "proposed": proposed, "accepted": accepted,
                "accept_rate": (round(accepted / proposed, 4)
                                if proposed else None)}
        return self._stats.snapshot(
            queue_depth=len(self._batcher) + len(self._overflow),
            in_flight=self.live_slots(), reset=reset, extra=extra)


# ---------------------------------------------------------------------------
# reference decode model


class TinyDecoder(Block):
    """Minimal runnable decode model: greedy argmax over a cumulative
    mean of token embeddings — the per-slot state is a genuine
    ``(slots, max_len, embed)`` cache of per-position embeddings, so it
    exercises the arena exactly like a transformer KV cache while
    staying a two-matmul CPU-friendly graph.

    Used by tests/test_decode.py and tools/decode_smoke.py; it doubles
    as the executable documentation of the decode model contract.  Math
    notes:

    - every per-slot quantity depends only on that slot's row, so
      continuous vs whole-batch decode is bit-identical by construction
      (the acceptance parity gate);
    - inactive slots are masked out of cache writes and divide by
      ``max(cursor+1, 1)``, so garbage slots can never NaN the batch.

    With ``proj_block=True`` the output projection is an ``nn.Dense``
    CHILD block instead of a raw parameter, which makes the model
    quantizable: ``contrib.quantization.quantize_net(model, ...)``
    swaps the projection for a compiled int8 Dense and the whole decode
    step (CachedStepOp) carries the int8 matmul — the INT8 decode path.
    Per-slot independence survives because calibrated ranges are
    runtime constants, not batch reductions.
    """

    def __init__(self, vocab=64, embed=16, proj_block=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self.vocab = int(vocab)
        self.embed_dim = int(embed)
        self._proj_block = bool(proj_block)
        self.embedding = self.params.get("embedding",
                                         shape=(vocab, embed))
        if proj_block:
            from ..gluon import nn as _gnn

            self.proj = _gnn.Dense(vocab, use_bias=False, flatten=False,
                                   in_units=embed)
        else:
            self.proj = self.params.get("proj", shape=(embed, vocab))

    def _logits(self, h):
        """Raw (..., d) hidden -> raw (..., vocab) logits, through the
        Dense child (quantizable) or the raw projection parameter."""
        if self._proj_block:
            return self.proj(_wrap(h))._data
        return h @ self.proj.data()._data

    def prefill(self, prompts, lengths):
        import jax.numpy as jnp

        E = self.embedding.data()._data
        p = prompts._data                      # (B, L) int32
        ln = lengths._data                     # (B,) int32
        emb = jnp.take(E, p, axis=0)           # (B, L, d)
        m = (jnp.arange(emb.shape[1])[None, :] < ln[:, None])
        h = jnp.sum(emb * m[..., None].astype(emb.dtype), axis=1) \
            / jnp.maximum(ln, 1).astype(emb.dtype)[:, None]
        first = jnp.argmax(self._logits(h), axis=-1).astype(jnp.int32)
        return _wrap(first), _wrap(emb)

    def decode_step(self, tokens, cursors, active, cache):
        import jax.numpy as jnp

        E = self.embedding.data()._data
        t, cur = tokens._data, cursors._data
        act, c = active._data, cache._data
        e = jnp.take(E, t, axis=0)             # (S, d)
        pos = jnp.arange(c.shape[1])[None, :]
        write = (pos == cur[:, None]) & act[:, None]
        c = jnp.where(write[..., None], e[:, None, :], c)
        seen = (pos <= cur[:, None])
        h = jnp.sum(c * seen[..., None].astype(c.dtype), axis=1) \
            / jnp.maximum(cur + 1, 1).astype(c.dtype)[:, None]
        nxt = jnp.argmax(self._logits(h), axis=-1).astype(jnp.int32)
        return _wrap(nxt), _wrap(c)


class TinyDraft(Block):
    """Reference DRAFT model for speculative decoding: the running-sum
    reformulation of :class:`TinyDecoder`, SHARING the target's
    parameters.

    Where the target re-reduces its whole ``(slots, max_len, embed)``
    cache every step (O(max_len) work, like attention over the full
    KV cache), the draft keeps ONE ``(slots, 1, embed)`` running-sum
    row per slot and folds each consumed token in with a single add —
    an O(embed) step, so proposals are nearly free next to verifies.
    It predicts the same cumulative-mean argmax as the target (modulo
    float summation order, which is why verification — not the draft —
    decides every emitted token), so acceptance sits near 1 while
    correctness never depends on it.

    Draft model contract (docs/serving.md)::

        prefill(prompts, lengths) -> (first_tokens, *state_rows)
            state_rows : (batch, L, ...) — row ``lengths[i] - 1`` seeds
            slot i's position-free running state at admission
        decode_step(tokens, cursors, active, *state)
            -> (next_tokens, *new_state)
            state : (max_slots, 1, ...) running rows (POSITION-FREE —
            drafts with per-position KV state are out of contract)
        accept(tok_block, accepted, active, *state) -> (*new_state,)
            fold the first ``accepted[i]`` tokens of ``tok_block[i]``
            into slot i's state — runs INSIDE the verify executable,
            re-syncing the draft to exactly the committed tokens
    """

    def __init__(self, target, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if not isinstance(target, Block):
            raise MXNetError("TinyDraft wraps a TinyDecoder target")
        self.model = target
        self.vocab = target.vocab
        self.embed_dim = target.embed_dim

    def prefill(self, prompts, lengths):
        import jax.numpy as jnp

        E = self.model.embedding.data()._data
        p = prompts._data                      # (B, L) int32
        ln = lengths._data                     # (B,) int32
        emb = jnp.take(E, p, axis=0)           # (B, L, d)
        m = (jnp.arange(emb.shape[1])[None, :] < ln[:, None])
        cum = jnp.cumsum(emb * m[..., None].astype(emb.dtype), axis=1)
        idx = jnp.clip(ln - 1, 0).reshape(-1, 1, 1)
        h = jnp.take_along_axis(cum, idx, axis=1)[:, 0] \
            / jnp.maximum(ln, 1).astype(emb.dtype)[:, None]
        first = jnp.argmax(self.model._logits(h),
                           axis=-1).astype(jnp.int32)
        return _wrap(first), _wrap(cum)

    def decode_step(self, tokens, cursors, active, state):
        import jax.numpy as jnp

        E = self.model.embedding.data()._data
        t, cur = tokens._data, cursors._data
        act, s = active._data, state._data     # (S, 1, d)
        s2 = s[:, 0] + jnp.take(E, t, axis=0)
        h = s2 / jnp.maximum(cur + 1, 1).astype(s.dtype)[:, None]
        nxt = jnp.argmax(self.model._logits(h),
                         axis=-1).astype(jnp.int32)
        ns = jnp.where(act[:, None, None], s2[:, None, :], s)
        return _wrap(nxt), _wrap(ns)

    def accept(self, tok_block, accepted, active, state):
        import jax.numpy as jnp

        E = self.model.embedding.data()._data
        tb, acc = tok_block._data, accepted._data
        act, s = active._data, state._data
        e = jnp.take(E, tb, axis=0)            # (S, k, d)
        m = (jnp.arange(tb.shape[1])[None, :] < acc[:, None]) \
            & act[:, None]
        s2 = s[:, 0] + jnp.sum(e * m[..., None].astype(e.dtype), axis=1)
        ns = jnp.where(act[:, None, None], s2[:, None, :], s)
        return (_wrap(ns),)
