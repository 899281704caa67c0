"""Continuous-batching serve engine: slot scheduler over the GOOM models.

``Engine`` owns a fixed set of persistent jitted executables — the
chunked-prefill steps (see ``prefill.py``), two *fused admission
finishers* (final prompt piece + first-token argmax + scatter into the
slot caches + token/position/termination bookkeeping, one dispatch), and
fused multi-step decode over the full slot batch (``decode_multi``, one
executable per horizon — the adaptive policy only ever uses k=1 and
k=``eos_scan_every``) — compiled at the first request and reused for the
engine's whole lifetime: shapes are fixed at ``(max_slots,)`` /
``(1, chunk)`` / ``(1, 1)``, so nothing ever re-traces mid-flight.

Scheduling loop (one ``step()``):

  1. *admit*  — while a slot is free and requests wait: chunked-prefill
     the next prompt into a fresh batch-1 cache, finishing with the fused
     step that samples the first token, scatters the state into the
     slot, and arms the slot's on-device termination row (active mask,
     EOS id, remaining token budget);
  2. *decode* — one jitted ``decode_multi`` dispatch advances every slot
     by a horizon of k fused steps (``_pick_horizon``: k=1 while
     admissions wait or a deadline is imminent, ``eos_scan_every``
     otherwise).  Slots that hit EOS or their budget mid-horizon freeze
     token/pos/cache writes in-device, so outputs stay bit-identical to
     the k=1 path.  Tokens and positions feed back on-device; the
     returned ``(k, max_slots)`` token block enters the ``_TokenFlight``
     double-buffered async device→host lane and materializes lazily
     (``_flush`` / ``_flush_stream``), so the loop is pure dispatch
     between finish events;
  3. *evict*  — finished sequences (EOS or token budget) release their
     slots on the host; freed slots admit new requests on the next step.

Per-sequence recurrent state is fixed-size (the GOOM pitch), so joins
and evictions are single-row scatters.  Global-attention KV lives in a
block-granular page pool with per-slot page tables
(``state_cache.PagePool``); admission consults a host-side radix index
of cached prompt prefixes (``state_cache.PrefixIndex``) and, on a hit,
restores the GOOM/SSM scan carry from a page-boundary checkpoint and
resumes chunked prefill at the divergence point — prefill cost becomes
O(suffix) on hit traffic.  See docs/serving.md.

Request lifecycle terminals (``finish_reason``): ``"length"`` (token
budget), ``"stop"`` (EOS), ``"timeout"`` (``deadline_ms`` expired — the
slot is evicted mid-decode and the partial output kept), ``"cancelled"``
(``Engine.cancel``, e.g. a disconnected client — no output is kept;
``result()`` returns the ``CANCELLED`` sentinel, distinct from the
``KeyError`` an unknown uid raises).  Streaming: requests with
``stream=True`` get their first token at admission, then per-dispatch
flushes of *completed* transfer blocks through the engine's
``stream_callback`` — the hook the HTTP front door (``serve/api``)
feeds SSE from — without ever blocking the dispatch loop on a transfer
still in flight.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model import DecoderLM
from . import state_cache
from .prefill import ChunkedPrefill
from .steps import _engine_scope, make_decode_multi


class _Cancelled:
    """Singleton terminal result of a cancelled request."""

    def __repr__(self):
        return "CANCELLED"

    def __bool__(self):
        return False


#: Sentinel returned by ``Engine.result`` for cancelled uids — a distinct
#: terminal state, so cancellation is distinguishable from "never
#: submitted" (which raises ``KeyError``) and from an empty generation.
CANCELLED = _Cancelled()


@dataclasses.dataclass
class Request:
    """One generation request.

    ``max_new_tokens`` counts every generated token (the first comes from
    the prompt's last logits).  ``prompt + max_new_tokens`` must fit the
    engine's ``page_len``.

    ``deadline_ms`` bounds the request's total latency from ``submit``
    (queue wait included): past it the request is evicted with partial
    output and ``finish_reason() == "timeout"``.  ``stream=True`` opts
    into per-step token flushes through the engine's ``stream_callback``.
    """

    uid: Any
    prompt: Sequence[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    deadline_ms: Optional[float] = None
    stream: bool = False


def _deadline_clock() -> float:
    """The scheduler's only clock read (``time.monotonic``).

    Deadline stamping, queue-expiry checks and the per-step sweep all
    route through here — the step loop itself stays dispatch-only, and
    goomcheck rule GC204 rejects any other ``time.monotonic()`` call in
    this module.  Resolves ``time`` from module globals at call time so
    tests can monkeypatch ``scheduler.time`` with a counting fake.
    """
    return time.monotonic()


@dataclasses.dataclass
class _Active:
    request: Request
    slot: int
    first: Any            # first generated token: device scalar until flushed
    out: List[int]        # materialized tokens (host)
    start_step: int       # engine step index of this request's first decode
    n_decoded: int = 0    # decode tokens produced (incl. not yet in `out`)
    deadline: Optional[float] = None   # absolute time.monotonic() bound
    n_streamed: int = 0   # tokens already pushed through stream_callback


class _TokenFlight:
    """Double-buffered async device→host lane for decode-token blocks.

    ``push`` starts an async device→host copy of each ``(k, max_slots)``
    block the moment its dispatch is issued, so block i transfers while
    block i+1 computes.  ``take(complete_only=True)`` — the streaming
    path — materializes every block *except* the newest (still
    computing/transferring), so SSE flushes never block the dispatch
    loop; ``take()`` — finish events — blocks on everything in flight.

    Every host materialization in the scheduler routes through this
    class: goomcheck rule GC206 flags ``np.asarray`` / ``jax.device_get``
    host pulls anywhere else in the serve hot loop.  ``n_syncs`` counts
    materialization points (block takes + admission-token scalars) for
    the ``/status`` host-sync budget.
    """

    def __init__(self):
        self._blocks: List[Any] = []
        self.n_syncs = 0

    def __len__(self) -> int:
        return len(self._blocks)

    def push(self, block) -> None:
        if hasattr(block, "copy_to_host_async"):
            block.copy_to_host_async()
        self._blocks.append(block)

    def take(self, complete_only: bool = False) -> Optional[np.ndarray]:
        """Buffered blocks as one ``(rows, max_slots)`` array, oldest
        first; None when nothing qualifies.  One host sync per call."""
        n = len(self._blocks) - (1 if complete_only else 0)
        if n <= 0:
            return None
        blocks, self._blocks = self._blocks[:n], self._blocks[n:]
        self.n_syncs += 1
        if len(blocks) == 1:
            return np.asarray(blocks[0])
        return np.concatenate([np.asarray(b) for b in blocks], axis=0)

    def scalar(self, x) -> int:
        """Materialize one device scalar (the admission-time first token)."""
        self.n_syncs += 1
        return int(np.asarray(x))


class Engine:
    """Continuous-batching engine over a ``DecoderLM``.

    >>> eng = Engine(model, params, max_slots=4, page_len=128, chunk=16)
    >>> eng.submit(Request(uid="a", prompt=[3, 1, 4], max_new_tokens=8))
    >>> results = eng.run()          # {"a": [8 generated token ids]}

    Greedy sampling; plain token prompts (no frontend embeddings).
    """

    def __init__(
        self,
        model: DecoderLM,
        params,
        *,
        max_slots: int = 8,
        page_len: int = 512,
        chunk: int = 64,
        backend: str = "auto",
        mesh=None,
        seq_shards="auto",
        blocks=None,
        eos_scan_every: int = 8,
        stream_callback: Optional[Callable[[Any, List[int],
                                            Optional[str]], None]] = None,
        page_size: Optional[int] = None,
        cache_pages: Optional[int] = None,
        prefix_reuse: bool = True,
    ):
        if model.cfg.frontend is not None:
            raise NotImplementedError(
                "serve.Engine handles token prompts only (no frontend "
                "prefix embeddings)")
        if chunk > page_len:
            raise ValueError(f"chunk {chunk} exceeds page_len {page_len}")
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.page_len = page_len
        # KV paging geometry.  page_size defaults to the prefill chunk so
        # chunk boundaries land on page boundaries: checkpoints then exist
        # at every page edge and a resumed prefill replays the exact chunk
        # schedule of the from-scratch one (bit-identical outputs).
        self.page_size = int(page_size if page_size is not None else chunk)
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self._max_blocks = -(-page_len // self.page_size)
        self._kv_len = self._max_blocks * self.page_size
        if cache_pages is None:
            # room for ~2 slots' worth of finished prefixes to outlive
            # their slots before LRU eviction kicks in
            cache_pages = 2 * self._max_blocks
        self._n_pages = max_slots * self._max_blocks + int(cache_pages)
        self.prefix_reuse = bool(prefix_reuse)
        # `eos_scan_every` doubles as the maximum decode horizon: EOS
        # requests need their token values on the host at that cadence
        # anyway, so the adaptive policy fuses up to that many decode
        # steps per dispatch (overrun past EOS/budget is frozen in-device
        # and trimmed at flush, so outputs are unchanged).  K=1 degrades
        # to the single-step engine.
        self.eos_scan_every = max(1, eos_scan_every)
        # called as stream_callback(uid, new_tokens, finish_reason) after
        # each flush for requests with stream=True; finish_reason is None
        # mid-stream and "length"/"stop"/"timeout"/"cancelled" exactly
        # once, on the terminal event.  Settable after construction (the
        # api gateway attaches itself here).
        self.stream_callback = stream_callback

        self._prefill = ChunkedPrefill(
            model, chunk, backend=backend, mesh=mesh, seq_shards=seq_shards,
            blocks=blocks)

        # fused multi-step decode: one compiled executable per horizon k,
        # built lazily by _decode_fn (the adaptive policy only ever uses
        # k=1 and k=eos_scan_every, so at most two compilations)
        self._scope = dict(backend=backend, mesh=mesh,
                           seq_shards=seq_shards, blocks=blocks)
        self._decode_multi: Dict[int, Callable] = {}
        # fused admission finishers: the prompt's final piece, the argmax
        # of its logits, the scatter into the slot caches, and the
        # token/position/termination bookkeeping all land in ONE dispatch
        # — admission costs (head dispatches + 1) instead of a string of
        # eager ops.  write_pages/table_row route the dense cache's KV
        # blocks into the slot's pool pages (sentinel entries skip shared
        # prefix pages).
        def _finish_admit(logits, caches, next_pos, slot_caches, slot,
                          tok_vec, pos_vec, write_pages, table_row,
                          term, eos_id, budget):
            first = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[0]
            slot_caches = state_cache.write_slot_paged(
                slot_caches, caches, slot, write_pages, table_row)
            # arm the slot's on-device termination row: decode_multi
            # freezes it at EOS / budget edge without a host round-trip
            alive = (budget > 0) & (first != eos_id)
            term = {
                "active": term["active"].at[slot].set(alive),
                "eos": term["eos"].at[slot].set(eos_id),
                "remaining": term["remaining"].at[slot].set(budget),
            }
            return (first, slot_caches, tok_vec.at[slot].set(first),
                    pos_vec.at[slot].set(next_pos), term)

        def admit_chunk(params, slot_caches, caches, tokens, positions,
                        slot, tok_vec, pos_vec, write_pages, table_row,
                        term, eos_id, budget):
            with _engine_scope(backend, mesh, seq_shards, blocks):
                logits, caches = model.prefill(params, tokens, caches,
                                               positions=positions)
            return _finish_admit(logits, caches, positions[0, -1] + 1,
                                 slot_caches, slot, tok_vec, pos_vec,
                                 write_pages, table_row, term, eos_id,
                                 budget)

        def admit_tail(params, slot_caches, caches, token, index,
                       slot, tok_vec, pos_vec, write_pages, table_row,
                       term, eos_id, budget):
            with _engine_scope(backend, mesh, seq_shards, blocks):
                logits, caches = model.decode_step(params, token, caches,
                                                   index)
            return _finish_admit(logits, caches, index[0] + 1,
                                 slot_caches, slot, tok_vec, pos_vec,
                                 write_pages, table_row, term, eos_id,
                                 budget)

        self._admit_chunk = jax.jit(admit_chunk, donate_argnums=(1,))
        self._admit_tail = jax.jit(admit_tail, donate_argnums=(1,))

        self._caches = model.init_slot_caches(
            max_slots, page_len, page_size=self.page_size,
            cache_pages=int(cache_pages))
        # fresh per-request prefill cache as one compiled executable (the
        # eager zeros tree costs a dispatch per leaf otherwise)
        self._fresh = jax.jit(lambda: model.init_caches(1, self._kv_len))
        self._alloc = state_cache.SlotAllocator(max_slots)
        # host-side page bookkeeping: the pool refcounts every page, the
        # radix index maps cached prompt block-prefixes to (page, carry
        # checkpoint), and _slot_pages records the refs each slot holds
        self._pool = state_cache.PagePool(self._n_pages)
        self._index = state_cache.PrefixIndex(self._pool, self.page_size)
        self._slot_pages: Dict[int, List[int]] = {}
        self._tokens_saved = 0
        # paged/dense skeleton of the slot tree (from shapes only): the
        # checkpoint strip walks dense batch-1 caches, which cannot tell
        # paged layers apart on their own
        meta = state_cache.paged_meta(jax.eval_shape(
            lambda: model.init_slot_caches(
                max_slots, page_len, page_size=self.page_size,
                cache_pages=int(cache_pages))))
        self._snapshot = jax.jit(
            lambda caches: state_cache.strip_checkpoint(meta, caches))
        self._gather = jax.jit(state_cache.gather_prefix)
        self._clear = jax.jit(state_cache.clear_slot_pages,
                              donate_argnums=(0,))
        self._queue: Deque[Request] = deque()
        self._active: Dict[int, _Active] = {}
        # next input token and its absolute position, per slot — both
        # device-resident: decode feeds itself without host round-trips
        self._tokens = jnp.zeros((max_slots,), jnp.int32)
        self._pos = jnp.zeros((max_slots,), jnp.int32)
        # per-slot termination state (active / eos / remaining), carried
        # on device by decode_multi and armed by the admission finishers
        self._term = state_cache.init_term_state(max_slots)
        self._results: Dict[Any, List[int]] = {}
        self._finish_reason: Dict[Any, str] = {}
        self._cancelled: set = set()
        # count of live (queued or active) requests carrying a deadline:
        # the step loop reads the clock only when this is nonzero, so a
        # deadline-free engine stays pure dispatch (regression-tested)
        self._n_deadlines = 0
        self._deadline_at: Dict[Any, float] = {}  # queued uids only
        # per-step wall-time estimate (EMA-free: last sweep-to-sweep
        # diff), maintained only while deadlines are live — it feeds the
        # "deadline imminent" horizon clamp without extra clock reads
        self._step_est: Optional[float] = None
        self._last_sweep: Optional[float] = None
        # decode outputs not yet materialized on the host: (k, max_slots)
        # token blocks in the async transfer lane, covering engine steps
        # [_pending_base, _step_id).  The host only blocks on them at a
        # finish event (or per-dispatch under EOS scanning); streaming
        # consumes completed blocks only — see _flush / _flush_stream.
        self._step_id = 0
        self._flight = _TokenFlight()
        self._pending_base = 0
        # decode dispatch counters (see decode_stats)
        self.n_dispatches = 0
        self.n_decode_steps = 0
        self._last_horizon = 0

    # -- bookkeeping --------------------------------------------------------
    @property
    def n_active(self) -> int:
        return len(self._active)

    @property
    def n_waiting(self) -> int:
        return len(self._queue)

    @property
    def has_work(self) -> bool:
        return bool(self._active or self._queue)

    def prefix_stats(self) -> Dict[str, Any]:
        """Prefix-cache and page-pool counters (host-side, cheap).

        The gateway polls this into ``ServeMetrics`` so ``GET /status``
        exposes hit rate, tokens saved, and pool occupancy."""
        idx, pool = self._index, self._pool
        return {
            "enabled": self.prefix_reuse,
            "lookups": idx.n_lookups,
            "hits": idx.n_hits,
            "hit_rate": idx.n_hits / max(idx.n_lookups, 1),
            "hit_tokens": idx.n_hit_tokens,
            "prefill_tokens_saved": self._tokens_saved,
            "nodes": idx.n_nodes,
            "evicted": idx.n_evicted,
            "page_size": self.page_size,
            "pages": {
                "total": pool.n_pages,
                "used": pool.n_used,
                "free": pool.n_free,
                "occupancy": pool.n_used / pool.n_pages,
            },
        }

    def decode_stats(self) -> Dict[str, Any]:
        """Multi-step decode counters (host-side, cheap).

        ``dispatches`` counts fused decode dispatches, ``decode_steps``
        the token steps they covered — their ratio is the realized
        horizon — and ``host_syncs`` the device→host materialization
        points (block takes + admission-token scalars).  The gateway
        polls this into ``ServeMetrics`` so ``GET /status`` exposes
        tokens-per-dispatch and host-syncs-per-token live."""
        d, s = self.n_dispatches, self.n_decode_steps
        syncs = self._flight.n_syncs
        return {
            "dispatches": d,
            "decode_steps": s,
            "tokens_per_dispatch": s / max(d, 1),
            "host_syncs": syncs,
            "syncs_per_token": syncs / max(s, 1),
            "horizon_max": self.eos_scan_every,
            "last_horizon": self._last_horizon,
        }

    def result(self, uid) -> List[int]:
        """Terminal result of a request.

        Generated tokens for a finished request (partial output for a
        ``"timeout"``), the ``CANCELLED`` sentinel for a cancelled one,
        and ``KeyError`` for a uid that was never submitted — the three
        terminal states are mutually distinguishable.
        """
        if uid in self._cancelled:
            return CANCELLED
        return self._results[uid]

    def finish_reason(self, uid) -> str:
        """Why a request terminated: length | stop | timeout | cancelled
        (KeyError while still queued/active or never submitted)."""
        return self._finish_reason[uid]

    def pop_result(self, uid):
        """``result(uid)`` that also forgets the request (long-lived
        servers drain terminal state through this to stay bounded)."""
        out = self.result(uid)
        self._cancelled.discard(uid)
        self._results.pop(uid, None)
        self._finish_reason.pop(uid, None)
        return out

    # -- request lifecycle ---------------------------------------------------
    def validate(self, request: Request) -> None:
        """Raise ValueError for a request the engine would reject.

        Split from ``submit`` so a front door can reject bad requests
        (HTTP 400) on its own thread before handing off to the engine's.
        """
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(request.prompt) < 1:
            raise ValueError("empty prompt: need at least one token")
        total = len(request.prompt) + request.max_new_tokens
        if total > self.page_len:
            raise ValueError(
                f"request {request.uid!r}: prompt + max_new_tokens = {total} "
                f"exceeds page_len {self.page_len}")
        if request.deadline_ms is not None and request.deadline_ms <= 0:
            raise ValueError("deadline_ms must be > 0 when set")
        uid = request.uid
        if (uid in self._results or uid in self._cancelled
                or any(r.uid == uid for r in self._queue)
                or any(a.request.uid == uid for a in self._active.values())):
            raise ValueError(f"duplicate request uid {uid!r}")

    def submit(self, request: Request) -> None:
        self.validate(request)
        if request.deadline_ms is not None:
            # stamp the absolute bound at arrival: queue wait counts
            request.deadline_ms = float(request.deadline_ms)
            self._deadline_at[request.uid] = (
                _deadline_clock() + request.deadline_ms / 1e3)
            self._n_deadlines += 1
        self._queue.append(request)

    def cancel(self, uid) -> bool:
        """Cancel a queued or active request (client disconnect path).

        An active request's slot is evicted immediately — freed for the
        next admission — and no output is kept: ``result(uid)`` returns
        ``CANCELLED``.  Returns False when the uid is unknown or already
        terminal (cancellation after the fact is a no-op).
        """
        for req in self._queue:
            if req.uid == uid:
                self._queue.remove(req)
                self._terminal_deadline(req.uid, req.deadline_ms is not None)
                self._mark_cancelled(req)
                return True
        for slot, act in list(self._active.items()):
            if act.request.uid == uid:
                del self._active[slot]
                self._release_slot(slot)
                self._terminal_deadline(uid, act.deadline is not None)
                self._mark_cancelled(act.request)
                return True
        return False

    def _release_slot(self, slot: int) -> None:
        """Return a slot and its page refs to their pools.

        Order matters: the slot's page tables are reset to the sentinel
        *before* its pages are unrefed — the dead row keeps decoding
        (static shapes), and a stale table would scatter KV into pages
        the pool may already have handed to another slot."""
        self._caches = self._clear(self._caches, jnp.asarray(slot, jnp.int32))
        for pg in self._slot_pages.pop(slot, []):
            self._pool.unref(pg)
        self._alloc.release(slot)

    def _mark_cancelled(self, request: Request) -> None:
        self._cancelled.add(request.uid)
        self._finish_reason[request.uid] = "cancelled"
        self._emit(request, [], "cancelled")

    def _terminal_deadline(self, uid, had_deadline: bool) -> None:
        self._deadline_at.pop(uid, None)
        if had_deadline:
            self._n_deadlines -= 1
            if not self._n_deadlines:
                # estimates die with the deadlines: a later deadline must
                # not consult a sweep timestamp from a different era
                self._last_sweep = self._step_est = None

    def _emit(self, request: Request, toks: List[int],
              reason: Optional[str]) -> None:
        if self.stream_callback is not None and request.stream:
            self.stream_callback(request.uid, toks, reason)

    def _finish(self, act: _Active, reason: str = "length") -> Any:
        self._results[act.request.uid] = act.out
        self._finish_reason[act.request.uid] = reason
        del self._active[act.slot]
        self._release_slot(act.slot)
        self._terminal_deadline(act.request.uid, act.deadline is not None)
        return act.request.uid

    def _consume(self, arr: np.ndarray) -> None:
        """Fold a materialized ``(rows, max_slots)`` token block into every
        active ``out``; rows cover steps ``_pending_base .. +rows``."""
        rows = arr.shape[0]
        for act in self._active.values():
            if not act.out:  # first generated token still on device
                act.out.append(self._flight.scalar(act.first))
            # decode step s landed in row s - _pending_base; a slot frozen
            # in-device repeats its last token past EOS/budget, so `hi`
            # (the budget edge, capped at what materialized) and the EOS
            # trim in step() drop exactly the frozen overrun
            lo = act.start_step + (len(act.out) - 1) - self._pending_base
            hi = min(act.start_step + act.n_decoded - self._pending_base,
                     rows)
            if hi > lo:
                act.out.extend(int(t) for t in arr[lo:hi, act.slot])
        self._pending_base += rows

    def _flush(self) -> None:
        """Materialize ALL pending decode outputs into every active ``out``.

        One host sync covers every dispatch since the last flush: the step
        loop stays dispatch-only between finish events unless an active
        request needs EOS scanning (then once per horizon)."""
        arr = self._flight.take()
        if arr is None:
            for act in self._active.values():
                if not act.out:
                    act.out.append(self._flight.scalar(act.first))
            return
        self._consume(arr)

    def _flush_stream(self) -> None:
        """Streaming flush: completed transfer blocks only.

        The newest block is still computing/transferring and is left in
        flight, so this never blocks the dispatch loop; its tokens reach
        clients one dispatch later (or at the next finish event)."""
        arr = self._flight.take(complete_only=True)
        if arr is not None:
            self._consume(arr)

    def _admit(self) -> List[Any]:
        finished = []
        while self._queue and self._alloc.n_free:
            req = self._queue.popleft()
            deadline = self._deadline_at.pop(req.uid, None)
            if deadline is not None and _deadline_clock() >= deadline:
                # expired while waiting: never admitted, empty output
                self._results[req.uid] = []
                self._finish_reason[req.uid] = "timeout"
                self._n_deadlines -= 1
                self._emit(req, [], "timeout")
                finished.append(req.uid)
                continue
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            p = int(prompt.shape[0])
            c = self._prefill.chunk
            r = p % c
            ps, mb = self.page_size, self._max_blocks
            sent = self._pool.sentinel
            slot = self._alloc.allocate()
            # the fused step reprocesses the prompt's final piece (a full
            # chunk when the length divides, the last token otherwise) —
            # a prefix hit must stop short of it so its logits are real
            fused_start = p - (1 if r else c)
            hit_blocks, hit_pages, ckpt = 0, [], None
            if self.prefix_reuse:
                hit_blocks, hit_pages, ckpt = self._index.match(
                    prompt.tolist(), fused_start // ps)
                # resume only on chunk-aligned boundaries: the suffix then
                # replays the from-scratch chunk schedule bit-for-bit
                # (always aligned when page_size % chunk == 0)
                while hit_blocks and (hit_blocks * ps) % c:
                    hit_blocks -= 1
                hit_pages = hit_pages[:hit_blocks]
            # the slot takes its page refs up front, before eviction can
            # run: reserve() below may drop the very index nodes we hit
            for pg in hit_pages:
                self._pool.ref(pg)
            self._index.reserve(mb - hit_blocks)
            fresh = self._pool.alloc(mb - hit_blocks)
            if fresh is None:  # sizing invariant guarantees this never trips
                raise RuntimeError("page pool exhausted at admission")
            table_row = hit_pages + fresh             # the slot's page table
            write_row = [sent] * hit_blocks + fresh   # skip shared pages
            hit_len = hit_blocks * ps
            if hit_len:
                # densify the cached prefix: pool pages through the hit
                # blocks (zeros past them) + the carry checkpoint at hit_len
                gather_row = np.asarray(
                    hit_pages + [sent] * (mb - hit_blocks), np.int32)
                caches = self._gather(self._caches, ckpt, gather_row)
                self._tokens_saved += hit_len
            else:
                caches = self._fresh()
            head = prompt[hit_len:fused_start]
            captures: Dict[int, Any] = {}
            if head.size:
                _, caches, _ = self._prefill(
                    self.params, head, caches, start=hit_len,
                    capture_every=ps,
                    capture=lambda pos, tree: captures.__setitem__(
                        pos, self._snapshot(tree)))
            slot = jnp.asarray(slot, jnp.int32)
            wp = np.asarray(write_row, np.int32)
            tr = np.asarray(table_row, np.int32)
            # termination row: -1 = "no EOS" (no token id is negative);
            # the budget counts decode steps after the admission token
            eos = np.int32(-1 if req.eos_id is None else req.eos_id)
            budget = np.int32(req.max_new_tokens - 1)
            if r:
                (first, self._caches, self._tokens, self._pos,
                 self._term) = self._admit_tail(
                    self.params, self._caches, caches,
                    prompt[None, -1:], np.asarray([p - 1], np.int32),
                    slot, self._tokens, self._pos, wp, tr,
                    self._term, eos, budget)
            else:
                (first, self._caches, self._tokens, self._pos,
                 self._term) = self._admit_chunk(
                    self.params, self._caches, caches,
                    prompt[None, p - c:],
                    np.arange(p - c, p, dtype=np.int32)[None],
                    slot, self._tokens, self._pos, wp, tr,
                    self._term, eos, budget)
            self._slot_pages[int(slot)] = list(table_row)
            if self.prefix_reuse:
                # publish only blocks fully covered by full-chunk calls
                # (captured checkpoints): future hits on them replay the
                # same compiled schedule regardless of this prompt's tail
                pub_blocks = (hit_len + (head.size // c) * c) // ps
                ckpts = [None] * hit_blocks + [
                    captures.get((b + 1) * ps)
                    for b in range(hit_blocks, pub_blocks)]
                self._index.publish(prompt.tolist(),
                                    table_row[:pub_blocks], ckpts)
            act = _Active(request=req, slot=int(slot), first=first, out=[],
                          start_step=self._step_id, deadline=deadline)
            self._active[int(slot)] = act
            if req.max_new_tokens == 1 or req.eos_id is not None or req.stream:
                # needs the value now: the request may finish before any
                # decode step, and a streaming client gets its first token
                # at admission (TTFT does not wait for a decode horizon)
                act.out.append(self._flight.scalar(first))
                reason = None
                if req.eos_id is not None and act.out[0] == req.eos_id:
                    reason = "stop"
                elif req.max_new_tokens == 1:
                    reason = "length"
                act.n_streamed = len(act.out)
                if req.stream or reason is not None:
                    self._emit(req, list(act.out), reason)
                if reason is not None:
                    finished.append(self._finish(act, reason))
        return finished

    # -- the hot loop --------------------------------------------------------
    def _decode_fn(self, k: int) -> Callable:
        """Jitted fused k-step decode, compiled once per distinct horizon
        (the adaptive policy only ever uses 1 and ``eos_scan_every``)."""
        fn = self._decode_multi.get(k)
        if fn is None:
            fn = jax.jit(make_decode_multi(self.model, k, **self._scope),
                         donate_argnums=(2,))
            self._decode_multi[k] = fn
        return fn

    def _pick_horizon(self) -> int:
        """Decode steps to fuse into the next dispatch.

        k=1 while admissions wait (a queued request must not sit behind a
        long horizon) or a live deadline is within ~2 horizons of the
        last sweep's clock (expiry is only checked between dispatches, so
        the horizon bounds timeout granularity); ``eos_scan_every``
        otherwise.  Reads no clock: the imminence test reuses the
        deadline sweep's timestamp and step estimate."""
        k_max = self.eos_scan_every
        if k_max == 1 or self._queue:
            return 1
        if self._n_deadlines:
            live = [act.deadline for act in self._active.values()
                    if act.deadline is not None]
            if live:
                if self._step_est is None or self._last_sweep is None:
                    return 1
                slack = min(live) - self._last_sweep
                if slack < 2.0 * k_max * self._step_est:
                    return 1
        return k_max

    def step(self) -> List[Any]:
        """Admit waiting requests, advance every slot one decode horizon
        (k fused steps, one dispatch), evict finished sequences.  Returns
        the uids that finished this step."""
        finished = self._admit()
        if not self._active:
            return finished
        k = self._pick_horizon()
        block, self._tokens, self._caches, self._pos, self._term = (
            self._decode_fn(k)(self.params, self._tokens, self._caches,
                               self._pos, self._term))
        self._flight.push(block)
        self._step_id += k
        self._last_horizon = k
        self.n_dispatches += 1
        self.n_decode_steps += k
        # deadline sweep: host clock only — and only read at all while a
        # deadlined request is live, so the common loop adds no work.
        # Expiry granularity is one dispatch (up to k steps); the horizon
        # policy drops to k=1 when a deadline gets imminent.
        expired = set()
        if self._n_deadlines:
            now = _deadline_clock()
            if self._last_sweep is not None:
                self._step_est = (now - self._last_sweep) / k
            self._last_sweep = now
            expired = {slot for slot, act in self._active.items()
                       if act.deadline is not None and now >= act.deadline}
        streaming = self.stream_callback is not None and any(
            act.request.stream for act in self._active.values())
        need_full = bool(expired)
        for act in self._active.values():
            # the device freezes a slot at its budget edge, so rows past
            # it repeat the last token: cap the host count to match
            act.n_decoded = min(act.n_decoded + k,
                                act.request.max_new_tokens - 1)
            if 1 + act.n_decoded >= act.request.max_new_tokens:
                need_full = True
            elif (act.request.eos_id is not None
                    and self._step_id - self._pending_base
                    >= self.eos_scan_every):
                need_full = True
        if not (need_full or streaming):
            return finished
        # only tokens this flush materializes need EOS scanning (out[0] was
        # checked at admission): keeps eviction O(1) amortized per token
        pre = {slot: len(act.out) for slot, act in self._active.items()}
        if need_full:
            self._flush()
        else:
            self._flush_stream()  # completed blocks only: non-blocking
        events = []
        for slot in list(self._active):
            act = self._active[slot]
            lo = max(pre[slot], 1)
            eos = act.request.eos_id
            fresh_toks = act.out[lo:]
            reason = None
            if eos is not None and eos in fresh_toks:
                act.out = act.out[:lo + fresh_toks.index(eos) + 1]
                reason = "stop"
            elif len(act.out) >= act.request.max_new_tokens:
                reason = "length"
            elif slot in expired:
                # evict mid-decode, keep the partial output
                reason = "timeout"
            if act.request.stream:
                new = act.out[act.n_streamed:]
                act.n_streamed = len(act.out)
                if new or reason is not None:
                    events.append((act.request, new, reason))
            if reason is not None:
                finished.append(self._finish(act, reason))
        # callbacks fire after the engine's own bookkeeping is consistent
        for req, new, reason in events:
            self._emit(req, new, reason)
        return finished

    def run(self, requests: Sequence[Request] = ()) -> Dict[Any, List[int]]:
        """Drive ``step()`` until every submitted request has finished."""
        for req in requests:
            self.submit(req)
        while self.has_work:
            self.step()
        out, self._results = self._results, {}
        for uid in out:
            self._finish_reason.pop(uid, None)
        return out
