"""Chunked prefill: prompt ingestion in fixed-size chunks with a threaded
carry, so every prompt length hits the same compiled shapes.

A prompt of length P runs as ``P // chunk`` full chunks through the
model's parallel-scan prefill (``model.prefill``: each GOOM/SSM layer is
one ``engine.*_scan_carry`` over the chunk, each attention layer a flash
pass over its KV page) and the ``P % chunk`` remainder token-by-token
through the decode step.  Exactly two compiled shapes — ``(1, chunk)``
and ``(1, 1)`` — serve any prompt length, and a 32k-token prompt never
materializes one 32k-long scan.

Carry semantics: the *cache tree is the carry*.  Each recurrent layer's
entering state rides in its ``state`` dict (folded into the scan as
``x0``), each attention layer's KV page and write offset ride in its
cache — threading the caches through successive calls is numerically the
recurrence algebra's exact chunking (the combine folds ``x0`` with the
same LMME/LSE monoid the full-length scan uses; parity is tested at
e±200 dynamic range in tests/test_serve_engine.py).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import numpy as np

from ..models.model import DecoderLM
from .steps import _engine_scope


class ChunkedPrefill:
    """Ingest prompts through two persistent jitted steps.

    Construct once per (model, backend, mesh) serving config; the jitted
    chunk/tail steps live for the object's lifetime, so every request
    reuses the same compiled executables.
    """

    def __init__(
        self,
        model: DecoderLM,
        chunk: int,
        *,
        backend: str = "auto",
        mesh=None,
        seq_shards="auto",
        blocks=None,
    ):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.model = model
        self.chunk = chunk
        # dispatch counters: prefix-reuse tests assert a warm hit issues
        # exactly the suffix's chunks, by deltas of these
        self.n_chunk_calls = 0
        self.n_tail_calls = 0

        def chunk_step(params, tokens, caches, positions):
            with _engine_scope(backend, mesh, seq_shards, blocks):
                return model.prefill(params, tokens, caches,
                                     positions=positions)

        def tail_step(params, token, caches, index):
            with _engine_scope(backend, mesh, seq_shards, blocks):
                return model.decode_step(params, token, caches, index)

        self._chunk_step = jax.jit(chunk_step, donate_argnums=(2,))
        self._tail_step = jax.jit(tail_step, donate_argnums=(2,))

    def __call__(
        self, params, prompt, caches, *, start: int = 0,
        capture_every: Optional[int] = None,
        capture: Optional[Callable[[int, Any], None]] = None,
    ) -> Tuple[jax.Array, Any, int]:
        """Ingest ``prompt`` (1-D int tokens) into a batch-1 cache tree.

        ``start`` is the absolute position of the prompt's first token
        (nonzero when streaming more tokens into an existing sequence, or
        when resuming past a cached prefix restored via
        ``state_cache.gather_prefix``).  ``capture(pos, caches)`` fires
        after each full chunk that lands on a multiple of
        ``capture_every`` — the engine snapshots scan carries at page
        boundaries there; the callback must not mutate or hold the live
        tree past the next call (it gets donated).  Only full-chunk
        boundaries are captured, so published checkpoints always come
        from the same compiled chunk schedule regardless of prompt tail.
        Returns ``(last_logits (1, vocab), caches, next_pos)`` — the
        logits of the final prompt token (sample the first generated
        token from them) and the position the first decode step runs at.
        """
        # slice on the host (numpy): each jitted call gets one small
        # transfer instead of per-chunk device slice/arange dispatches
        # (np.asarray already pulls device arrays to host — no device_get)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p = int(prompt.shape[0])
        if p == 0:
            raise ValueError("empty prompt: need at least one token")
        c = self.chunk
        n_full = p // c
        pos = start
        logits = None
        for j in range(n_full):
            toks = prompt[None, j * c:(j + 1) * c]
            positions = np.arange(pos, pos + c, dtype=np.int32)[None]
            logits, caches = self._chunk_step(params, toks, caches, positions)
            self.n_chunk_calls += 1
            pos += c
            if (capture is not None and capture_every
                    and pos % capture_every == 0):
                capture(pos, caches)
        for t in range(n_full * c, p):
            logits, caches = self._tail_step(
                params, prompt[None, t:t + 1],
                caches, np.asarray([pos], np.int32))
            self.n_tail_calls += 1
            pos += 1
        return logits[:, -1, :], caches, pos
