#!/usr/bin/env python3
"""Bring-up smoke run of the GOOM system's main paths on a TPU.

Run from the root of a checkout::

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # only the path that spans four chips

One chip runs four phases at goom-rnn-124m's full width (d_model 768,
24 layers, head_dim 16, vocab 50257), with random weights and data made
from ``--seed``:

* ``kernels``: each engine op resolves to ``pallas_tpu`` under ``auto``
  and compiles to a ``tpu_custom_call``, at the shapes the model and the
  paper's jobs use.  ``lmme`` agrees with the XLA reference on the same
  chip; each recurrence, through the kernel and through the XLA
  reference, agrees with a float64 recurrence on the host over the same
  inputs;
* ``paper``: a 65,536-step chain of 16x16 random matrices
  (``cumulative_lmme``; kernel and XLA reference against float64) and the
  parallel Lyapunov spectrum of the systems the tests use against the
  sequential one;
* ``train``: ``repro.launch.train`` for 3 steps (copy task, seq 256, the
  largest batch whose compiled step fits the chip), a checkpoint, and one
  resume from it;
* ``serve``: the HTTP front door in process -- 8 requests, half of them
  streamed -- then final-position logits against a reference forward pass.

``--chips 4`` runs the sequence-sharded ``cumulative_lmme`` and
``matrix_scan`` (and the same ops on one chip) against float64, and 3 train
steps with ``--seq-shards 4`` against ``--devices 1`` with the same seed.

Everything runs in this one process: a chip belongs to one process.  The
script refuses to run when JAX finds no TPU.  The times it prints are
one-off bring-up observations, not benchmark figures.  A failed phase makes
it exit non-zero; on success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import math
import os
import re
import shutil
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "goom-rnn-124m"
H, HD = 48, 16          # goom-rnn-124m: 48 heads x head_dim 16 = d_model 768
SEQ = 256               # train and logits-check sequence length
# Largest batch whose full-width train step compiles for one v5e at seq 256:
# 7 needs 15.09 GiB of the 15.75 GiB the compiler allows; 8 is refused.
TRAIN_BATCH = 7
SHARDED_TRAIN_BATCH = 2
CHAIN_STEPS = 65_536
N_REQUESTS, MAX_TOKENS = 8, 32
PROMPT_LENS = (64, 512)
SLOTS, PAGE_LEN, CHUNK = 8, 576, 64
# Final-position logits, kernels vs reference, as max|diff| / max|ref|.
# The model's projections run in bf16 (one ulp is 3.9e-3 relative), and a
# flipped rounding compounds over 24 layers; a faulty kernel moves the
# logits by O(1) of their range.
LOGITS_BOUND = 5e-2
# Recurrences, through the kernel and through the chip's XLA reference, each
# against a float64 recurrence on the host over the same inputs, as
# row-normalised error (one contraction, lmme, is held to the XLA reference
# at the tests' 1e-4 with their log and sign checks).  On the v5e both sides
# drift alike, far past f32's own 1e-7: the chip's f32 log, in XLA and in
# Mosaic, is off by up to 1.0e-4.  Each bound sits between the chip's sound
# reading (the larger of kernel and reference) and planted faults that must
# fail it: the kernel's dots on bf16 operands, the carry dropped at the
# midpoint (the shard stitch dropped, for four chips).  Readings (PERF.md):
#   case, steps                  sound    bf16 dots   carry dropped   bound
#   diagonal_scan 4096           1.60e-4  (no dots)   0.87            1e-3
#   matrix_scan 256              3.92e-4  8.0e-3      1.00            2e-3
#   matrix_scan 1024 (4 chips)   9.5e-4   1.5e-2      1.00            4e-3
#   cumulative_lmme 8192         6.16e-3  4.9e-2      0.86            1.5e-2
RECURRENCE_ATOL = {"diagonal_scan": 1e-3, "matrix_scan 256": 2e-3,
                   "matrix_scan 1024": 4e-3, "cumulative_lmme": 1.5e-2}
# The signed 65,536-step chain against float64: each step's scale (max
# log-magnitude) relatively, and its direction (the matrix divided by its
# scale) over the first 256 steps.  Later directions are not compared: where
# two near-rank-1 compounds barely overlap, their product's direction rests
# on parts below f32 rounding (on CPU too, f32 directions differ from
# float64 by O(1) at some steps).  Sound: scale 1.24e-4, direction 3.02e-3;
# bf16 dots: 3.6e-4 and 7.6e-2, with non-finite entries.
CHAIN_SCALE_RTOL = 1e-3
CHAIN_DIR_ATOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _block(tree):
    import jax

    return jax.block_until_ready(tree)


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------
def goom_close(got, want, *, atol=1e-4, cancel_margin=12.0):
    """``repro.core.goom.closeness`` (the tests' ``assert_goom_close``) as
    a report with a verdict.  ``cancel_margin=None`` keeps only the
    row-normalised comparison, for results whose error is a fraction of
    the row scale: it bounds no entry far below that scale."""
    from repro.core.goom import closeness

    rep = closeness(got, want, cancel_margin=cancel_margin)
    rep["pass"] = (rep["finite"] and rep["norm_err"] <= atol
                   and rep.get("log_ok", True)
                   and rep.get("sign_mismatches", 0) == 0)
    return rep


def chain_close(got, exact, *, early=256):
    """A chain of signed random matrix products against float64.

    Each matrix's scale (its max log-magnitude) is compared relatively at
    every step; each matrix divided by its scale (its direction) over the
    first ``early`` steps, where a wrong product order shows, and over all
    steps, where the f32 spacing of log-magnitudes near 10^5 (7.8e-3) is
    itself the error: ``dir_f32_floor`` is what rounding the float64
    chain's log-magnitudes to f32 alone costs."""
    import numpy as np

    def direction(lg, sg):
        return sg * np.exp(lg - lg.max((-2, -1), keepdims=True))

    gl, gs = np.asarray(got.log_abs, np.float64), np.asarray(got.sign)
    wl, ws = np.asarray(exact.log_abs, np.float64), np.asarray(exact.sign)
    gm, wm = gl.max((-2, -1)), wl.max((-2, -1))
    dir_err = np.abs(direction(gl, gs) - direction(wl, ws)).max((-2, -1))
    floor = np.abs(direction(wl.astype(np.float32).astype(np.float64), ws)
                   - direction(wl, ws)).max((-2, -1))
    rep = {"finite": bool(np.isfinite(gl).all()),
           "max_scale_rel_err": float((np.abs(gm - wm)
                                       / np.maximum(np.abs(wm), 1.0)).max()),
           f"dir_err_first_{early}": float(dir_err[:early].max()),
           "dir_err": float(dir_err.max()),
           "dir_f32_floor": float(floor.max())}
    rep["pass"] = (rep["finite"]
                   and rep["max_scale_rel_err"] <= CHAIN_SCALE_RTOL
                   and rep[f"dir_err_first_{early}"] <= CHAIN_DIR_ATOL)
    return rep


# ---------------------------------------------------------------------------
# float64 references on the host, over the same inputs
# ---------------------------------------------------------------------------
def _values64(g):
    """A GOOM's values in float64: exact, for an f32 log-magnitude."""
    import numpy as np

    return np.asarray(g.sign, np.float64) * np.exp(np.asarray(g.log_abs, np.float64))


def _goom64(values, log_scale=0.0):
    """(log|values| + log_scale, sign) as a float64 GOOM."""
    import numpy as np
    from repro.core.goom import Goom

    with np.errstate(divide="ignore"):
        return Goom(np.log(np.abs(values)) + log_scale,
                    np.where(values < 0, -1.0, 1.0))


def host_diagonal_scan(a, b):
    """x_t = a_t x_{t-1} + b_t, sequentially in float64."""
    import numpy as np

    a, b = _values64(a), _values64(b)
    out = np.empty(np.broadcast_shapes(a.shape, b.shape))
    x = np.zeros(out.shape[1:])
    for t in range(len(out)):
        x = a[t] * x + b[t]
        out[t] = x
    return _goom64(out)


def host_matrix_scan(a, b):
    """X_t = A_t X_{t-1} + B_t, sequentially in float64."""
    import numpy as np

    a, b = _values64(a), _values64(b)
    out = np.empty(b.shape)
    x = np.zeros(b.shape[1:])
    for t in range(len(out)):
        x = a[t] @ x + b[t]
        out[t] = x
    return _goom64(out)


def host_prefix_products(a):
    """P_t = A_t ... A_1, sequentially in float64.  Each step's product is
    divided by its largest entry and the log of that scale carried apart,
    so a chain whose magnitude leaves float64 (e^88739) stays exact."""
    import numpy as np
    from repro.core.goom import Goom

    la = np.asarray(a.log_abs, np.float64)
    sa = np.asarray(a.sign, np.float64)
    out_l, out_s = np.empty(la.shape), np.empty(la.shape)
    p, scale = None, np.zeros(la.shape[1:-2] + (1, 1))
    for t in range(len(la)):
        m = la[t].max((-2, -1), keepdims=True)
        at = sa[t] * np.exp(la[t] - m)
        p = at if p is None else at @ p
        big = np.abs(p).max((-2, -1), keepdims=True)
        p = p / big
        scale = scale + m + np.log(big)
        g = _goom64(p, scale)
        out_l[t], out_s[t] = g.log_abs, g.sign
    return Goom(out_l, out_s)


def _rows(g, swap=False):
    """A GOOM with the axis to normalise over moved last."""
    from repro.core.goom import Goom

    if swap:
        return Goom(g.log_abs.swapaxes(-1, -2), g.sign.swapaxes(-1, -2))
    return g


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------
def require_kernels() -> None:
    from repro.core import engine

    with engine.use_backend("auto"):
        got = engine.resolved_backend()
    check(got == "pallas_tpu", f"auto resolves to {got}, not pallas_tpu")


def has_kernel(hlo_text: str) -> bool:
    return "tpu_custom_call" in hlo_text


def on_kernels(fn, *args):
    """Compile ``fn`` under ``auto`` and run it; the compiled program must
    hold a Pallas kernel.  Returns (out, compile s, run s)."""
    import jax
    from repro.core import engine

    t0 = time.perf_counter()
    with engine.use_backend("auto"):
        # a fresh function: jit's trace cache does not key on the backend
        compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    t1 = time.perf_counter()
    check(has_kernel(compiled.as_text()),
          f"{getattr(fn, '__name__', fn)} compiled without a tpu_custom_call")
    out = _block(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t1


def on_reference(fn, *args):
    """``fn`` on the XLA reference backend, f32 matmuls at full precision
    (a TPU's default f32 matmul is one bf16 pass)."""
    import jax
    from repro.core import engine

    with engine.use_backend("reference"), jax.default_matmul_precision("highest"):
        return _block(jax.jit(lambda *a: fn(*a))(*args))


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def _normal(key, shape, scale=1.0):
    import jax
    from repro.core.goom import to_goom

    return to_goom(jax.random.normal(key, shape) * scale)


def _decays(key, shape):
    import jax
    import jax.numpy as jnp
    from repro.core.goom import to_goom

    return to_goom(jnp.exp(-jnp.abs(jax.random.normal(key, shape))))


def _positive(key, shape):
    """Entries |N(0, 1)| / (16 E|N|): 16x16 products with Perron root ~1."""
    import jax
    import jax.numpy as jnp
    from repro.core.goom import to_goom

    x = jnp.abs(jax.random.normal(key, shape)) / (HD * math.sqrt(2 / math.pi))
    return to_goom(x)


def f32_floor(exact, swap=False):
    """Row-normalised error of ``exact`` (float64) rounded to an f32 GOOM:
    what storing its log-magnitudes in f32 alone costs, the least any f32
    implementation can reach."""
    import numpy as np
    from repro.core.goom import Goom

    rounded = Goom(np.asarray(exact.log_abs, np.float32), exact.sign)
    return goom_close(_rows(rounded, swap), _rows(exact, swap),
                      cancel_margin=None)["norm_err"]


def against_f64(outs, exact, swap, atol):
    """Each of ``outs`` (name -> GOOM) against the float64 result, held to
    ``atol`` of the row scale; returns (all pass, report)."""
    rep = {"f32_floor": f32_floor(exact, swap)}
    ok = True
    for name, out in outs.items():
        r = goom_close(_rows(out, swap), _rows(exact, swap), atol=atol,
                       cancel_margin=None)
        rep[name] = r["norm_err"]
        ok = ok and r["pass"]
    return ok, rep


def phase_kernels(seed: int) -> None:
    import jax
    from repro.core import engine

    require_kernels()
    key = jax.random.PRNGKey(seed)
    k = list(jax.random.split(key, 8))
    a_heads = _normal(k[0], (H, HD, HD), HD ** -0.5)
    # (name, op, args, normalise over the state axis (-2)?,
    #  None or (float64 recurrence, bound))
    cases = [
        # GOOM layer B.u: a 64-token prefill chunk, and an 8-slot decode step
        ("lmme B.u prefill chunk", engine.lmme,
         (a_heads, _normal(k[1], (CHUNK, 1, H, HD, 1))), True, None),
        ("lmme B.u decode", engine.lmme,
         (a_heads, _normal(k[2], (1, SLOTS, H, HD, 1))), True, None),
        ("diagonal_scan 4096x768", engine.diagonal_scan,
         (_decays(k[3], (4096, 768)), _normal(k[4], (4096, 768))), False,
         (host_diagonal_scan, RECURRENCE_ATOL["diagonal_scan"])),
        # Matrix recurrences over positive operands, so that the kernel's
        # tiling and carries are compared where sums cannot cancel (the
        # tests' convention at e±200); the paper phase runs a signed chain.
        # Sequence-sharded GOOM layer: batch 8 in the state columns.
        ("matrix_scan 256x48x16x16", engine.matrix_scan,
         (_positive(k[5], (SEQ, H, HD, HD)), _positive(k[6], (SEQ, H, HD, 8))),
         True, (host_matrix_scan, RECURRENCE_ATOL["matrix_scan 256"])),
        ("cumulative_lmme 8192x16x16", engine.cumulative_lmme,
         (_positive(k[7], (8192, HD, HD)),), False,
         (host_prefix_products, RECURRENCE_ATOL["cumulative_lmme"])),
    ]
    failed = []
    for name, fn, args, swap, exact in cases:
        got, t_c, t_r = on_kernels(fn, *args)
        want = on_reference(fn, *args)
        if exact is None:  # one contraction: the tests' closeness
            rep = goom_close(_rows(got, swap), _rows(want, swap))
            ok, what = rep["pass"], "vs reference"
        else:  # a recurrence: kernel and reference, each against float64
            host, bound = exact
            ok, rep = against_f64({"kernel": got, "reference": want},
                                  host(*args), swap, bound)
            what = f"norm err vs float64 (bound {bound})"
        log(f"  {name}: compile {t_c:.2f} s, run {t_r:.3f} s; {what} "
            f"{json.dumps(rep)}")
        if not ok:
            failed.append(name)
    check(not failed, f"kernels disagree with their references: {failed}")


# ---------------------------------------------------------------------------
# phase: the paper's jobs
# ---------------------------------------------------------------------------
def phase_paper(seed: int) -> None:
    import functools

    import jax
    import numpy as np
    from repro.core import engine
    from repro.core.chains import chain_elements, goom_chain_parallel
    from repro.core.lyapunov import (SYSTEMS, spectrum_parallel,
                                     spectrum_sequential,
                                     trajectory_and_jacobians)

    require_kernels()
    key = jax.random.PRNGKey(seed)
    chain = functools.partial(goom_chain_parallel, key, HD, CHAIN_STEPS - 1)
    got, t_c, t_r = on_kernels(chain)
    want = on_reference(chain)
    exact = host_prefix_products(chain_elements(key, HD, CHAIN_STEPS - 1))
    reps = {name: chain_close(out, exact)
            for name, out in (("kernel", got), ("reference", want))}
    top = float(np.asarray(got.log_abs[-1]).max())
    log(f"  chain {CHAIN_STEPS}x{HD}x{HD}: compile {t_c:.2f} s, run {t_r:.3f} s; "
        f"final max log|.| {top:.2f} (float64 {float(exact.log_abs[-1].max()):.2f}); "
        f"vs float64 {json.dumps(reps)}")
    failed = [f"chain ({n})" for n, r in reps.items() if not r["pass"]]
    for name in ("logistic", "henon", "lorenz63"):
        system = SYSTEMS[name]
        _, jacs = trajectory_and_jacobians(system, 4096)
        par_fn = functools.partial(spectrum_parallel, dt=system.dt)
        with jax.default_matmul_precision("highest"):
            par, t_c, t_r = on_kernels(par_fn, jacs)
            with engine.use_backend("reference"):
                seq = _block(jax.jit(spectrum_sequential, static_argnums=1)(
                    jacs, system.dt))
        par, seq = np.asarray(par), np.asarray(seq)
        ok = bool(np.allclose(par, seq, rtol=1e-3, atol=1e-3))
        log(f"  lyapunov {name}: compile {t_c:.2f} s, run {t_r:.3f} s; "
            f"parallel {par.tolist()} sequential {seq.tolist()} "
            f"{'agree' if ok else 'DISAGREE'}")
        if not ok:
            failed.append(f"lyapunov {name}")
    check(not failed, f"disagree with their references: {failed}")


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------
_STEP_LINE = re.compile(r"^step\s+(\d+)\s+loss\s+(\S+)", re.M)


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def run_train(argv):
    """``repro.launch.train.main(argv)`` in process; returns (state, the
    losses it logged by step, its output)."""
    from repro.launch import train

    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        state = train.main(argv)
    text = buf.getvalue()
    losses = {int(s): float(v) for s, v in _STEP_LINE.findall(text)}
    return state, losses, text


def train_argv(seed, batch, steps, *extra):
    return ["--arch", ARCH, "--task", "copy", "--seq-len", str(SEQ),
            "--batch", str(batch), "--steps", str(steps), "--log-every", "1",
            "--seed", str(seed), *extra]


def phase_train(seed: int, out_dir: Path) -> None:
    require_kernels()
    ckpt = out_dir / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    argv = train_argv(seed, TRAIN_BATCH, 3, "--ckpt-dir", str(ckpt))
    t0 = time.perf_counter()
    state, losses, _ = run_train(argv)
    del state
    gc.collect()
    log(f"  3 steps at batch {TRAIN_BATCH}: {time.perf_counter() - t0:.1f} s "
        f"(compile included); losses {losses}")
    check(sorted(losses) == [0, 1, 2], f"logged steps {sorted(losses)}")
    check(all(math.isfinite(v) for v in losses.values()),
          f"non-finite losses {losses}")

    t0 = time.perf_counter()
    argv = train_argv(seed, TRAIN_BATCH, 4, "--ckpt-dir", str(ckpt))
    state, resumed, text = run_train(argv)
    del state
    gc.collect()
    log(f"  resume to step 4: {time.perf_counter() - t0:.1f} s; "
        f"losses {resumed}")
    check("resumed from checkpoint step 3" in text,
          "the second run did not resume from the step-3 checkpoint")
    check(sorted(resumed) == [3] and math.isfinite(resumed[3]),
          f"resumed run logged {resumed}")
    # full-width checkpoints (params + Adam moments) are ~1.3 GB a step:
    # keep their file list, not their bytes
    files = {str(p.relative_to(ckpt)): p.stat().st_size
             for p in sorted(ckpt.rglob("*")) if p.is_file()}
    (out_dir / "ckpt_manifest.json").write_text(json.dumps(files, indent=1))
    log(f"  checkpoint: {len(files)} files, {sum(files.values())} bytes "
        f"(manifest in {out_dir / 'ckpt_manifest.json'})")
    shutil.rmtree(ckpt)


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def _request(port, prompt, stream):
    from repro.serve.api import client

    payload = {"prompt": prompt, "max_tokens": MAX_TOKENS}
    if not stream:
        out = client.completion("127.0.0.1", port, payload, timeout=900.0)
        choice = out["choices"][0]
        return choice["tokens"], choice["finish_reason"]
    toks, reason = [], None
    for event in client.stream_completion("127.0.0.1", port, payload,
                                          timeout=900.0):
        choice = event["choices"][0]
        if choice["token"] is not None:
            toks.append(choice["token"])
        reason = choice["finish_reason"] or reason
    return toks, reason


def phase_serve(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import engine
    from repro.serve.api import client
    from repro.serve.api.gateway import Gateway
    from repro.serve.api.server import BackgroundServer, build_engine

    require_kernels()
    t0 = time.perf_counter()
    eng, cfg = build_engine(ARCH, smoke=False, max_slots=SLOTS,
                            page_len=PAGE_LEN, chunk=CHUNK, backend="auto",
                            seed=seed)
    rng = np.random.default_rng(seed)
    lens = [PROMPT_LENS[1], PROMPT_LENS[0]] + rng.integers(
        PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS - 3).tolist()
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in lens]
    # prompt 0 goes both streamed and not streamed: 4 streamed of 8
    jobs = [(p, i % 2 == 0) for i, p in enumerate(prompts)] + [(prompts[0], False)]
    server = BackgroundServer(Gateway(eng)).start()
    try:
        with ThreadPoolExecutor(len(jobs)) as pool:
            futs = [pool.submit(_request, server.port, p, s) for p, s in jobs]
            results = [f.result() for f in futs]
        status = client.get_status("127.0.0.1", server.port)
    finally:
        server.stop()
    log(f"  {len(jobs)} requests (prompts {lens} + prompt 0 again), "
        f"{MAX_TOKENS} tokens each: {time.perf_counter() - t0:.1f} s "
        f"(engine build and compile included)")
    reasons = [r for _, r in results]
    log(f"  finish reasons {reasons}; decode {json.dumps(status['decode'])}")
    check(all(r == "length" for r in reasons), f"finish reasons {reasons}")
    check(all(len(t) == MAX_TOKENS for t, _ in results),
          f"token counts {[len(t) for t, _ in results]}")
    check(results[0][0] == results[-1][0],
          "streamed and non-streamed tokens differ for the same prompt")
    check(status["decode"]["tokens_per_dispatch"] > 1,
          f"tokens_per_dispatch {status['decode']['tokens_per_dispatch']}")

    tokens = jax.numpy.asarray(prompts[0][:SEQ], jax.numpy.int32)[None]

    def last_logits(params, toks):
        return eng.model.apply(params, toks)[0][:, -1]

    with jax.default_matmul_precision("highest"):
        got, t_c, t_r = on_kernels(last_logits, eng.params, tokens)
        want = on_reference(last_logits, eng.params, tokens)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    same_top = bool((got.argmax(-1) == want.argmax(-1)).all())
    log(f"  logits of a {SEQ}-token prompt, kernels vs reference: max rel "
        f"diff {rel:.3e} (bound {LOGITS_BOUND}); same argmax {same_top}; "
        f"compile {t_c:.2f} s, run {t_r:.3f} s")
    check(np.isfinite(got).all(), "non-finite logits")
    check(rel <= LOGITS_BOUND, f"logits differ by {rel:.3e} > {LOGITS_BOUND}")


# ---------------------------------------------------------------------------
# phase: the sequence-sharded path on four chips
# ---------------------------------------------------------------------------
def _spread(arr, n):
    devs = {s.device for s in arr.addressable_shards}
    return len(devs) == n and all(
        s.data.shape[0] == arr.shape[0] // n for s in arr.addressable_shards)


def phase_sharded(seed: int, n: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import engine

    require_kernels()
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    cases = [
        ("cumulative_lmme 8192x16x16", engine.cumulative_lmme,
         (_positive(k1, (8192, HD, HD)),), False, host_prefix_products,
         RECURRENCE_ATOL["cumulative_lmme"]),
        ("matrix_scan 1024x48x16x16", engine.matrix_scan,
         (_positive(k2, (1024, H, HD, HD)), _positive(k3, (1024, H, HD, 8))),
         True, host_matrix_scan, RECURRENCE_ATOL["matrix_scan 1024"]),
    ]
    failed = []
    for name, fn, args, swap, host, bound in cases:
        local, _, t_local = on_kernels(fn, *args)
        put = NamedSharding(mesh, P("seq"))
        sharded_args = jax.device_put(args, put)
        t0 = time.perf_counter()
        with engine.use_backend("auto"), engine.use_mesh(mesh, seq_shards=n):
            compiled = jax.jit(lambda *a: fn(*a)).lower(*sharded_args).compile()
        t1 = time.perf_counter()
        text = compiled.as_text()
        collectives = sorted({c for c in ("all-gather", "collective-permute",
                                          "all-reduce", "all-to-all")
                              if c in text})
        check(has_kernel(text), f"sharded {name} holds no tpu_custom_call")
        check(collectives, f"sharded {name} holds no collective")
        out = _block(compiled(*sharded_args))
        spread = _spread(out.log_abs, n)
        ok, rep = against_f64({f"{n} chips": out, "one chip": local},
                              host(*args), swap, bound)
        log(f"  {name} over {n} chips: compile {t1 - t0:.2f} s, run "
            f"{time.perf_counter() - t1:.3f} s (one chip {t_local:.3f} s); "
            f"collectives {collectives}; output spread over {n} devices "
            f"{spread}; norm err vs float64 (bound {bound}) "
            f"{json.dumps(rep)}")
        if not (spread and ok):
            failed.append(name)

    state, sharded, _ = run_train(train_argv(
        seed, SHARDED_TRAIN_BATCH, 3, "--seq-shards", str(n)))
    leaves = jax.tree.leaves(state.params)
    n_split = sum(len(x.sharding.device_set) == n for x in leaves)
    del state, leaves
    gc.collect()
    state, one, _ = run_train(train_argv(
        seed, SHARDED_TRAIN_BATCH, 3, "--devices", "1"))
    del state
    gc.collect()
    log(f"  train --seq-shards {n}: losses {sharded}; {n_split} param leaves "
        f"on {n} devices; one device, same seed: losses {one}")
    agree = (sorted(sharded) == sorted(one) == [0, 1, 2] and all(
        math.isfinite(sharded[s]) and abs(sharded[s] - one[s]) <= 1e-2 * abs(one[s])
        for s in one))
    if not (n_split and agree):
        failed.append(f"train --seq-shards {n}")
    check(not failed, f"sequence-sharded path: {failed}")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: every phase on one chip; 4: only the "
                         "sequence-sharded path and what it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="directory for the checkpoint and its manifest")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not under {ROOT / 'src'} "
              f"({e}); run this script from a checkout of the repository",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX finds only {dev.platform} devices "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
              "nothing is run off the chip", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX finds {len(devices)}", file=sys.stderr)
        return 3
    cache = compile_cache.enable()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; libtpu {libtpu}; compile cache {cache}")
    log("times below are one-off bring-up observations, not benchmark "
        "figures")
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.chips == 1:
        phases = [("kernels", lambda: phase_kernels(args.seed)),
                  ("paper", lambda: phase_paper(args.seed)),
                  ("train", lambda: phase_train(args.seed, out_dir)),
                  ("serve", lambda: phase_serve(args.seed))]
    else:
        phases = [("sharded", lambda: phase_sharded(args.seed, args.chips))]
    failed = []
    for name, run in phases:
        log(f"[{name}]")
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # report, run the other phases, exit non-zero
            traceback.print_exc()
            traceback.clear_frames(e.__traceback__)
            failed.append(name)
            log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f} s: "
                f"{type(e).__name__}: {e}")
        else:
            log(f"[{name}] ok in {time.perf_counter() - t0:.1f} s")
        gc.collect()
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
