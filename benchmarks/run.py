"""Benchmark harness — one entry per paper table/figure.

  fig1_chains     — §4.1/Fig.1: longest random-normal matrix-product chain
                    without catastrophic error: float32/64 vs GOOM LMME.
  fig3_lyapunov   — §4.2/Fig.3: Lyapunov-spectrum estimation, sequential
                    iterative-QR vs the paper's parallel algorithm
                    (accuracy vs literature values + wall-time ratio).
  fig4_rnn        — §4.3/Fig.4: train the GOOM-RNN (non-diagonal SSM over
                    GOOMs, parallel scan, no stabilization) on Copy-Memory.
  table1_range    — §3/Table 1: dynamic ranges, verified numerically.
  appD_error      — App. D: per-op decimal digits of error of GOOM ops.
  appD_time       — App. D: per-op wall-time of GOOM ops vs raw floats.
  roofline        — §Dry-run/§Roofline: prints the roofline table from
                    results/dryrun_baseline.json (run dryrun first).
  scan_backends   — engine dispatch sweep: all four engine ops per backend
                    (reference / pallas / pallas_gpu_interpret by default),
                    with cross-backend parity checks.  ``--emit-bench``
                    additionally writes results/BENCH_scan.json: the
                    normalized per-op throughput table plus a sequence-
                    length sweep of kernel-vs-reference speedup ratios
                    (``--preset smoke`` shrinks the sweep for CI).
  scan_sharded    — sequence-sharded scans across the device mesh: per-
                    shard-count timings of matrix_scan / cumulative_lmme /
                    diagonal_scan, with single-device parity checks.  On
                    CPU, run alone so the harness can force 8 host devices
                    (or export XLA_FLAGS=--xla_force_host_platform_device_count=8).
  serve_throughput — continuous-batching serve engine vs the legacy
                    static-batch path: requests/s both ways plus p50/p99
                    decode-step latency (``--preset smoke`` for CI shapes).
  serve_api       — the full HTTP front door under concurrent streaming
                    clients (more clients than slots, 429-retry loop):
                    aggregate tok/s, ttft and request-latency percentiles
                    from /status, rejection counts (``--preset smoke``
                    for CI shapes).
  serve_prefix    — cross-request prefix reuse A/B: shared-system-prompt
                    TTFT and dispatched prefill tokens, reuse on vs off
                    (writes the ``serve_prefix`` section of
                    results/BENCH_serve.json).
  serve_decode    — fused multi-step decode A/B: horizon 1 vs adaptive 8,
                    streaming off/on — tokens/s, tokens-per-dispatch,
                    host-syncs-per-token, with bit-identical outputs
                    across cells (writes the ``serve_decode`` section of
                    results/BENCH_serve.json).

Usage: PYTHONPATH=src python -m benchmarks.run [names...] [--backend B ...]
       [--preset {full,smoke}] [--emit-bench]

``--backend`` (repeatable; ``reference``/``pallas``/``auto`` or any concrete
backend name, e.g. ``pallas_gpu_interpret``) selects the scan-engine
backend.  ``scan_backends`` sweeps every requested backend (default:
``reference``, ``pallas``, and ``pallas_gpu_interpret``); all other
benchmarks run under the first requested backend (default ``auto``).
``--preset smoke`` shrinks the serving benchmark to CI size.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")


def _bench(fn, *args, reps=3):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


# ---------------------------------------------------------------------------
def fig1_chains():
    """Longest surviving chain S_t = A_t S_{t-1}, A ~ N(0,1)^{d x d}."""
    from repro.core.chains import float_chain_survival, goom_chain

    print("# fig1_chains: steps survived")
    print("d,repr,steps_survived,final_log_frobenius_norm")
    rows = []
    for d in (8, 32, 128):
        for name, dtype in (("float32", jnp.float32),):
            res = jax.jit(
                lambda k: float_chain_survival(k, d, 20_000, dtype)
            )(jax.random.PRNGKey(0))
            steps = int(res.steps_survived)
            rows.append((d, name, steps, float(res.final_log_norm)))
            print(f"{d},{name},{steps},{rows[-1][3]:.1f}")
            assert steps < 20_000, "float chain must fail"
        res = jax.jit(lambda k: goom_chain(k, d, 2_000))(jax.random.PRNGKey(0))
        rows.append((d, "goom_c64", int(res.steps_survived),
                     float(res.final_log_norm)))
        print(f"{d},goom_c64,{int(res.steps_survived)},"
              f"{float(res.final_log_norm):.1f}")
        assert int(res.steps_survived) == 2_000, "GOOM chain must complete"
    return {"rows": rows}


def fig3_lyapunov():
    """Spectrum accuracy + sequential/parallel wall-time ratio."""
    from repro.core.lyapunov import (
        SYSTEMS, spectrum_parallel, spectrum_sequential,
        trajectory_and_jacobians,
    )

    print("# fig3_lyapunov: lambda_max vs literature; wall-time ratio")
    print("system,lambda_max_est,lambda_max_ref,seq_ms,par_ms,seq_over_par")
    out = {}
    for name, sys_ in SYSTEMS.items():
        n = 4096
        _, js = trajectory_and_jacobians(sys_, n)
        seq = jax.jit(lambda j: spectrum_sequential(j, sys_.dt))
        par = jax.jit(lambda j: spectrum_parallel(j, sys_.dt, chunk_size=256))
        t_seq = _bench(seq, js)
        t_par = _bench(par, js)
        spec = np.sort(np.asarray(par(js)))[::-1]
        ref = np.sort(np.asarray(sys_.ref_spectrum))[::-1]
        out[name] = dict(est=spec.tolist(), ref=ref.tolist(),
                         seq_ms=t_seq * 1e3, par_ms=t_par * 1e3)
        print(f"{name},{spec[0]:.4f},{ref[0]:.4f},"
              f"{t_seq*1e3:.1f},{t_par*1e3:.1f},{t_seq/t_par:.2f}")
        assert abs(spec[0] - ref[0]) < max(0.15, 0.2 * abs(ref[0]) + 0.05), name
    return out


def fig4_rnn():
    """Train the paper's RNN on Copy-Memory; training must be 'unremarkable'."""
    from repro.launch.train import main as train_main

    print("# fig4_rnn: GOOM-RNN on copy task (reduced: 2L/64d, 120 steps)")
    state = train_main([
        "--arch", "goom-rnn-124m", "--smoke", "--task", "copy",
        "--steps", "120", "--seq-len", "64", "--batch", "16",
        "--lr", "3e-3", "--log-every", "30",
    ])
    return {"final_step": int(state.step)}


def table1_range():
    """Dynamic range table (§3, Table 1) — verified numerically."""
    print("# table1_range: representable magnitude bounds")
    print("repr,bits,smallest_normal,largest")
    f32 = np.finfo(np.float32)
    f64 = np.finfo(np.float64)
    print(f"float32,32,{f32.tiny:.3e},{f32.max:.3e}")
    print(f"float64,64,{f64.tiny:.3e},{f64.max:.3e}")
    # GOOM(c64): the log-magnitude is itself an f32: exp(±3.4e38)
    print(f"goom_c64,64,exp(-{f32.max:.3e}),exp(+{f32.max:.3e})")
    print(f"goom_c128,128,exp(-{f64.max:.3e}),exp(+{f64.max:.3e})")
    # verify: a GOOM with log-magnitude 1e30 still contracts finitely
    from repro.core.goom import Goom, to_goom
    from repro.core.ops import lmme_reference

    a = to_goom(jnp.ones((4, 4)))
    big = Goom(a.log_abs + 1e30, a.sign)
    out = lmme_reference(big, a)
    assert bool(jnp.all(jnp.isfinite(out.log_abs)))
    return {}


def appD_error():
    """Per-op magnitude of error (decimal digits) vs float64 ground truth."""
    from repro.core.goom import Goom, from_goom, to_goom
    from repro.core.ops import goom_add, goom_mul, lmme_reference

    print("# appD_error: max decimal digits of relative error, f32-GOOM ops")
    rng = np.random.default_rng(0)
    xs64 = 10.0 ** rng.uniform(-6, 6, 100_000)
    ys64 = 10.0 ** rng.uniform(-6, 6, 100_000)
    xs = jnp.asarray(xs64, jnp.float32)
    ys = jnp.asarray(ys64, jnp.float32)

    def digits(got, ref64):
        rel = np.abs(np.asarray(got, np.float64) - ref64) / np.abs(ref64)
        return float(np.log10(np.maximum(rel, 1e-17).max()))

    g, h = to_goom(xs), to_goom(ys)
    out = {
        "reciprocal": digits(from_goom(Goom(-g.log_abs, g.sign)), 1.0 / xs64),
        "square": digits(from_goom(goom_mul(g, g)), xs64 * xs64),
        "sqrt": digits(from_goom(Goom(0.5 * g.log_abs, g.sign)),
                       np.sqrt(xs64)),
        "log": digits(g.log_abs, np.log(xs64)),
        "mul": digits(from_goom(goom_mul(g, h)), xs64 * ys64),
        "add": digits(from_goom(goom_add(g, h)), xs64 + ys64),
    }
    a64 = rng.normal(size=(256, 256))
    b64 = rng.normal(size=(256, 256))
    ref = a64 @ b64
    got = from_goom(lmme_reference(to_goom(jnp.asarray(a64, jnp.float32)),
                                   to_goom(jnp.asarray(b64, jnp.float32))))
    out["matmul_fro_rel"] = float(
        np.linalg.norm(np.asarray(got, np.float64) - ref) / np.linalg.norm(ref)
    )
    for k, v in out.items():
        print(f"{k},{v:.3f}")
    # float32 carries ~7.2 decimal digits; GOOM ops must stay within ~1.5
    assert out["mul"] < -5.0 and out["square"] < -5.0
    assert out["matmul_fro_rel"] < 1e-4
    return out


def appD_time():
    """Per-op wall-time: GOOM vs raw float (App. D; CPU here, not GPU)."""
    from repro.core.ops import goom_add, goom_mul, lmme_reference
    from repro.core.goom import to_goom

    print("# appD_time: mean ms per op on 4M-element batches (CPU)")
    print("op,float_ms,goom_ms,ratio")
    n = 1 << 22
    x = jax.random.uniform(jax.random.PRNGKey(0), (n,)) + 0.1
    y = jax.random.uniform(jax.random.PRNGKey(1), (n,)) + 0.1
    gx, gy = to_goom(x), to_goom(y)
    out = {}
    for name, ff, gf in [
        ("mul", jax.jit(lambda a, b: a * b), jax.jit(goom_mul)),
        ("add", jax.jit(lambda a, b: a + b), jax.jit(goom_add)),
    ]:
        tf = _bench(ff, x, y)
        tg = _bench(gf, gx, gy)
        out[name] = {"float_ms": tf * 1e3, "goom_ms": tg * 1e3}
        print(f"{name},{tf*1e3:.2f},{tg*1e3:.2f},{tg/tf:.1f}")
    a = jax.random.normal(jax.random.PRNGKey(2), (512, 512))
    b = jax.random.normal(jax.random.PRNGKey(3), (512, 512))
    ga, gb = to_goom(a), to_goom(b)
    tf = _bench(jax.jit(jnp.matmul), a, b)
    tg = _bench(jax.jit(lmme_reference), ga, gb)
    out["matmul"] = {"float_ms": tf * 1e3, "goom_ms": tg * 1e3}
    print(f"matmul,{tf*1e3:.2f},{tg*1e3:.2f},{tg/tf:.1f}")
    return out


def roofline():
    """Print the roofline table from the dry-run sweep results."""
    path = os.path.join(RESULTS_DIR, "dryrun_baseline.json")
    if not os.path.exists(path):
        print("# roofline: run `python -m repro.launch.dryrun --all "
              "--both-meshes --out results/dryrun_baseline.json` first")
        return {}
    with open(path) as f:
        rows = json.load(f)
    print("# roofline (from the compiled dry-run): times in ms")
    print("arch,shape,mesh,compute_ms,memory_ms,collective_ms,bottleneck,"
          "useful_frac,mfu,peak_GiB")
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        if "skipped" in r:
            print(f"{r['arch']},{r['shape']},{r['mesh']},SKIP")
            continue
        peak = (r.get("memory_per_device") or {}).get("peak_bytes", 0) / 2**30
        print(f"{r['arch']},{r['shape']},{r['mesh']},"
              f"{r['compute_s']*1e3:.2f},{r['memory_s']*1e3:.2f},"
              f"{r['collective_s']*1e3:.2f},{r['bottleneck']},"
              f"{r['useful_fraction']:.2f},{r['mfu']:.4f},{peak:.1f}")
    return {"n": len(rows)}


def scan_backends(backends=("reference", "pallas", "pallas_gpu_interpret"),
                  emit_bench: bool = False, preset: str = "full"):
    """All four engine ops per backend, with cross-backend parity.

    Default sweep: the XLA reference, whatever ``pallas`` resolves to on
    this host (compiled TPU/GPU kernels, interpret on CPU), and the
    GPU-shaped kernels under interpret (the CI parity column).  With
    ``emit_bench`` a normalized per-op throughput table plus a sequence-
    length sweep (per-op kernel-vs-``xla_reference`` speedup ratios at
    each T) is written to ``results/BENCH_scan.json`` (CI uploads it as
    the perf-trajectory artifact).  ``preset="smoke"`` shrinks the sweep
    to interpret-friendly lengths for CI."""
    import numpy as np
    from repro.core import engine
    from repro.core.goom import to_goom

    print("# scan_backends: engine-dispatched GOOM ops")
    print("op,backend,resolved,shape,ms,melem_per_s")
    out = {}
    key = jax.random.PRNGKey(0)
    baseline = {}
    for backend in backends:
        with engine.use_backend(backend):
            resolved = engine.resolved_backend()
            # interpret mode executes the kernel body per grid step in
            # Python — a correctness path, so keep its shapes small.
            small = resolved in ("pallas_interpret", "pallas_gpu_interpret")
            t, c = (256, 64) if small else (4096, 512)
            tm, d = (32, 8) if small else (512, 16)
            n = 128 if small else 512

            da = to_goom(jnp.exp(-jnp.abs(jax.random.normal(key, (t, c)))))
            db = to_goom(jax.random.normal(jax.random.PRNGKey(1), (t, c)))
            ma = to_goom(jax.random.normal(key, (tm, d, d)) * 0.5)
            mb = to_goom(jax.random.normal(jax.random.PRNGKey(2), (tm, d, 1)) * 0.5)
            la = to_goom(jax.random.normal(key, (n, n)))
            lb = to_goom(jax.random.normal(jax.random.PRNGKey(4), (n, n)))

            cells = [
                ("diagonal_scan", engine.diagonal_scan, (da, db),
                 f"({t}x{c})", t * c),
                ("matrix_scan", engine.matrix_scan, (ma, mb),
                 f"({tm}x{d}x{d})", tm * d * d),
                ("cumulative_lmme", engine.cumulative_lmme, (ma,),
                 f"({tm}x{d}x{d})", tm * d * d),
                ("lmme", engine.lmme, (la, lb), f"({n}x{n})", n * n),
            ]
            row = {"resolved": resolved}
            for op, fn, args, shape, elems in cells:
                ms = _bench(jax.jit(fn), *args) * 1e3
                row[op] = {"shape": shape, "ms": ms, "elems": elems,
                           "melem_per_s": elems / ms / 1e3}
                print(f"{op},{backend},{resolved},{shape},{ms:.2f},"
                      f"{row[op]['melem_per_s']:.2f}")
            out[backend] = row

            # parity across backends on a shared small problem
            pa = to_goom(jax.random.normal(key, (24, 4, 4)) * 0.5)
            pb = to_goom(jax.random.normal(jax.random.PRNGKey(3), (24, 4, 1)))
            got = engine.matrix_scan(pa, pb)
            if "matrix" in baseline:
                np.testing.assert_allclose(
                    got.log_abs, baseline["matrix"], rtol=1e-4, atol=1e-3)
            baseline["matrix"] = np.asarray(got.log_abs)
    if emit_bench:
        sweep = _scan_seq_sweep(backends, preset)
        path = os.path.join(RESULTS_DIR, "BENCH_scan.json")
        with open(path, "w") as f:
            json.dump({"schema": "bench_scan/v2",
                       "device_kind": jax.devices()[0].device_kind,
                       "platform": jax.default_backend(),
                       "preset": preset,
                       "backends": out,
                       "seq_sweep": sweep}, f, indent=1)
        print(f"wrote {path}")
    return out


def _scan_seq_sweep(backends, preset: str):
    """Per-op speedup-vs-``xla_reference`` across a sequence-length sweep.

    Every scan op is timed at each T under the reference backend and under
    every requested kernel backend; the recorded ``speedup_vs_reference``
    is ref_ms / kernel_ms (>1 = the kernel wins).  The smoke preset keeps
    T small enough for interpret mode, where the kernel body runs one grid
    step at a time in Python — those ratios track the perf *trajectory*
    across PRs, not absolute kernel quality."""
    from repro.core import engine
    from repro.core.goom import to_goom

    smoke = preset == "smoke"
    ts = (64, 256, 1024) if smoke else (256, 4096, 65536)
    c = 32 if smoke else 256
    d, m = (4, 1) if smoke else (8, 1)
    kernel_backends = [b for b in backends
                       if b not in ("reference", "xla_reference")]

    print("# seq sweep: per-op speedup vs xla_reference")
    print("op,backend,resolved,T,ms,speedup_vs_reference")
    sweep = {}
    for t in ts:
        key = jax.random.PRNGKey(t)
        da = to_goom(jnp.exp(-jnp.abs(jax.random.normal(key, (t, c)))))
        db = to_goom(jax.random.normal(jax.random.PRNGKey(1), (t, c)))
        ma = to_goom(jax.random.normal(key, (t, d, d)) * 0.5)
        mb = to_goom(jax.random.normal(jax.random.PRNGKey(2), (t, d, m)) * 0.5)
        cells = [
            ("diagonal_scan", engine.diagonal_scan, (da, db)),
            ("matrix_scan", engine.matrix_scan, (ma, mb)),
            ("cumulative_lmme", engine.cumulative_lmme, (ma,)),
        ]
        ref_ms = {}
        with engine.use_backend("reference"):
            for op, fn, args in cells:
                ref_ms[op] = _bench(jax.jit(fn), *args) * 1e3
                print(f"{op},reference,xla_reference,{t},"
                      f"{ref_ms[op]:.2f},1.00")
        per_t = {"reference_ms": ref_ms, "kernels": {}}
        for backend in kernel_backends:
            with engine.use_backend(backend):
                resolved = engine.resolved_backend()
                row = {"resolved": resolved}
                for op, fn, args in cells:
                    ms = _bench(jax.jit(fn), *args) * 1e3
                    row[op] = {"ms": ms,
                               "speedup_vs_reference": ref_ms[op] / ms}
                    print(f"{op},{backend},{resolved},{t},{ms:.2f},"
                          f"{ref_ms[op] / ms:.2f}")
                per_t["kernels"][backend] = row
        sweep[str(t)] = per_t
    return sweep


def scan_sharded():
    """Sequence-sharded scans: timings per shard count + parity vs 1 device."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import engine
    from repro.core.goom import to_goom

    devs = jax.devices()
    counts = [p for p in (1, 2, 4, 8, 16) if p <= len(devs)]
    print(f"# scan_sharded: {len(devs)} devices; shard counts {counts}")
    print("op,seq_shards,shape,ms")
    t, d, m = 2048, 8, 8
    tc, c = 8192, 256
    key = jax.random.PRNGKey(0)
    a = to_goom(jax.random.normal(key, (t, d, d)) * 0.5)
    b = to_goom(jax.random.normal(jax.random.PRNGKey(1), (t, d, m)) * 0.5)
    da = to_goom(jnp.exp(-jnp.abs(jax.random.normal(key, (tc, c)))))
    db = to_goom(jax.random.normal(jax.random.PRNGKey(2), (tc, c)))

    out = {}
    baseline = {}
    for p in counts:
        mesh = Mesh(np.array(devs[:p]).reshape(1, p), ("data", "seq"))
        with engine.use_mesh(mesh, seq_axis="seq"):
            assert engine.active_seq_shards() == p or p == 1
            row = {}
            for op, fn, args, shape in [
                ("matrix_scan", engine.matrix_scan, (a, b), f"({t}x{d}x{m})"),
                ("cumulative_lmme", engine.cumulative_lmme, (a,),
                 f"({t}x{d}x{d})"),
                ("diagonal_scan", engine.diagonal_scan, (da, db),
                 f"({tc}x{c})"),
            ]:
                jf = jax.jit(fn)
                ms = _bench(jf, *args) * 1e3
                row[op] = ms
                print(f"{op},{p},{shape},{ms:.2f}")
                got = np.asarray(jf(*args).log_abs)
                if op in baseline:
                    # smoke parity: signed data compounds over 2k steps, so
                    # cancellation-adjacent elements reassociate at ~1e-4;
                    # the strict 1e-5 bounds live in tests/test_sharded.py
                    # on well-posed (positive-operand) problems.
                    finite = np.isfinite(baseline[op])
                    np.testing.assert_allclose(
                        got[finite], baseline[op][finite],
                        rtol=1e-3, atol=1e-3)
                else:
                    baseline[op] = got
            out[p] = row
    return out


def serve_throughput(preset: str = "full", backend: str = "auto"):
    """Continuous-batching engine vs the legacy static-batch serve path.

    Same request mix both ways: the legacy path prefills whole waves of
    ``max_slots`` prompts in lockstep and decodes every wave to its
    *longest* request; the engine admits requests into slots as they
    free up.  Reports requests/s for both and p50/p99 decode-step (per-
    token) latency for the engine.  ``--preset smoke`` shrinks everything
    to CI size; timings are informational (no assertions — CI machines
    jitter), the parity suite lives in tests/test_serve_engine.py.
    """
    from repro.configs import get_config
    from repro.models.common import unzip
    from repro.models.model import DecoderLM
    from repro.serve import Engine, Request, slot_cache_bytes
    from repro.serve.steps import generate

    smoke = preset == "smoke"
    arch = "goom-rnn-124m"  # the paper's model: every layer a GOOM scan
    cfg = get_config(arch, smoke=True)
    model = DecoderLM(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))

    # short prompts, long high-variance generations: the chat-serving
    # profile continuous batching exists for — a static wave decodes every
    # member to the wave maximum, the engine refills freed slots instead
    if smoke:
        n_req, p_len, max_slots, chunk = 4, 4, 2, 4
        gens = [3 if i % 2 == 0 else 48 for i in range(n_req)]
    else:
        n_req, p_len, max_slots, chunk = 12, 8, 4, 8
        gens = [4 + (i % 4) * 28 for i in range(n_req)]       # 4..88
    page_len = p_len + max(gens)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (n_req, p_len), 0, cfg.vocab)

    sb = slot_cache_bytes(model, max_slots, page_len)
    print(f"# serve_throughput[{preset}]: {arch}(smoke), {n_req} requests, "
          f"prompt {p_len}, gen {min(gens)}..{max(gens)}, "
          f"{max_slots} slots x page {page_len} "
          f"({sb['per_slot']/2**10:.1f} KiB/slot)")

    # -- legacy static batching: waves of max_slots, lockstep to the max --
    def legacy_pass():
        done = 0
        for w0 in range(0, n_req, max_slots):
            wave = list(range(w0, min(w0 + max_slots, n_req)))
            toks = generate(model, params, prompts[jnp.asarray(wave)],
                            n_tokens=max(gens[i] for i in wave),
                            max_len=page_len, backend=backend)
            jax.block_until_ready(toks)
            done += len(wave)
        return done

    legacy_pass()  # warm the cached jitted steps
    t0 = time.perf_counter()
    legacy_pass()
    t_legacy = time.perf_counter() - t0

    # -- continuous batching engine --------------------------------------
    def engine_pass(eng):
        for i in range(n_req):
            eng.submit(Request(uid=i, prompt=list(map(int, prompts[i])),
                               max_new_tokens=gens[i]))
        lats = []
        while eng.has_work:
            s0 = time.perf_counter()
            eng.step()
            lats.append(time.perf_counter() - s0)
        return lats

    eng = Engine(model, params, max_slots=max_slots, page_len=page_len,
                 chunk=chunk, backend=backend)
    engine_pass(eng)  # warm the persistent executables
    eng.run()         # drain warm-pass results through the public API
    t0 = time.perf_counter()
    lats = engine_pass(eng)
    t_engine = time.perf_counter() - t0
    results = eng.run()
    assert sorted(results) == list(range(n_req))
    # no EOS in this workload: every request must generate its full budget
    assert all(len(results[i]) == gens[i] for i in range(n_req))

    lat = np.sort(np.asarray(lats))
    p50 = float(lat[len(lat) // 2]) * 1e3
    p99 = float(lat[min(len(lat) - 1, int(len(lat) * 0.99))]) * 1e3
    out = {
        "legacy_rps": n_req / t_legacy,
        "engine_rps": n_req / t_engine,
        "speedup": t_legacy / t_engine,
        "p50_step_ms": p50,
        "p99_step_ms": p99,
        "per_slot_bytes": sb["per_slot"],
    }
    print("path,requests_per_s,total_s")
    print(f"legacy_static,{out['legacy_rps']:.2f},{t_legacy:.2f}")
    print(f"engine,{out['engine_rps']:.2f},{t_engine:.2f}")
    print(f"engine decode-step latency: p50 {p50:.1f} ms, p99 {p99:.1f} ms")
    print(f"speedup (legacy/engine): {out['speedup']:.2f}x")
    return out


def serve_api(preset: str = "full", backend: str = "auto"):
    """End-to-end HTTP serving: concurrent streaming clients over SSE.

    Measures the whole stack — socket, SSE framing, gateway thread hop,
    engine step loop — not just the engine: aggregate client-observed
    tokens/s, ttft / request-latency percentiles from ``/status``, and
    admission-control behavior (clients outnumber the waiting-queue
    watermark, so the 429-retry path is exercised under load).  Timings
    are informational (no assertions); conformance lives in
    tests/test_serve_api.py.
    """
    import threading

    from repro.serve.api import BackgroundServer, Gateway, build_engine
    from repro.serve.api import client as api_client

    smoke = preset == "smoke"
    arch = "goom-rnn-124m"
    if smoke:
        n_clients, max_slots, p_len, gen, max_queue = 6, 2, 4, 24, 2
    else:
        n_clients, max_slots, p_len, gen, max_queue = 24, 4, 8, 96, 8
    page_len = p_len + gen

    eng, cfg = build_engine(arch, smoke=True, max_slots=max_slots,
                            page_len=page_len, chunk=4, backend=backend)
    gateway = Gateway(eng, max_queue=max_queue)
    srv = BackgroundServer(gateway).start()
    print(f"# serve_api[{preset}]: {arch}(smoke), {n_clients} streaming "
          f"clients, {max_slots} slots x page {page_len}, "
          f"queue watermark {max_queue}")

    prompts = jax.random.randint(
        jax.random.PRNGKey(7), (n_clients, p_len), 0, cfg.vocab)

    def client(i, out):
        toks, retries = [], 0
        while True:
            try:
                for ev in api_client.stream_completion(
                        srv.host, srv.port,
                        {"prompt": list(map(int, prompts[i])),
                         "max_tokens": gen}):
                    toks.append(ev["choices"][0]["token"])
                out[i] = (len(toks), retries)
                return
            except api_client.RetryLater as e:
                retries += 1
                time.sleep(min(e.retry_after, 0.5))

    try:
        # warm the jitted paths off the clock
        api_client.completion(srv.host, srv.port,
                              {"prompt": [1, 2, 3], "max_tokens": 2})
        out = [None] * n_clients
        threads = [threading.Thread(target=client, args=(i, out),
                                    daemon=True) for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        snap = api_client.get_status(srv.host, srv.port)
    finally:
        srv.stop()

    n_tok = sum(n for n, _ in out)
    n_retries = sum(r for _, r in out)
    lat = snap["latency_ms"]
    res = {
        "clients": n_clients,
        "tokens_total": n_tok,
        "client_tok_per_s": n_tok / wall,
        "wall_s": wall,
        "retries_429": n_retries,
        "rejected": snap["requests"]["rejected"],
        "ttft_ms": lat["ttft"],
        "request_ms": lat["request"],
        "engine_step_ms": lat["engine_step"],
    }
    assert all(n == gen for n, _ in out)  # every client got its budget
    print("metric,value")
    print(f"client_tokens_per_s,{res['client_tok_per_s']:.1f}")
    print(f"wall_s,{wall:.2f}")
    print(f"retries_429,{n_retries} (server rejected {res['rejected']})")
    print(f"ttft_ms,p50 {lat['ttft']['p50']:.0f} / p99 {lat['ttft']['p99']:.0f}")
    print(f"request_ms,p50 {lat['request']['p50']:.0f} / "
          f"p99 {lat['request']['p99']:.0f}")
    print(f"engine_step_ms,p50 {lat['engine_step']['p50']:.1f} / "
          f"p99 {lat['engine_step']['p99']:.1f}")
    return res


def _update_bench_serve(section: str, payload: dict) -> str:
    """Merge one benchmark's rows into ``results/BENCH_serve.json``
    (bench_serve/v2: one file, one section per serve benchmark, so
    ``serve_prefix`` and ``serve_decode`` don't clobber each other).  A
    v1 file (bare serve_prefix payload at top level) is discarded."""
    path = os.path.join(RESULTS_DIR, "BENCH_serve.json")
    doc = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            doc = {}
    if doc.get("schema") != "bench_serve/v2":
        doc = {}
    doc.update({
        "schema": "bench_serve/v2",
        "device_kind": jax.devices()[0].device_kind,
        "platform": jax.default_backend(),
    })
    doc[section] = payload
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


def serve_decode(preset: str = "full", backend: str = "auto"):
    """Fused multi-step decode A/B: horizon 1 vs adaptive, stream off/on.

    The decode-heavy continuous-batching shape multi-step decode exists
    for (short prompts, long generations, every slot busy): the same
    workload through four engines — ``eos_scan_every=1`` (one dispatch
    and one host sync per token, the pre-fusion engine) vs ``8``
    (adaptive fused horizons + double-buffered token flight), each with
    streaming callbacks off and on.  Outputs must be bit-identical
    across all four cells.  Deterministic acceptance: the fused
    non-streaming cell dispatches >=4 tokens per device round-trip and
    materializes <=1/8 host syncs per token; wall-clock tokens/s is
    reported (and the h8/h1 speedup printed) but only gated on not
    *regressing* below 1x so CI stays robust to noisy runners.  Writes
    the ``serve_decode`` section of results/BENCH_serve.json
    (bench_serve/v2).
    """
    from repro.configs import get_config
    from repro.models.common import unzip
    from repro.models.model import DecoderLM
    from repro.serve import Engine, Request

    smoke = preset == "smoke"
    arch = "goom-rnn-124m"
    cfg = get_config(arch, smoke=True)
    model = DecoderLM(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))

    if smoke:
        n_req, p_len, gen, max_slots, chunk = 4, 4, 48, 4, 4
    else:
        n_req, p_len, gen, max_slots, chunk = 8, 8, 128, 4, 4
    page_len = p_len + gen + 8
    prompts = [list(map(int, jax.random.randint(
        jax.random.PRNGKey(20 + i), (p_len,), 0, cfg.vocab)))
        for i in range(n_req)]
    print(f"# serve_decode[{preset}]: {arch}(smoke), {n_req} requests x "
          f"{gen} tokens through {max_slots} slots, chunk {chunk}")

    def run_cell(horizon, stream):
        events = []
        eng = Engine(model, params, max_slots=max_slots, page_len=page_len,
                     chunk=chunk, backend=backend, eos_scan_every=horizon,
                     stream_callback=(
                         (lambda uid, toks, reason:
                          events.append((uid, list(toks)))) if stream
                         else None))
        # warm pass: max_slots+1 short requests compile prefill plus both
        # decode horizons (k=1 runs while the extra request queues)
        eng.run([Request(uid=f"w{j}", prompt=prompts[0], max_new_tokens=8,
                         stream=stream) for j in range(max_slots + 1)])
        events.clear()
        pre = eng.decode_stats()  # counters are cumulative: delta the warm
        t0 = time.perf_counter()
        for i in range(n_req):
            eng.submit(Request(uid=i, prompt=prompts[i],
                               max_new_tokens=gen, stream=stream))
        while eng.has_work:
            eng.step()
        wall = time.perf_counter() - t0
        outs = {i: eng.pop_result(i) for i in range(n_req)}
        if stream:  # the event stream must reassemble the exact outputs
            per = {i: [] for i in range(n_req)}
            for uid, toks in events:
                per[uid].extend(toks)
            assert per == outs
        post = eng.decode_stats()
        dispatches = post["dispatches"] - pre["dispatches"]
        steps = post["decode_steps"] - pre["decode_steps"]
        syncs = post["host_syncs"] - pre["host_syncs"]
        n_tok = sum(len(v) for v in outs.values())
        return {
            "horizon": horizon,
            "streaming": stream,
            "wall_s": wall,
            "tokens_total": n_tok,
            "tokens_per_s": n_tok / wall,
            "dispatches": dispatches,
            "tokens_per_dispatch": steps / max(dispatches, 1),
            "host_syncs": syncs,
            "syncs_per_token": syncs / max(steps, 1),
        }, outs

    cells = {}
    ref_outs = None
    for horizon in (1, 8):
        for stream in (False, True):
            key = f"h{horizon}_{'stream' if stream else 'batch'}"
            cells[key], outs = run_cell(horizon, stream)
            if ref_outs is None:
                ref_outs = outs
            else:
                assert outs == ref_outs  # fusion must not change a token
    speedup = (cells["h8_batch"]["tokens_per_s"]
               / cells["h1_batch"]["tokens_per_s"])
    stream_speedup = (cells["h8_stream"]["tokens_per_s"]
                      / cells["h1_stream"]["tokens_per_s"])
    # deterministic acceptance: the fused engine really batches the work
    assert cells["h8_batch"]["tokens_per_dispatch"] >= 4.0, cells["h8_batch"]
    assert cells["h8_batch"]["syncs_per_token"] <= 1.0 / 8, cells["h8_batch"]
    assert cells["h8_stream"]["host_syncs"] < cells["h1_stream"]["host_syncs"]
    assert speedup >= 1.0, f"fused decode slower than single-step: {speedup}"

    res = {
        "preset": preset,
        "workload": {"arch": arch, "requests": n_req, "prompt": p_len,
                     "gen": gen, "max_slots": max_slots, "chunk": chunk,
                     "page_len": page_len},
        "cells": cells,
        "decode_speedup": speedup,
        "stream_speedup": stream_speedup,
    }
    path = _update_bench_serve("serve_decode", res)
    print("cell,tokens_per_s,tokens_per_dispatch,syncs_per_token")
    for key, row in cells.items():
        print(f"{key},{row['tokens_per_s']:.1f},"
              f"{row['tokens_per_dispatch']:.2f},"
              f"{row['syncs_per_token']:.4f}")
    print(f"decode speedup (h8/h1): {speedup:.2f}x non-streaming, "
          f"{stream_speedup:.2f}x streaming")
    print(f"wrote {path}")
    return res


def serve_prefix(preset: str = "full", backend: str = "auto"):
    """Cross-request prefix reuse: shared-system-prompt TTFT, on vs off.

    The chat-serving shape prefix caching exists for: N clients share one
    K-token system prompt and differ only in a short suffix.  Each client
    is submitted alone and timed to its first token (TTFT == admission ==
    prefill cost), against two engines over the same params — prefix
    reuse on (warm radix index + carry checkpoints) and off (every
    admission re-prefills from token 0).  Prefill work is also counted in
    *dispatched tokens* via the prefill's call counters — a deterministic
    proxy for prefill FLOPs that CI can assert on while wall-clock stays
    informational.  Writes the ``serve_prefix`` section of
    ``results/BENCH_serve.json`` (bench_serve/v2).
    """
    from repro.configs import get_config
    from repro.models.common import unzip
    from repro.models.model import DecoderLM
    from repro.serve import Engine, Request

    smoke = preset == "smoke"
    arch = "goom-rnn-124m"
    cfg = get_config(arch, smoke=True)
    model = DecoderLM(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))

    if smoke:
        n_clients, k_shared, sfx, gen, chunk, max_slots = 6, 48, 4, 4, 4, 2
    else:
        n_clients, k_shared, sfx, gen, chunk, max_slots = 16, 192, 8, 8, 8, 4
    page_len = k_shared + sfx + gen
    shared = jax.random.randint(
        jax.random.PRNGKey(11), (k_shared,), 0, cfg.vocab)
    suffixes = jax.random.randint(
        jax.random.PRNGKey(12), (n_clients, sfx), 0, cfg.vocab)
    prompts = [list(map(int, shared)) + list(map(int, suffixes[i]))
               for i in range(n_clients)]
    print(f"# serve_prefix[{preset}]: {arch}(smoke), {n_clients} clients "
          f"sharing a {k_shared}-token prefix (+{sfx} suffix), chunk {chunk}")

    def run_engine(prefix_reuse):
        eng = Engine(model, params, max_slots=max_slots, page_len=page_len,
                     chunk=chunk, backend=backend,
                     prefix_reuse=prefix_reuse)
        # warm pass: compiles every jitted path and (reuse on) populates
        # the index — the measured clients then hit a *warm* cache
        eng.submit(Request(uid="warm", prompt=prompts[0],
                           max_new_tokens=gen))
        while eng.has_work:
            eng.step()
        eng.pop_result("warm")
        pre_chunk = eng._prefill.n_chunk_calls
        pre_tail = eng._prefill.n_tail_calls
        ttfts, outs = [], {}
        for i in range(n_clients):
            t0 = time.perf_counter()
            eng.submit(Request(uid=i, prompt=prompts[i],
                               max_new_tokens=gen))
            eng.step()  # admission (prefill) + first decode
            ttfts.append(time.perf_counter() - t0)
            while eng.has_work:
                eng.step()
            outs[i] = eng.pop_result(i)
        # fused admission reprocesses the final piece: count it too, so
        # dispatched == prompt tokens when reuse is off
        fused = chunk if (k_shared + sfx) % chunk == 0 else 1
        dispatched = ((eng._prefill.n_chunk_calls - pre_chunk) * chunk
                      + (eng._prefill.n_tail_calls - pre_tail)
                      + n_clients * fused)
        lat = np.sort(np.asarray(ttfts)) * 1e3
        stats = eng.prefix_stats()
        return {
            "ttft_ms": {"p50": float(lat[len(lat) // 2]),
                        "p99": float(lat[min(len(lat) - 1,
                                             int(len(lat) * 0.99))]),
                        "mean": float(lat.mean())},
            "prefill_tokens_dispatched": dispatched,
            "prefill_tokens_per_prompt_token": dispatched / (
                n_clients * (k_shared + sfx)),
            "prefix_hit_rate": stats["hit_rate"],
            "prefill_tokens_saved": stats["prefill_tokens_saved"],
            "pool_occupancy": stats["pages"]["occupancy"],
        }, outs

    on, outs_on = run_engine(True)
    off, outs_off = run_engine(False)
    assert outs_on == outs_off  # reuse must not change a single token
    # deterministic acceptance: warm hits really skipped prefix prefill
    assert on["prefill_tokens_saved"] > 0
    assert on["prefill_tokens_dispatched"] < off["prefill_tokens_dispatched"]

    res = {
        "preset": preset,
        "workload": {"arch": arch, "clients": n_clients,
                     "shared_prefix": k_shared, "suffix": sfx, "gen": gen,
                     "chunk": chunk, "max_slots": max_slots,
                     "page_len": page_len},
        "reuse_on": on,
        "reuse_off": off,
        "ttft_speedup_p50": off["ttft_ms"]["p50"] / on["ttft_ms"]["p50"],
        "dispatch_reduction": (off["prefill_tokens_dispatched"]
                               / on["prefill_tokens_dispatched"]),
    }
    path = _update_bench_serve("serve_prefix", res)
    print("mode,ttft_p50_ms,ttft_p99_ms,prefill_tokens,hit_rate")
    for mode, row in (("reuse_on", on), ("reuse_off", off)):
        print(f"{mode},{row['ttft_ms']['p50']:.1f},"
              f"{row['ttft_ms']['p99']:.1f},"
              f"{row['prefill_tokens_dispatched']},"
              f"{row['prefix_hit_rate']:.2f}")
    print(f"ttft speedup (off/on, p50): {res['ttft_speedup_p50']:.2f}x; "
          f"prefill dispatch reduction: {res['dispatch_reduction']:.1f}x")
    print(f"wrote {path}")
    return res


ALL = {
    "table1_range": table1_range,
    "fig1_chains": fig1_chains,
    "appD_error": appD_error,
    "appD_time": appD_time,
    "fig3_lyapunov": fig3_lyapunov,
    "fig4_rnn": fig4_rnn,
    "roofline": roofline,
    "scan_backends": scan_backends,
    "scan_sharded": scan_sharded,
    "serve_throughput": serve_throughput,
    "serve_api": serve_api,
    "serve_prefix": serve_prefix,
    "serve_decode": serve_decode,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="*", metavar="name",
                    help=f"benchmarks to run (default: all): {', '.join(ALL)}")
    ap.add_argument("--backend", action="append",
                    choices=["reference", "pallas", "auto",
                             "pallas_tpu", "pallas_gpu", "pallas_interpret",
                             "pallas_gpu_interpret", "xla_reference"],
                    help="scan-engine backend; repeat to sweep (scan_backends "
                         "sweeps reference+pallas+pallas_gpu_interpret by "
                         "default)")
    ap.add_argument("--preset", choices=["full", "smoke"], default="full",
                    help="problem sizes for the serve_* benchmarks and the "
                         "scan_backends --emit-bench seq sweep (smoke = "
                         "CI/interpret shapes)")
    ap.add_argument("--emit-bench", action="store_true",
                    help="write results/BENCH_scan.json (normalized per-op "
                         "throughput from scan_backends; CI artifact)")
    args = ap.parse_args()
    names = args.names or list(ALL)
    if "scan_sharded" in names and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # Force 8 host devices for the sharded sweep.  Only effective if the
        # jax backend has not initialized yet (i.e. scan_sharded run alone
        # or first); otherwise the sweep covers whatever devices exist.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
    from repro import compile_cache
    from repro.core import engine

    compile_cache.enable()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    results = {}
    for name in names:
        print(f"\n=== {name} " + "=" * max(1, 60 - len(name)))
        t0 = time.time()
        if name == "scan_backends":
            results[name] = scan_backends(
                tuple(args.backend
                      or ("reference", "pallas", "pallas_gpu_interpret")),
                emit_bench=args.emit_bench, preset=args.preset)
        elif name.startswith("serve_"):
            results[name] = ALL[name](
                args.preset, (args.backend or ["auto"])[0])
        else:
            with engine.use_backend((args.backend or ["auto"])[0]):
                results[name] = ALL[name]()
        print(f"=== {name} done in {time.time()-t0:.1f}s")
    with open(os.path.join(RESULTS_DIR, "bench_results.json"), "w") as f:
        json.dump(results, f, indent=1, default=str)
    print("\nwrote results/bench_results.json")


if __name__ == "__main__":
    main()
