"""Continuous-batching serve engine: chunked-prefill parity (engine carry
ops at e±200 dynamic range; model logits across chunk sizes incl.
non-divisible lengths), slot cache ops, and scheduler join/leave parity
against per-sequence sequential decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.goom import Goom, to_goom
from repro.configs import get_config
from repro.models.common import unzip
from repro.models.model import DecoderLM
from repro.serve import (
    CANCELLED,
    Engine,
    Request,
    SlotAllocator,
    abstract_slot_caches,
    read_slot,
    slot_cache_bytes,
    write_slot,
)
from repro.serve.prefill import ChunkedPrefill

CHUNKS = [1, 7, 64]


def _model(arch="olmo-1b", f32=False):
    cfg = get_config(arch, smoke=True)
    if f32:
        import dataclasses

        cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    model = DecoderLM(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def _prompt(cfg, n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab)


# ---------------------------------------------------------------------------
# engine carry ops: chunked == full scan, bit-level in log space at e±200
# ---------------------------------------------------------------------------
def _chunked_scan(scan_carry, a, b, chunk):
    """Thread the carry through fixed-size chunks (+ remainder)."""
    t = a.shape[0]
    carry = None
    outs = []
    for lo in range(0, t, chunk):
        hi = min(lo + chunk, t)
        states, carry = scan_carry(a[lo:hi], b[lo:hi], carry)
        outs.append(states)
    return Goom(
        jnp.concatenate([o.log_abs for o in outs]),
        jnp.concatenate([o.sign for o in outs]),
    )


@pytest.mark.parametrize("chunk", CHUNKS)
def test_diagonal_scan_carry_chunked_matches_full_e200(chunk):
    """±e200 dynamic range: per-step log-decays of ±2 compound to log
    magnitudes past ±200 over 150 steps — parity must hold in log space."""
    t, c = 150, 8
    key = jax.random.PRNGKey(0)
    # half the channels grow (log a ≈ +2/step), half decay (≈ -2/step):
    # compound magnitudes sweep past e^{±200} in both directions
    drift = jnp.where(jnp.arange(c) % 2 == 0, 2.0, -2.0)
    a = Goom(drift[None] + jax.random.uniform(key, (t, c), minval=-0.5,
                                              maxval=0.5),
             jnp.ones((t, c)))
    b = to_goom(jax.random.normal(jax.random.PRNGKey(1), (t, c)))
    full = engine.diagonal_scan(a, b)
    assert float(jnp.max(jnp.abs(full.log_abs))) > 200.0  # range reached
    got = _chunked_scan(engine.diagonal_scan_carry, a, b, chunk)
    np.testing.assert_allclose(got.log_abs, full.log_abs,
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(got.sign, full.sign)


@pytest.mark.parametrize("chunk", CHUNKS)
def test_matrix_scan_carry_chunked_matches_full_e200(chunk):
    t, d = 150, 4
    # positive operands scaled so compounds sweep far past e±200: parity in
    # log space must be near-exact (no cancellation to blur reassociation)
    key = jax.random.PRNGKey(2)
    a = to_goom(jnp.abs(jax.random.normal(key, (t, d, d))) * 4.0)
    b = to_goom(jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (t, d, 1))))
    full = engine.matrix_scan(a, b)
    assert float(jnp.max(jnp.abs(full.log_abs))) > 200.0
    got = _chunked_scan(engine.matrix_scan_carry, a, b, chunk)
    np.testing.assert_allclose(got.log_abs, full.log_abs,
                               rtol=1e-6, atol=1e-4)
    np.testing.assert_array_equal(got.sign, full.sign)


def test_carry_out_equals_last_state():
    a = to_goom(jax.random.normal(jax.random.PRNGKey(4), (12, 3, 3)))
    b = to_goom(jax.random.normal(jax.random.PRNGKey(5), (12, 3, 1)))
    states, carry = engine.matrix_scan_carry(a, b)
    np.testing.assert_array_equal(carry.log_abs, states.log_abs[-1])
    np.testing.assert_array_equal(carry.sign, states.sign[-1])


# ---------------------------------------------------------------------------
# chunked prefill vs full-sequence prefill, per architecture
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_prefill_goom_rnn_matches_full(chunk):
    """The paper's model (every layer a GOOM scan): chunked ingestion must
    reproduce the full-sequence parallel scan to f32 reassociation level
    (f32 compute isolates the scan algebra from bf16 matmul lowering)."""
    cfg, model, params = _model("goom-rnn-124m", f32=True)
    prompt = _prompt(cfg, 19)
    lg_full, _ = model.prefill(params, prompt[None], model.init_caches(1, 64))
    lg, _, pos = ChunkedPrefill(model, chunk)(
        params, prompt, model.init_caches(1, 64))
    assert pos == 19
    scale = float(jnp.std(lg_full))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_full[:, -1]),
                               rtol=0, atol=1e-4 * scale)


@pytest.mark.parametrize("arch", ["olmo-1b", "gemma3-1b", "jamba-v0.1",
                                  "rwkv6-7b"])
@pytest.mark.parametrize("chunk", CHUNKS)
def test_chunked_prefill_archs_match_full(arch, chunk):
    """Mixed archs (attention pages, windowed SWA, mamba conv+ssm, rwkv
    token-shift states): chunked == full within bf16 KV-cache rounding."""
    cfg, model, params = _model(arch)
    prompt = _prompt(cfg, 19)
    lg_full, _ = model.prefill(params, prompt[None], model.init_caches(1, 64))
    lg, _, _ = ChunkedPrefill(model, chunk)(
        params, prompt, model.init_caches(1, 64))
    scale = float(jnp.std(lg_full))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_full[:, -1]),
                               rtol=0, atol=0.1 * scale)


def test_chunked_prefill_carry_positions_thread_across_calls():
    """Streaming ingestion: two ChunkedPrefill calls with `start` offsets
    equal one call over the concatenated prompt."""
    cfg, model, params = _model("goom-rnn-124m", f32=True)
    prompt = _prompt(cfg, 16)
    cp = ChunkedPrefill(model, 8)
    lg_one, _, _ = cp(params, prompt, model.init_caches(1, 64))
    caches = model.init_caches(1, 64)
    _, caches, pos = cp(params, prompt[:10], caches)
    lg_two, _, _ = cp(params, prompt[10:], caches, start=pos)
    np.testing.assert_allclose(np.asarray(lg_two), np.asarray(lg_one),
                               rtol=0, atol=1e-4 * float(jnp.std(lg_one)))


# ---------------------------------------------------------------------------
# slot cache ops
# ---------------------------------------------------------------------------
def test_slot_write_read_roundtrip():
    cfg, model, params = _model("jamba-v0.1")
    slots = model.init_slot_caches(4, 32)
    prompt = _prompt(cfg, 9)
    _, caches, _ = ChunkedPrefill(model, 4)(params, prompt,
                                            model.init_caches(1, 32))
    slots = write_slot(slots, caches, 2)
    back = read_slot(slots, 2)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # neighboring slots untouched (still zeros)
    other = read_slot(slots, 1)
    for leaf in jax.tree.leaves(other):
        assert float(jnp.max(jnp.abs(leaf.astype(jnp.float32)))) == 0.0


def test_abstract_slot_caches_no_allocation():
    _, model, _ = _model("olmo-1b")
    tree = abstract_slot_caches(model, 8, 128)
    leaves = jax.tree.leaves(tree)
    assert leaves and all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    # every leaf leads with the slot dim
    assert all(l.shape[0] == 8 for l in leaves)
    sb = slot_cache_bytes(model, 8, 128)
    assert sb["total"] == sb["kv_pages"] + sb["recurrent"]
    assert sb["kv_pages"] > 0  # olmo: attention KV pages dominate
    shapes = jax.eval_shape(lambda: model.init_slot_caches(8, 128))
    assert jax.tree.structure(shapes) == jax.tree.structure(tree)


def test_slot_allocator_lifecycle():
    alloc = SlotAllocator(3)
    got = [alloc.allocate() for _ in range(3)]
    assert got == [0, 1, 2] and alloc.allocate() is None
    alloc.release(1)
    assert alloc.n_free == 1 and alloc.allocate() == 1
    with pytest.raises(ValueError):
        alloc.release(5)
    alloc.release(0)
    with pytest.raises(ValueError):
        alloc.release(0)


# ---------------------------------------------------------------------------
# scheduler: continuous batching == per-sequence sequential decode
# ---------------------------------------------------------------------------
def _solo(model, params, prompt, n, page_len=64, chunk=4, **kw):
    eng = Engine(model, params, max_slots=1, page_len=page_len, chunk=chunk)
    return eng.run([Request(uid=0, prompt=prompt, max_new_tokens=n, **kw)])[0]

@pytest.mark.parametrize("arch", ["olmo-1b", "jamba-v0.1"])
def test_scheduler_join_leave_matches_sequential(arch):
    """5 requests with different prompt/generation lengths through 2 slots:
    sequences join and leave mid-batch; every output must equal the same
    request decoded alone (per-sequence sequential decode)."""
    cfg, model, params = _model(arch)
    prompts = [list(map(int, _prompt(cfg, 4 + 5 * i, seed=10 + i)))
               for i in range(5)]
    lens = [3 + 2 * i for i in range(5)]
    eng = Engine(model, params, max_slots=2, page_len=64, chunk=4)
    res = eng.run([Request(uid=i, prompt=p, max_new_tokens=n)
                   for i, (p, n) in enumerate(zip(prompts, lens))])
    assert sorted(res) == list(range(5))
    for i, (p, n) in enumerate(zip(prompts, lens)):
        assert res[i] == _solo(model, params, p, n), f"request {i}"
        assert len(res[i]) == n


def test_scheduler_first_token_matches_full_forward():
    """Greedy first token == argmax of the full forward at the last prompt
    position (same check the legacy driver passes)."""
    cfg, model, params = _model("olmo-1b")
    prompt = _prompt(cfg, 8)
    res = _solo(model, params, list(map(int, prompt)), 3)
    logits, _, _ = model.apply(params, prompt[None])
    assert res[0] == int(jnp.argmax(logits[0, -1]))


def test_scheduler_eos_frees_slot_for_waiting_request():
    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 8, seed=20)))
    base = _solo(model, params, p0, 12)
    eos = base[4]
    eng = Engine(model, params, max_slots=1, page_len=64, chunk=4)
    res = eng.run([
        Request(uid="a", prompt=p0, max_new_tokens=12, eos_id=eos),
        Request(uid="b", prompt=list(map(int, _prompt(cfg, 5, seed=21))),
                max_new_tokens=4),
    ])
    assert res["a"] == base[:5]          # truncated at EOS
    assert len(res["b"]) == 4            # admitted after the slot freed


def test_scheduler_rejects_oversized_empty_and_duplicate_requests():
    _, model, params = _model("olmo-1b")
    eng = Engine(model, params, max_slots=1, page_len=16, chunk=4)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=[1] * 12, max_new_tokens=8))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=1, prompt=[], max_new_tokens=2))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=2, prompt=[1, 2], max_new_tokens=0))
    eng.submit(Request(uid=3, prompt=[1, 2], max_new_tokens=2))
    with pytest.raises(ValueError):  # duplicate uid would shadow results
        eng.submit(Request(uid=3, prompt=[3, 4], max_new_tokens=2))


def test_legacy_generate_reuses_cached_jitted_steps():
    """Repeated generate calls must reuse the compiled steps (the re-jit
    fix): the per-model cache holds exactly one prefill and one decode
    entry across calls."""
    from repro.serve.steps import _STEP_CACHE, generate

    cfg, model, params = _model("olmo-1b")
    prompt = _prompt(cfg, 6).reshape(1, 6)
    out1 = generate(model, params, prompt, n_tokens=3, max_len=16)
    cached = _STEP_CACHE[model]
    assert len(cached) == 2  # one prefill + one decode entry
    steps1 = list(cached.values())
    out2 = generate(model, params, prompt, n_tokens=3, max_len=16)
    assert list(_STEP_CACHE[model].values()) == steps1  # same executables
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# cancellation: slot eviction + the CANCELLED terminal state
# ---------------------------------------------------------------------------
class _FakeClock:
    """Deterministic stand-in for the scheduler's ``time`` module."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self):
        return self.now


def test_cancel_active_request_frees_slot_and_returns_sentinel():
    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 6, seed=30)))
    p1 = list(map(int, _prompt(cfg, 5, seed=31)))
    ref1 = _solo(model, params, p1, 4)
    eng = Engine(model, params, max_slots=1, page_len=64, chunk=4)
    eng.submit(Request(uid="a", prompt=p0, max_new_tokens=30))
    eng.submit(Request(uid="b", prompt=p1, max_new_tokens=4))
    eng.step()
    eng.step()
    assert eng.n_active == 1 and eng.n_waiting == 1
    assert eng.cancel("a") is True
    # slot evicted immediately: allocator row free, request terminal
    assert eng.n_active == 0 and eng._alloc.n_used == 0
    assert eng.result("a") is CANCELLED
    assert eng.finish_reason("a") == "cancelled"
    assert "a" not in eng._results
    # the freed slot admits the waiting request, which decodes correctly
    while eng.has_work:
        eng.step()
    assert eng.result("b") == ref1
    assert eng._alloc.n_used == 0
    # terminal cancels are no-ops; unknown uids too
    assert eng.cancel("a") is False
    assert eng.cancel("b") is False
    assert eng.cancel("never-submitted") is False


def test_cancel_queued_request_never_runs():
    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 6, seed=32)))
    p1 = list(map(int, _prompt(cfg, 4, seed=33)))
    ref0 = _solo(model, params, p0, 5)
    eng = Engine(model, params, max_slots=1, page_len=64, chunk=4)
    eng.submit(Request(uid=0, prompt=p0, max_new_tokens=5))
    eng.submit(Request(uid=1, prompt=p1, max_new_tokens=5))
    eng.step()  # 0 active, 1 queued
    assert eng.cancel(1) is True
    assert eng.n_waiting == 0
    assert eng.result(1) is CANCELLED
    while eng.has_work:
        eng.step()
    assert eng.result(0) == ref0


def test_result_error_contract_distinguishes_terminal_states():
    """Regression for the docstring promise: KeyError for unknown uids,
    CANCELLED sentinel (never a KeyError, never a token list) for
    cancelled ones — so cancellation != "never submitted"."""
    _, model, params = _model("olmo-1b")
    eng = Engine(model, params, max_slots=1, page_len=32, chunk=4)
    with pytest.raises(KeyError):
        eng.result("never-submitted")
    eng.submit(Request(uid="c", prompt=[1, 2, 3], max_new_tokens=8))
    eng.cancel("c")
    assert eng.result("c") is CANCELLED
    assert not CANCELLED  # falsy sentinel, repr()s as CANCELLED
    assert repr(CANCELLED) == "CANCELLED"
    # a cancelled uid is a *used* uid: resubmission is a duplicate error
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(Request(uid="c", prompt=[1, 2], max_new_tokens=2))
    # pop_result forgets the terminal state entirely
    assert eng.pop_result("c") is CANCELLED
    with pytest.raises(KeyError):
        eng.result("c")


# ---------------------------------------------------------------------------
# deadlines: mid-decode eviction, queue expiry, and the dispatch-only rule
# ---------------------------------------------------------------------------
def test_deadline_mid_decode_evicts_and_frees_slot(monkeypatch):
    from repro.serve import scheduler

    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 6, seed=40)))
    p1 = list(map(int, _prompt(cfg, 5, seed=41)))
    ref0 = _solo(model, params, p0, 20)
    ref1 = _solo(model, params, p1, 4)
    clock = _FakeClock()
    monkeypatch.setattr(scheduler, "time", clock)
    eng = Engine(model, params, max_slots=1, page_len=64, chunk=4)
    eng.submit(Request(uid="t", prompt=p0, max_new_tokens=20,
                       deadline_ms=50.0))
    eng.submit(Request(uid="u", prompt=p1, max_new_tokens=4))
    eng.step()
    eng.step()
    assert eng.n_active == 1
    clock.now += 0.2  # 200ms: past the 50ms deadline
    finished = eng.step()
    assert "t" in finished
    assert eng.finish_reason("t") == "timeout"
    # partial output kept, and it is a prefix of the reference decode
    got = eng.result("t")
    assert 0 < len(got) < 20
    assert got == ref0[:len(got)]
    # the freed slot serves the queued request
    while eng.has_work:
        eng.step()
    assert eng.result("u") == ref1
    assert eng._alloc.n_used == 0


def test_deadline_expired_in_queue_reports_timeout(monkeypatch):
    from repro.serve import scheduler

    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 6, seed=42)))
    clock = _FakeClock()
    monkeypatch.setattr(scheduler, "time", clock)
    eng = Engine(model, params, max_slots=1, page_len=64, chunk=4)
    eng.submit(Request(uid="long", prompt=p0, max_new_tokens=6))
    eng.submit(Request(uid="q", prompt=[1, 2, 3], max_new_tokens=4,
                       deadline_ms=10.0))
    eng.step()  # "long" holds the only slot
    clock.now += 1.0
    while eng.has_work:
        eng.step()
    # never admitted: empty output, timeout reason, nothing leaked
    assert eng.result("q") == []
    assert eng.finish_reason("q") == "timeout"
    assert len(eng.result("long")) == 6
    assert eng._alloc.n_used == 0 and eng._n_deadlines == 0


def test_step_loop_dispatch_only_without_deadlines(monkeypatch):
    """Deadline support must cost nothing when unused: the step loop
    reads no clock and materializes no extra host syncs (flush only at
    the finish event) — the monkeypatch-and-count style of
    test_dispatch_matrix.py applied to the scheduler hot loop."""
    from repro.serve import scheduler

    cfg, model, params = _model("olmo-1b")
    eng = Engine(model, params, max_slots=2, page_len=32, chunk=4)

    clock_calls = {"n": 0}
    real_time = scheduler.time

    class _Counting:
        @staticmethod
        def monotonic():
            clock_calls["n"] += 1
            return real_time.monotonic()

    flush_calls = {"n": 0}
    real_flush = Engine._flush

    def counting_flush(self):
        flush_calls["n"] += 1
        return real_flush(self)

    monkeypatch.setattr(scheduler, "time", _Counting)
    monkeypatch.setattr(Engine, "_flush", counting_flush)
    eng.submit(Request(uid=0, prompt=[5, 6, 7], max_new_tokens=6))
    eng.submit(Request(uid=1, prompt=[8, 9], max_new_tokens=6))
    while eng.has_work:
        eng.step()
    assert clock_calls["n"] == 0, "deadline-free step loop read the clock"
    # both requests finish on the same step (same budget, admitted
    # together): exactly one flush materializes every token
    assert flush_calls["n"] == 1
    assert len(eng.result(0)) == 6 and len(eng.result(1)) == 6


def test_scheduler_clock_reads_are_goomcheck_guarded():
    """The deadline-clock invariant as a goomcheck rule (GC204): every
    ``time.monotonic()`` in the real scheduler sits inside the
    ``_deadline_clock`` guard, so clock cost scales with live deadlines
    only.  The zero-deadline runtime smoke above stays; the
    count-reads-per-step variant this test used to be is now the static
    rule."""
    from repro.analysis import repo_root, run_source

    sched = repo_root() / "src" / "repro" / "serve" / "scheduler.py"
    hits = [f for f in run_source(sched.read_text(), "serve/scheduler.py")
            if f.rule == "GC204"]
    assert hits == [], [str(h) for h in hits]
    # and the rule actually bites on a regression:
    bad = "import time\n\ndef step():\n    return time.monotonic()\n"
    assert [f.rule for f in run_source(bad, "serve/scheduler.py")] == ["GC204"]


# ---------------------------------------------------------------------------
# streaming: per-step token flush through stream_callback
# ---------------------------------------------------------------------------
def test_stream_callback_delivers_tokens_incrementally():
    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 5, seed=50)))
    ref = _solo(model, params, p0, 6)
    got = []
    eng = Engine(model, params, max_slots=2, page_len=32, chunk=4,
                 stream_callback=lambda uid, toks, reason:
                     got.append((uid, list(toks), reason)))
    eng.submit(Request(uid="s", prompt=p0, max_new_tokens=6, stream=True))
    while eng.has_work:
        eng.step()
    # terminal event exactly once, with the right reason
    assert [e[2] for e in got].count(None) == len(got) - 1
    assert got[-1][2] == "length"
    streamed = [t for _, toks, _ in got for t in toks]
    assert streamed == ref == eng.result("s")
    # streaming flushes every step: first batch arrives before finish
    assert len(got) >= 2


def test_stream_callback_cancel_emits_terminal_event():
    cfg, model, params = _model("olmo-1b")
    events = []
    eng = Engine(model, params, max_slots=1, page_len=32, chunk=4,
                 stream_callback=lambda uid, toks, reason:
                     events.append((uid, reason)))
    eng.submit(Request(uid="x", prompt=[1, 2, 3], max_new_tokens=20,
                       stream=True))
    eng.step()
    eng.step()
    eng.cancel("x")
    assert events[-1] == ("x", "cancelled")
    assert eng.result("x") is CANCELLED


# ---------------------------------------------------------------------------
# multi-step decode: fused horizons must be invisible in the outputs
# ---------------------------------------------------------------------------
HORIZONS = [1, 2, 8]


@pytest.mark.parametrize("chunk", CHUNKS)
def test_multi_step_horizon_parity_bit_identical(chunk):
    """Fused decode horizons are a dispatch-granularity change only:
    horizon 1, 2, and 8 engines produce bit-identical token streams for
    the same workload (3 requests through 2 slots, joins and leaves
    mid-batch), all equal to per-sequence sequential decode."""
    cfg, model, params = _model("olmo-1b")
    prompts = [list(map(int, _prompt(cfg, 4 + 3 * i, seed=60 + i)))
               for i in range(3)]
    lens = [12, 5, 9]
    def reqs():  # fresh Request objects per engine run
        return [Request(uid=i, prompt=p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, lens))]
    results = {}
    for h in HORIZONS:
        eng = Engine(model, params, max_slots=2, page_len=64, chunk=chunk,
                     eos_scan_every=h)
        results[h] = eng.run(reqs())
        assert eng.decode_stats()["horizon_max"] == h
    for h in HORIZONS[1:]:
        assert results[h] == results[1], f"horizon {h} diverged from 1"
    for i, (p, n) in enumerate(zip(prompts, lens)):
        assert results[1][i] == _solo(model, params, p, n), f"request {i}"


def test_multi_step_fuses_dispatches():
    """The horizon-8 engine actually fuses: far fewer dispatches than
    decode steps, and the realized tokens-per-dispatch approaches the
    horizon once no admissions are queued."""
    cfg, model, params = _model("olmo-1b")
    p = list(map(int, _prompt(cfg, 5, seed=64)))
    eng = Engine(model, params, max_slots=2, page_len=32, chunk=4,
                 eos_scan_every=8)
    eng.run([Request(uid=0, prompt=p, max_new_tokens=24)])
    stats = eng.decode_stats()
    assert stats["decode_steps"] >= 23
    assert stats["dispatches"] <= 5  # vs 23 single-step dispatches
    assert stats["tokens_per_dispatch"] > 4.0
    assert stats["last_horizon"] == 8


def test_multi_step_eos_mid_horizon_truncates_exactly():
    """EOS landing mid-horizon: the device freezes the slot in-flight and
    the host trims the frozen-repeat tail — output identical to the
    single-step engine's truncation."""
    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 8, seed=20)))
    base = _solo(model, params, p0, 12)
    eos = base[4]  # index 4: lands mid-way through the first 8-horizon
    eng = Engine(model, params, max_slots=1, page_len=64, chunk=4,
                 eos_scan_every=8)
    eng.submit(Request(uid="a", prompt=p0, max_new_tokens=12, eos_id=eos))
    eng.submit(Request(uid="b",
                       prompt=list(map(int, _prompt(cfg, 5, seed=21))),
                       max_new_tokens=4))
    while eng.has_work:
        eng.step()
    assert eng.result("a") == base[:base.index(eos) + 1]
    assert eng.finish_reason("a") == "stop"
    assert len(eng.result("b")) == 4  # the frozen slot freed for the queue
    assert eng._alloc.n_used == 0


@pytest.mark.parametrize("budget", [6, 10])
def test_multi_step_budget_exhaustion_mid_horizon(budget):
    """Budgets that end mid-horizon (6 and 10 at k=8: inside the first
    fused dispatch / one step into the second): the device freeze plus
    the host-side cap trim to exactly ``max_new_tokens`` tokens,
    bit-identical to sequential decode."""
    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 6, seed=65)))
    ref = _solo(model, params, p0, budget)
    eng = Engine(model, params, max_slots=2, page_len=32, chunk=4,
                 eos_scan_every=8)
    eng.submit(Request(uid="a", prompt=p0, max_new_tokens=budget))
    while eng.has_work:
        eng.step()
    assert eng.result("a") == ref and len(eng.result("a")) == budget
    assert eng.finish_reason("a") == "length"


def test_multi_step_deadline_expiry_dispatch_granularity(monkeypatch):
    """Deadline expiry under k>1: expiry is only checked between
    dispatches, so a deadline passing mid-horizon evicts at the *next*
    sweep with up to one horizon of extra tokens — the partial output is
    still an exact prefix of the reference decode, and the freed slot
    serves the queue."""
    from repro.serve import scheduler

    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 6, seed=40)))
    p1 = list(map(int, _prompt(cfg, 5, seed=41)))
    ref0 = _solo(model, params, p0, 40)
    ref1 = _solo(model, params, p1, 4)
    clock = _FakeClock()
    monkeypatch.setattr(scheduler, "time", clock)
    eng = Engine(model, params, max_slots=1, page_len=64, chunk=4,
                 eos_scan_every=8)
    # submitted alone: a non-empty admission queue would (correctly) pin
    # the horizon at k=1, and this test needs the fused path
    eng.submit(Request(uid="t", prompt=p0, max_new_tokens=40,
                       deadline_ms=50.0))
    eng.step()  # admission + first dispatch (k=1: no step estimate yet)
    eng.step()
    eng.step()  # frozen fake clock -> step estimate 0 -> full horizon
    assert eng.decode_stats()["last_horizon"] == 8
    clock.now += 0.2  # 200ms: past the 50ms deadline
    finished = eng.step()
    assert "t" in finished
    assert eng.finish_reason("t") == "timeout"
    got = eng.result("t")
    assert 0 < len(got) < 40
    assert got == ref0[:len(got)]
    # the freed slot serves a follow-up request
    eng.submit(Request(uid="u", prompt=p1, max_new_tokens=4))
    while eng.has_work:
        eng.step()
    assert eng.result("u") == ref1
    assert eng._alloc.n_used == 0


def test_multi_step_streaming_flush_ordering():
    """Streaming at horizon 8: events deliver every token exactly once in
    order (first token at admission, then completed transfer blocks), the
    concatenation equals the non-streaming reference, and streaming no
    longer costs one blocking sync per generated token."""
    cfg, model, params = _model("olmo-1b")
    p0 = list(map(int, _prompt(cfg, 5, seed=50)))
    ref = _solo(model, params, p0, 24)
    got = []
    eng = Engine(model, params, max_slots=2, page_len=32, chunk=4,
                 eos_scan_every=8,
                 stream_callback=lambda uid, toks, reason:
                     got.append((uid, list(toks), reason)))
    eng.submit(Request(uid="s", prompt=p0, max_new_tokens=24, stream=True))
    while eng.has_work:
        eng.step()
    # exactly one terminal event, and it is last
    assert [e[2] for e in got].count(None) == len(got) - 1
    assert got[-1][2] == "length"
    streamed = [t for _, toks, _ in got for t in toks]
    assert streamed == ref == eng.result("s")
    # incrementality: the first token arrives before the request finishes
    assert len(got) >= 2
    # the double-buffered flight batches the host syncs: strictly fewer
    # materializations than generated tokens (the old engine paid one
    # blocking sync per token to stream)
    stats = eng.decode_stats()
    assert stats["host_syncs"] < stats["decode_steps"]
    assert stats["host_syncs"] <= stats["dispatches"] + 2


def test_multi_step_host_syncs_per_token_regression():
    """The acceptance bound: at horizon 8 with non-streaming requests the
    engine materializes at most 1/8 host sync per generated token (the
    flight buffers whole (k, slots) blocks; no EOS means no scan-window
    flushes either)."""
    cfg, model, params = _model("olmo-1b")
    reqs = [Request(uid=i,
                    prompt=list(map(int, _prompt(cfg, 4 + i, seed=70 + i))),
                    max_new_tokens=48)
            for i in range(2)]
    eng = Engine(model, params, max_slots=2, page_len=64, chunk=4,
                 eos_scan_every=8)
    res = eng.run(reqs)
    assert all(len(res[i]) == 48 for i in range(2))
    stats = eng.decode_stats()
    assert stats["host_syncs"] * 8 <= stats["decode_steps"], stats
    assert stats["syncs_per_token"] <= 1.0 / 8


def test_scheduler_host_syncs_are_goomcheck_guarded():
    """The host-sync invariant as a goomcheck rule (GC206): every
    device->host pull in the real scheduler and steps modules sits inside
    the ``_TokenFlight`` transfer buffer, so sync cost scales with
    flushes, not tokens.  Companion to the GC204 clock-guard test."""
    from repro.analysis import repo_root, run_source

    src_dir = repo_root() / "src" / "repro"
    for rel in ("serve/scheduler.py", "serve/steps.py"):
        hits = [f for f in run_source((src_dir / rel).read_text(), rel)
                if f.rule == "GC206"]
        assert hits == [], [str(h) for h in hits]
    # and the rule actually bites on a regression:
    bad = ("import numpy as np\n"
           "\n"
           "def flush(pending):\n"
           "    return np.asarray(pending)\n")
    assert [f.rule for f in run_source(bad, "serve/steps.py")] == ["GC206"]


# ---------------------------------------------------------------------------
# instrumentation: admission counters, the per-request log's clock reads,
# and the serving path's spans and program names in a profiler capture
# ---------------------------------------------------------------------------
PREFILL_CHUNK = 16


@pytest.fixture(scope="module")
def prefill_engine():
    _, model, params = _model("olmo-1b")
    return Engine(model, params, max_slots=1, page_len=64,
                  chunk=PREFILL_CHUNK)


def _admission_dispatches(p, c, hit=0):
    """Admission of a p-token prompt whose first ``hit`` tokens come from
    the prefix cache: full chunks, then single-token steps, up to the
    prompt's final piece (a whole chunk when c divides p, else its last
    token), which one fused finisher computes."""
    head = p - (1 if p % c else c) - hit
    return {"admissions": 1, "prompt_tokens": p - hit,
            "chunk_calls": head // c, "tail_calls": head % c,
            "finisher_calls": 1}


def _delta(after, before):
    return {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("p", [1, 7, PREFILL_CHUNK - 1, PREFILL_CHUNK,
                               PREFILL_CHUNK + 1, 2 * PREFILL_CHUNK + 3])
def test_prefill_stats_count_admission_dispatches(prefill_engine, p):
    eng = prefill_engine
    prompt = list(map(int, _prompt(eng.model.cfg, p, seed=300 + p)))
    before = eng.prefill_stats()
    eng.run([Request(uid=f"p{p}", prompt=prompt, max_new_tokens=2)])
    assert _delta(eng.prefill_stats(), before) == \
        _admission_dispatches(p, PREFILL_CHUNK)


def test_prefill_stats_count_a_prefix_hit_apart(prefill_engine):
    """A repeated prompt resumes past its cached whole chunks: their
    tokens count as saved (``prefix_stats``), not as prefill work."""
    eng, c = prefill_engine, PREFILL_CHUNK
    p = 2 * c + 3
    prompt = list(map(int, _prompt(eng.model.cfg, p, seed=333)))
    eng.run([Request(uid="first", prompt=prompt, max_new_tokens=2)])
    before, saved = eng.prefill_stats(), eng.prefix_stats()
    eng.run([Request(uid="again", prompt=prompt, max_new_tokens=2)])
    hit = eng.prefix_stats()["prefill_tokens_saved"] - \
        saved["prefill_tokens_saved"]
    assert hit == 2 * c
    assert _delta(eng.prefill_stats(), before) == \
        _admission_dispatches(p, c, hit)


def test_request_log_reads_the_clock_per_admission_not_per_token(
        monkeypatch):
    """With a ``ServeMetrics`` attached the engine stamps two events per
    admission (start, first token); the decode loop adds no clock read,
    however many tokens and dispatches it runs."""
    from repro.serve import ServeMetrics
    from repro.serve import metrics as metrics_mod

    cfg, model, params = _model("olmo-1b")
    reads = {"n": 0}
    real_time = metrics_mod.time

    class _Counting:
        @staticmethod
        def monotonic():
            reads["n"] += 1
            return real_time.monotonic()

    def run(n_tokens):
        eng = Engine(model, params, max_slots=2, page_len=64, chunk=4)
        eng.metrics = ServeMetrics()
        reads["n"] = 0
        monkeypatch.setattr(metrics_mod, "time", _Counting)
        eng.run([Request(uid=i, prompt=[5 + i, 6, 7], max_new_tokens=n_tokens,
                         stream=True) for i in range(3)])
        monkeypatch.setattr(metrics_mod, "time", real_time)
        log = eng.metrics.request_log()
        assert [e["uid"] for e in log] == [0, 1, 2]
        assert all(e["admit_start"] <= e["admitted"] for e in log)
        return reads["n"], eng.decode_stats()["dispatches"]

    short, long_ = run(4), run(30)
    assert long_[1] > short[1]        # more dispatches and tokens ...
    assert short[0] == long_[0] == 2 * 3  # ... same reads: 2 per admission


def test_profiler_capture_names_serve_spans_and_programs(tmp_path):
    """A CPU profiler capture of a small engine run: the serving spans are
    on the host plane, with the request id on ``serve.admit``, and every
    jitted step of the serving path runs under its program name."""
    import glob

    from jax.profiler import ProfileData

    cfg, model, params = _model("olmo-1b")
    eng = Engine(model, params, max_slots=2, page_len=64, chunk=4,
                 stream_callback=lambda uid, toks, reason: None)
    long_prompt = list(map(int, _prompt(cfg, 11, seed=400)))
    reqs = [Request(uid="a", prompt=long_prompt, max_new_tokens=12,
                    stream=True),
            Request(uid="b", prompt=[3, 1, 4, 1], max_new_tokens=3),
            Request(uid="c", prompt=[2, 7, 1], max_new_tokens=2),
            Request(uid="d", prompt=long_prompt, max_new_tokens=2)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    names, admitted = set(), set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "serve.admit":
                    admitted.add(dict(ev.stats)["uid"])
    assert {"serve.admit", "serve.prefill.chunk", "serve.prefill.tail",
            "serve.admit.finish", "serve.first_token_sync", "serve.decode",
            "serve.flush"} <= names
    assert admitted == {"a", "b", "c", "d"}
    programs = {"decode_k1", "decode_k8", "prefill_chunk", "prefill_tail",
                "admit_chunk", "admit_tail", "fresh_cache", "snapshot_carry",
                "gather_prefix", "clear_slot"}
    assert {f"PjitFunction({p})" for p in programs} <= names
