"""The reduction of the program's own marks (``bench/spans.py``) on made-up
spans and programs and on a trace recorded on a TPU v5e, the span and
counter metrics' readers, and the traced serving run that reads them
(``bench/serve_spans.py``) at a CPU size."""

import benchutil
import pytest

from bench import core, spans
from bench import trace as bench_trace

SPAN_METRICS = ("frontdoor_p90_ms.serve", "queue_wait_p90_ms.serve",
                "admission_p90_ms.serve", "prompt_tokens_per_dispatch",
                "admission_device_share.serve")

KNOWN_SPANS = {"serve.http", "serve.sse_first", "serve.drain", "serve.step", "serve.admit",
               "serve.prefill.chunk", "serve.prefill.tail", "serve.admit.finish",
               "serve.first_token_sync", "serve.decode", "serve.flush", "serve.flush_stream"}

# engine thread (line 1): a step holding an admission that waits for its
# first token, then a step holding a decode dispatch; a handler thread
# (line 0) with a front-door span over everything
SPANS = [
    ("serve.http", 0, 1000, 0),
    ("serve.step", 100, 500, 1),
    ("serve.admit", 120, 480, 1),
    ("serve.first_token_sync", 300, 470, 1),
    ("serve.step", 600, 700, 1),
    ("serve.decode", 610, 640, 1),
]
OPS = {0: [("%copy.1 = f32[] copy()", 0, 290),
           ("%broadcast.2.clone = f32[] broadcast()", 480, 590),
           ("%fusion.3 = f32[] fusion()", 600, 700),
           ("%copy-start.4 = f32[] copy-start()", 900, 950)]}


def test_gaps_are_labelled_by_the_engine_threads_spans():
    got = spans.labelled_gaps(OPS, SPANS)
    # 700-900: nothing on the engine thread; 290-480: the admission's
    # token wait covers it (the step and the admission around it too);
    # 590-600: between two steps
    assert got == [
        ["no span | after fusion, before copy-start", pytest.approx(200e-9)],
        ["serve.step>serve.admit>serve.first_token_sync | after copy, before broadcast.2.clone",
         pytest.approx(190e-9)],
        ["no span | after broadcast.2.clone, before fusion", pytest.approx(10e-9)],
    ]
    assert spans.labelled_gaps(OPS, SPANS, top=1) == got[:1]


def test_a_span_over_half_a_gap_or_less_does_not_label_it():
    eng = spans.engine_spans(SPANS)
    assert all(s[3] == 1 for s in eng) and len(eng) == 5
    assert spans.label(600, 660, eng) == "serve.step"
    assert spans.label(600, 680, eng) == "serve.step"
    assert spans.label(580, 620, eng) == "no span"       # step covers exactly half
    assert spans.engine_spans(SPANS[:1]) == []        # the front door's alone
    assert spans.engine_spans(SPANS[2:4]) == SPANS[2:4]   # a step open before the trace


def test_programs_sum_runs_by_name_over_chips():
    runs = {0: [("jit_decode_k8(123)", 0, 40), ("jit_prefill_tail(9)", 40, 50),
                ("jit_decode_k8(123)", 60, 100)],
            1: [("jit_decode_k8(77)", 0, 60), ("jit_fresh_cache(5)", 60, 80)]}
    assert spans.programs(runs) == [["decode_k8", pytest.approx(70e-9)],
                                    ["fresh_cache", pytest.approx(10e-9)],
                                    ["prefill_tail", pytest.approx(5e-9)]]
    assert spans.program_name("jit__lambda(14611981295196840842)") == "_lambda"
    assert spans.program_name("decode_k1") == "decode_k1"


FIXTURE = str(benchutil.ROOT / "tests" / "bench" / "fixtures" / "serve_admit.xplane.pb")


def test_recorded_serve_trace():
    """A 4.8-ms cut of a trace recorded on a TPU v5e in the serving cell:
    the end of one admission (its last prefill tail, eager slicing, the
    fused finisher, the first-token sync) and the next decode dispatch.
    The cut keeps every event that overlaps it; to fit the repository each
    op's HLO text is cut to 64 characters and its metadata stats dropped."""
    host = spans.host_spans(FIXTURE)
    assert [s[0] for s in host] == ["serve.first_token_sync", "serve.sse_first",
                                    "serve.decode"]
    # the front door's span is on the event loop's thread, not the engine's
    assert [s[0] for s in spans.engine_spans(host)] == ["serve.first_token_sync",
                                                        "serve.decode"]
    runs = spans.device_programs(FIXTURE)
    assert list(runs) == [0] and len(runs[0]) == 13
    got = dict(spans.programs(runs))
    assert got["decode_k8"] == pytest.approx(359.380806e-3, rel=1e-9)
    assert got["admit_tail"] == pytest.approx(1.494905e-3, rel=1e-9)
    assert got["prefill_tail"] == pytest.approx(1.469283e-3, rel=1e-9)
    assert set(got) == {"decode_k8", "admit_tail", "prefill_tail", "convert_element_type",
                        "dynamic_slice", "select_n", "less", "add"}
    # the device waits 2.8 ms between the finisher and the decode while
    # the host, its token read, gets to the next dispatch
    ops = bench_trace.device_ops(FIXTURE)
    gaps = spans.labelled_gaps(ops, host, top=40)
    assert gaps[0] == ["no span | after copy-done, before copy-start",
                       pytest.approx(2.799251e-3, rel=1e-6)]
    assert [g[0] for g in gaps if not g[0].startswith("no span")] == [
        "serve.first_token_sync | after copy-start, before copy-done"]


def _entry(t):
    """A logged request whose phases are 2 ms front door (1 + 1), 40 ms
    queue wait and ``t`` ms admission."""
    return {"received": 0.0, "submitted": 0.001, "admit_start": 0.041,
            "admitted": 0.041 + t / 1e3, "first_taken": 0.042 + t / 1e3,
            "first_frame": 0.043 + t / 1e3}


REQUESTS = [_entry(t) for t in range(0, 110, 10)]     # admission 0 .. 100 ms


@pytest.mark.parametrize("name,ctx,want", [
    ("frontdoor_p90_ms.serve", {"requests": REQUESTS}, 2.0),
    ("queue_wait_p90_ms.serve", {"requests": REQUESTS}, 40.0),
    ("admission_p90_ms.serve", {"requests": REQUESTS}, 90.0),
    ("admission_p90_ms.serve", {"requests": REQUESTS + [{"submitted": 5.0}]}, 90.0),
    ("prompt_tokens_per_dispatch",
     {"prefill": {"admissions": 2, "prompt_tokens": 151, "chunk_calls": 1,
                  "tail_calls": 98, "finisher_calls": 2}}, 151 / 101),
    ("admission_device_share.serve",
     {"trace": {"busy_s": 2.0, "programs": [["decode_k8", 1.5], ["prefill_tail", 0.2],
                                            ["admit_tail", 0.05], ["fresh_cache", 0.02],
                                            ["snapshot_carry", 0.01], ["gather_prefix", 0.01],
                                            ["clear_slot", 0.1], ["decode_k1", 0.1]]}},
     100 * 0.29 / 2.0),
])
def test_span_readers(name, ctx, want):
    assert core.metric_reader(name).read(ctx) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_span_reader_with_nothing_to_read_returns_nothing(name):
    reader = core.metric_reader(name)
    assert reader.read({"trace": None, "counters": None, "work": None}) is None
    # what bench/run.py's traced run passes today: a summary without
    # programs, and neither the request log nor the prefill counters
    assert reader.read({"trace": {"busy_s": 1.0, "window_s": 1.0},
                        "counters": {"decode_steps": 8, "dispatches": 1}}) is None
    assert reader.read({"requests": [], "prefill": {},
                        "trace": {"busy_s": 1.0, "programs": []}}) is None


def test_phases_of_a_request():
    e = _entry(30)
    assert spans.phase_ms(e, "queue_wait") == pytest.approx(40.0)
    assert spans.phase_ms(e, "admission") == pytest.approx(30.0)
    assert spans.phase_ms(e, "frontdoor") == pytest.approx(2.0)
    assert spans.phase_ms({"submitted": 1.0}, "admission") is None
    with pytest.raises(ValueError):
        spans.phase_ms(e, "decode")


def test_serve_spans_run_accounts_for_each_request():
    """The traced run at a CPU size: every request of the window logged in
    full, the admission counters read, the account's parts finite, and
    the device-trace reader silent (a CPU has no device plane)."""
    from bench import serve_spans

    out = serve_spans.run("rnn124m-longgen", 2 ** 32 + 21, 2.0, require_chip=False,
                          overrides=benchutil.SERVE_SMOKE)
    acc = out["ttft_account"]
    assert acc["requests"] == 4
    m = out["metrics"]
    for name in SPAN_METRICS[:3]:
        assert 0 <= m[name] < acc["client_ttft"]["p90"]
    assert 1.0 <= m["prompt_tokens_per_dispatch"] <= benchutil.SERVE_SMOKE["traffic"]["engine"]["chunk"]
    assert m["admission_device_share.serve"] is None
    assert out["prefill"]["admissions"] == out["prefill"]["finisher_calls"] == 4
    # the engine thread polls its commands all through the traced span
    names = set(out["breakdown"]["host_spans"])
    assert "serve.drain" in names and names <= KNOWN_SPANS
    for part in ("late", "frontdoor", "queue_wait", "admission", "remainder"):
        assert abs(acc[part]["p50"]) < acc["client_ttft"]["p90"]
