"""Work counts of the per-layer metrics against hand-computed values, and
their readers on a made-up trace summary."""

import benchutil  # noqa: F401  (import paths)
import pytest

from bench import core

RNN = core.load_json("bench/configs/goom-rnn-124m.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return core.metric_reader(name)


def test_model_flops_per_token():
    # per layer: in_proj 768^2, B u and A x 2*48*16*16, C x and D u
    # 2*48*16*32, out_proj 768^2; output head 768*50257; 2 FLOPs a MAC
    per_layer = 589824 + 24576 + 49152 + 589824
    assert reader("mfu.serve").flops_per_token(RNN) == 2 * (24 * per_layer + 38597376)
    assert reader("mfu.serve").flops_per_token(RNN) == 137356800


def test_goom_recurrence_work_per_token():
    flops, nbytes = reader("goom_kernel_roofline.serve").work_per_token(RNN)
    assert flops == 24 * 48 * 2 * 2 * 16 * 16 == 1179648
    assert nbytes == 24 * 768 * 2 * 4 * 2 == 294912


TRACE = {"window_s": 2.0, "busy_s": 1.5, "kernel_s": 0.5, "collective_s": 0.2,
         "collective_exposed_s": 0.05, "chips": 1}


@pytest.mark.parametrize("name,ctx,want", [
    ("device_idle_share.serve", {}, 25.0),
    ("mfu.serve", {"work": {"tokens": 1000}}, 100 * 1000 * 137356800 / (2.0 * 197e12)),
    ("goom_kernel_roofline.serve", {"work": {"tokens": 1000}},
     100 * 1000 * (294912 / 819e9) / 0.5),
    ("tokens_per_dispatch", {"counters": {"decode_steps": 96, "dispatches": 16}}, 6.0),
])
def test_readers(name, ctx, want):
    full = dict({"config": RNN, "peaks": PEAKS, "trace": TRACE}, **ctx)
    assert reader(name).read(full) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", [m["name"] for m in core.load_spec()["per_layer"]])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    empty = {"config": RNN, "peaks": PEAKS, "trace": None, "counters": None, "work": None}
    assert reader(name).read(empty) is None
