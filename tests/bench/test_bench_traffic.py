"""The traffic generator: seeded, the same work for every seed, clipped."""

import benchutil  # noqa: F401  (import paths)
import pytest

from bench import core
from bench.traffic import schedule

MIX = core.traffic_file("longgen")


def test_same_seed_same_schedule():
    assert schedule(MIX, 2 ** 33 + 5, 51.0, 50257) == schedule(MIX, 2 ** 33 + 5, 51.0, 50257)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2 ** 31 + 11)])
def test_every_seed_sends_the_same_work_at_the_same_times(seeds):
    a, b = (schedule(MIX, s, 51.0, 50257) for s in seeds)
    assert len(a) == len(b) == round(MIX["rate_per_s"] * 51.0)
    assert [(r["t"], len(r["prompt"]), r["max_tokens"]) for r in a] == [
        (r["t"], len(r["prompt"]), r["max_tokens"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_lengths_stay_within_their_clips_and_arrivals_in_the_window():
    s = schedule(MIX, 3, 51.0, 50257)
    p, o = MIX["prompt"], MIX["output"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in s)
    assert all(o["min"] <= r["max_tokens"] <= o["max"] for r in s)
    assert all(0 <= tok < 50257 for r in s for tok in r["prompt"])
    t = [r["t"] for r in s]
    assert t == sorted(t) and 0 <= t[0] and t[-1] <= 51.0
    engine = MIX["engine"]
    assert all(len(r["prompt"]) + r["max_tokens"] <= engine["page_len"] for r in s)
