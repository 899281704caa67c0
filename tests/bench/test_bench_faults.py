"""Whole runs at a CPU size with the timed path broken underneath: each
fault must come out as ``correct`` false, and the lower-precision control
must read above the sound run."""

import benchutil

from repro.serve import scheduler

SERVE = dict(benchutil.SERVE_SMOKE)


def serve(seed, overrides=SERVE):
    return benchutil.run.run_cell("rnn124m-longgen", seed, 2.0, 0, require_chip=False,
                                  overrides=overrides)


def test_serve_sound_run_is_correct_and_the_controls_read_above_it():
    line, checks = serve(11, benchutil.with_traffic(SERVE, control=True))
    assert line["correct"], checks.items
    sound = checks.items["logit_gap"]["value"]
    assert line["control"]["logit_gap.operands"] > 3 * sound
    assert line["control"]["logit_gap.recurrence"] > sound
    assert set(line["control"]) == {f"{n}.{c}" for n in ("logit_gap", "gap_share")
                                    for c in ("operands", "recurrence")}


def test_serve_token_altered_where_produced(monkeypatch):
    emit = scheduler.Engine._emit

    def altered(self, request, toks, reason):
        toks = [(t + 1) % 256 for t in toks]
        return emit(self, request, toks, reason)

    monkeypatch.setattr(scheduler.Engine, "_emit", altered)
    line, checks = serve(12)
    assert not line["correct"]
    assert checks.items["logit_gap"]["value"] > checks.items["logit_gap"]["limit"]


def test_serve_decode_returns_its_state_unchanged(monkeypatch):
    make = scheduler.make_decode_multi

    def frozen(*args, **kwargs):
        step = make(*args, **kwargs)

        def run(params, tokens, caches, pos, term):
            block, tokens, _, pos, term = step(params, tokens, caches, pos, term)
            return block, tokens, caches, pos, term

        return run

    monkeypatch.setattr(scheduler, "make_decode_multi", frozen)
    line, checks = serve(13)
    assert not line["correct"]
    assert checks.items["logit_gap"]["value"] > checks.items["logit_gap"]["limit"]
