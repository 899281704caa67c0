"""The plain references against the program, at a size the CPU holds."""

import benchutil
import jax
import jax.numpy as jnp
import numpy as np

from bench import core
from bench.drivers import serve_stream
from bench.reference import goom_rnn as rnn_ref

SMOKE = dict(core.load_json("bench/configs/goom-rnn-124m.json"),
             **benchutil.SERVE_SMOKE["config"])


def test_goom_rnn_reference_matches_the_program_forward():
    from repro.models.model import DecoderLM

    w = rnn_ref.init_weights(SMOKE, 3)
    model = DecoderLM(serve_stream.lm_config(SMOKE))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 40), 0, SMOKE["vocab"])
    got, _, _ = model.apply(serve_stream.program_params(w), tokens)
    want = rnn_ref.hidden(w, tokens) @ w["head"]
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))
    # the program's projections run in bf16 (a rounding is 3.9e-3 relative)
    assert err < 5e-2, err


def test_gaps_of_the_references_own_greedy_tokens_are_zero():
    w = rnn_ref.init_weights(SMOKE, 4)
    prompt = [5, 17, 200, 3]
    seq = list(prompt)
    for _ in range(6):
        h = rnn_ref.hidden(w, jnp.asarray([seq]))
        seq.append(int(jnp.argmax(h[0, -1] @ w["head"])))
    (g,) = rnn_ref.position_gaps(w, [(prompt, seq[len(prompt):])], rnn_ref.FLOAT32)
    assert g.shape == (1, 6) and g.max() == 0.0
    bad = seq[len(prompt):]
    bad[2] = (bad[2] + 1) % SMOKE["vocab"]
    (g,) = rnn_ref.position_gaps(w, [(prompt, bad)], rnn_ref.FLOAT32)
    assert g[0, :2].max() == 0.0 and g[0, 2] > 0.05


def test_the_stated_precision_and_its_controls():
    stated = rnn_ref.stated(SMOKE)
    assert stated == rnn_ref.Precision(jnp.bfloat16, jnp.bfloat16, jnp.float32)
    ctl = rnn_ref.controls(stated)
    assert ctl["operands"] == stated._replace(operands=jnp.dtype(jnp.float8_e4m3fn))
    assert ctl["recurrence"] == stated._replace(recurrence=jnp.dtype(jnp.bfloat16))


def test_the_stated_precision_rounds_where_float32_does_not():
    w = rnn_ref.init_weights(SMOKE, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, SMOKE["vocab"])
    h32 = rnn_ref.hidden(w, tokens, rnn_ref.FLOAT32)
    h16 = rnn_ref.hidden(w, tokens, rnn_ref.stated(SMOKE))
    assert bool(jnp.all(h16 == h16.astype(jnp.bfloat16).astype(jnp.float32)))
    err = float(jnp.max(jnp.abs(h16 - h32)) / jnp.max(jnp.abs(h32)))
    assert 1e-4 < err < 5e-2, err


def test_served_numbers_by_hand():
    # two requests; per row the served tokens' gaps, then one control's
    per_row = [np.array([[0.0, 0.3, 0.05], [0.5, 0.0, 0.2]]),
               np.array([[0.02, 0.0, 0.0, 0.15, 0.01], [0.0, 0.0, 0.0, 0.0, 0.0]])]
    got = serve_stream.served_numbers(per_row, early=2, above=0.1)
    assert got["logit_gap"] == [0.3, 0.5]
    assert got["gap_share"] == [2 / 8, 2 / 8]
    assert got["widest"] == [0.3, 0.5]
