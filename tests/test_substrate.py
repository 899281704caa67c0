"""Training substrate: optimizer, data pipeline, checkpointing, sharding
rules, and the serve driver."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sharding.rules import make_rules
from repro.train.checkpoint import CheckpointManager
from repro.train.data import DataConfig, SyntheticStream
from repro.train.optimizer import (
    AdamW, Lion, clip_by_global_norm, compress_int8, cosine_schedule,
    decompress_int8,
)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_matches_manual_step():
    opt = AdamW(lambda s: jnp.asarray(0.1), b1=0.9, b2=0.99, eps=1e-8,
                weight_decay=0.0)
    params = {"w": jnp.asarray([1.0, -2.0])}
    grads = {"w": jnp.asarray([0.5, 0.5])}
    state = opt.init(params)
    new, state = opt.update(grads, state, params)
    # step 1: mhat = g, vhat = g², delta = g/(|g|+eps) = sign(g)
    np.testing.assert_allclose(new["w"], params["w"] - 0.1 * jnp.sign(grads["w"]),
                               rtol=1e-5)


def test_adamw_weight_decay_mask():
    opt = AdamW(lambda s: jnp.asarray(0.0), weight_decay=1.0)  # lr=0: no move
    params = {"dense": {"w": jnp.ones(2)}, "norm": {"scale": jnp.ones(2)}}
    mask = opt._decay_mask(params)
    assert mask["dense"]["w"] is True
    assert mask["norm"]["scale"] is False


def test_lion_step_is_sign_update():
    opt = Lion(lambda s: jnp.asarray(0.1), weight_decay=0.0)
    params = {"w": jnp.asarray([1.0, -1.0])}
    grads = {"w": jnp.asarray([0.3, -0.7])}
    state = opt.init(params)
    new, _ = opt.update(grads, state, params)
    np.testing.assert_allclose(new["w"], params["w"] - 0.1 * jnp.sign(grads["w"]))


def test_clip_by_global_norm():
    tree = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}  # norm 5
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert abs(float(norm) - 5.0) < 1e-5
    total = jnp.sqrt(clipped["a"] ** 2 + clipped["b"] ** 2)
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


def test_cosine_schedule_shape():
    sched = cosine_schedule(1.0, 10, 100, final_fraction=0.1)
    assert float(sched(0)) == 0.0
    assert abs(float(sched(10)) - 1.0) < 1e-5
    assert float(sched(100)) < 0.11
    assert float(sched(55)) < float(sched(20))


def test_int8_compression_roundtrip_error():
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (128,))}
    rt = decompress_int8(compress_int8(tree))
    amax = float(jnp.max(jnp.abs(tree["w"])))
    assert float(jnp.max(jnp.abs(rt["w"] - tree["w"]))) <= amax / 127.0 + 1e-6


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_stream_is_restart_stable():
    cfg = DataConfig(task="markov", vocab=32, seq_len=16, global_batch=4, seed=7)
    s1 = SyntheticStream(cfg)
    batches = [next(s1) for _ in range(3)]
    s2 = SyntheticStream(cfg)
    s2.load_state_dict({"step": 2})
    b2 = next(s2)
    np.testing.assert_array_equal(b2["tokens"], batches[2]["tokens"])


def test_stream_host_sharding_disjoint():
    k = dict(task="markov", vocab=32, seq_len=16, global_batch=4, seed=7)
    h0 = SyntheticStream(DataConfig(**k, process_index=0, process_count=2))
    h1 = SyntheticStream(DataConfig(**k, process_index=1, process_count=2))
    b0, b1 = next(h0), next(h1)
    assert b0["tokens"].shape == (2, 16)
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_copy_task_labels():
    cfg = DataConfig(task="copy", vocab=32, seq_len=64, global_batch=2,
                     copy_len=8)
    b = next(SyntheticStream(cfg))
    toks, labels = b["tokens"], b["labels"]
    # recall span: labels repeat the prefix
    np.testing.assert_array_equal(labels[:, -9:-1], toks[:, :8])
    assert (labels[:, :8] == -1).all()


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    mgr.save(5, tree, extra={"data": {"step": 5}})
    assert mgr.latest_step() == 5
    restored, extra = mgr.restore(5, jax.eval_shape(lambda: tree))
    np.testing.assert_array_equal(restored["a"], tree["a"])
    assert extra["data"]["step"] == 5


def test_checkpoint_gc_and_atomicity(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = {"x": jnp.zeros(3)}
    for s in (1, 2, 3):
        mgr.save(s, tree)
    assert mgr.all_steps() == [2, 3]
    # a stale .tmp dir (crashed save) must be ignored
    os.makedirs(tmp_path / "step_99.tmp")
    assert mgr.latest_step() == 3


def test_checkpoint_restore_latest_resharding(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = {"w": jnp.arange(16, dtype=jnp.float32)}
    mgr.save(1, tree)
    out = mgr.restore_latest(jax.eval_shape(lambda: tree))
    assert out is not None
    step, restored, _ = out
    assert step == 1
    np.testing.assert_array_equal(restored["w"], tree["w"])


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
def _mesh22():
    # AbstractMesh: axis sizes without needing real devices (1-CPU CI)
    return jax.sharding.AbstractMesh((2, 2), ("data", "model"))


def test_rules_divisibility_drop():
    rules = make_rules(_mesh22())
    # kv_heads=3 not divisible by model=2: dropped
    spec = rules.spec((8, 3, 16), ["embed", "kv_heads", None])
    assert spec[0] == "data"
    assert len(spec) < 2 or spec[1] is None


def test_rules_no_axis_reuse():
    rules = make_rules(_mesh22())
    # both dims map to "model": only the first keeps it
    spec = rules.spec((4, 4), ["mlp", "vocab"])
    entries = list(spec) + [None] * (2 - len(spec))
    assert entries[0] == "model"
    assert entries[1] is None


def test_rules_multi_axis_batch():
    mesh = jax.sharding.AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    rules = make_rules(mesh)
    spec = rules.spec((8, 128), ["batch", None])
    assert spec[0] == ("pod", "data")


# ---------------------------------------------------------------------------
# serve driver
# ---------------------------------------------------------------------------
def test_generate_greedy_matches_stepwise():
    from repro.configs import get_config
    from repro.models.common import unzip
    from repro.models.model import DecoderLM
    from repro.serve.steps import generate

    cfg = get_config("olmo-1b", smoke=True)
    model = DecoderLM(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    toks = generate(model, params, prompt, n_tokens=4, max_len=16)
    assert toks.shape == (2, 4)
    # greedy step 1 must equal argmax of the full forward
    logits, _, _ = model.apply(params, prompt)
    np.testing.assert_array_equal(
        np.asarray(toks[:, 0]), np.asarray(jnp.argmax(logits[:, -1], -1))
    )
