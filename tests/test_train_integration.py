"""Training-loop integration: learning happens, resume is exact,
microbatching is equivalent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.common import unzip
from repro.models.model import DecoderLM
from repro.train.data import DataConfig, SyntheticStream
from repro.train.optimizer import AdamW, constant_schedule
from repro.train.train_loop import init_train_state, make_train_step


def _setup(arch="goom-rnn-124m", lr=3e-3):
    cfg = get_config(arch, smoke=True)
    model = DecoderLM(cfg)
    opt = AdamW(constant_schedule(lr))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    stream = SyntheticStream(DataConfig(task="copy", vocab=cfg.vocab,
                                        seq_len=64, global_batch=8))
    return model, opt, state, stream


def test_loss_decreases_on_copy_task():
    model, opt, state, stream = _setup()
    step = jax.jit(make_train_step(model, opt))
    first = last = None
    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in stream.generate(i).items()}
        state, metrics = step(state, batch)
        if i < 3:
            first = float(metrics["ce_loss"]) if first is None else first
        last = float(metrics["ce_loss"])
    assert last < first - 0.2, (first, last)


def test_resume_is_bit_exact(tmp_path):
    from repro.train.checkpoint import CheckpointManager

    model, opt, state, stream = _setup("olmo-1b")
    step = jax.jit(make_train_step(model, opt))

    # path A: 4 straight steps
    sa = state
    for i in range(4):
        batch = {k: jnp.asarray(v) for k, v in stream.generate(i).items()}
        sa, _ = step(sa, batch)

    # path B: 2 steps, checkpoint, restore, 2 steps
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    sb = state
    for i in range(2):
        batch = {k: jnp.asarray(v) for k, v in stream.generate(i).items()}
        sb, _ = step(sb, batch)
    mgr.save(2, sb)
    restored, _ = mgr.restore(2, jax.eval_shape(lambda: sb))
    sb = jax.tree.map(lambda a, b: b.astype(a.dtype), sb, restored)
    for i in range(2, 4):
        batch = {k: jnp.asarray(v) for k, v in stream.generate(i).items()}
        sb, _ = step(sb, batch)

    for pa, pb in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def test_train_launcher_runs_and_resumes(tmp_path, capsys, monkeypatch):
    """The training entry point end to end on the host mesh: steps,
    checkpoint, resume from it."""
    from repro.launch.train import main

    # the launcher then leaves this process's compile cache as it is
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    argv = ["--arch", "goom-rnn-124m", "--smoke", "--task", "copy",
            "--seq-len", "32", "--batch", "4", "--log-every", "1",
            "--ckpt-dir", str(tmp_path)]
    main(argv + ["--steps", "2"])
    main(argv + ["--steps", "3"])
    out = capsys.readouterr().out
    assert "resumed from checkpoint step 2" in out
    losses = [float(line.split()[3]) for line in out.splitlines()
              if line.startswith("step ")]
    assert len(losses) == 3 and np.isfinite(losses).all()


def test_microbatched_grads_match_full_batch():
    """Accumulated microbatch gradients equal the full-batch gradient
    (per-microbatch token counts are equal here, so means compose).
    Compared pre-optimizer: Adam's vhat normalization amplifies benign
    rounding differences into direction flips for near-zero entries."""
    model, opt, state, stream = _setup("olmo-1b")
    batch = {k: jnp.asarray(v) for k, v in stream.generate(0).items()}

    def loss_fn(params, b):
        return model.loss(params, b["tokens"], b["labels"])[0]

    g_full = jax.grad(loss_fn)(state.params, batch)
    mb = jax.tree.map(lambda x: x.reshape((4, -1) + x.shape[1:]), batch)
    g_acc = jax.tree.map(jnp.zeros_like, state.params)
    for i in range(4):
        b_i = jax.tree.map(lambda x: x[i], mb)
        g_i = jax.grad(loss_fn)(state.params, b_i)
        g_acc = jax.tree.map(lambda a, g: a + g / 4.0, g_acc, g_i)

    gn = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                            for x in jax.tree.leaves(g_full))))
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3 * max(gn, 1.0))


def test_int8_grad_compression_still_learns():
    model, opt, state, stream = _setup()
    step = jax.jit(make_train_step(model, opt, grad_compression="int8"))
    first = last = None
    for i in range(20):
        batch = {k: jnp.asarray(v) for k, v in stream.generate(i).items()}
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["ce_loss"])
        last = float(metrics["ce_loss"])
    assert np.isfinite(last) and last < first + 0.1
