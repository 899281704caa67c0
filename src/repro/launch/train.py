"""Training launcher: mesh setup, sharded state, fault-tolerant loop.

Runs end-to-end on whatever devices exist (CPU smoke → full pod).  The
production launch is the same file with ``--mesh production``:

  PYTHONPATH=src python -m repro.launch.train --arch goom-rnn-124m \\
      --task copy --steps 200 --ckpt-dir /tmp/ckpt

Fault tolerance contract (see train/checkpoint.py):
  * checkpoints every --ckpt-every steps, atomically, async;
  * on start, auto-resumes from the latest COMPLETE checkpoint, including
    the data-iterator cursor (no replayed/skipped batches);
  * SIGTERM (preemption) triggers a final synchronous checkpoint;
  * restarting with a different device count reshards the same checkpoint
    (elastic scaling: the index stores global logical shapes).

Straggler mitigation at scale: each host logs step wall-times; hosts whose
step time exceeds the fleet median by --straggler-factor are reported for
the scheduler to replace (with SPMD, one slow host gates the ring — the
mitigation is detection + replacement + restart-from-checkpoint, which this
loop's checkpoint/resume machinery makes cheap)."""

from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache
from repro.configs import SHAPES, get_config
from repro.core import engine
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.common import unzip
from repro.models.model import DecoderLM
from repro.sharding.rules import make_rules, param_shardings, use_rules
from repro.train.checkpoint import CheckpointManager
from repro.train.data import DataConfig, SyntheticStream
from repro.train.optimizer import AdamW, cosine_schedule
from repro.train.train_loop import (init_train_state, make_train_step,
                                    state_shardings)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="goom-rnn-124m")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--task", default="markov", choices=["markov", "copy"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default="host", choices=["host", "production",
                                                       "production-multipod"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "pallas", "reference",
                             "pallas_tpu", "pallas_gpu", "pallas_interpret",
                             "pallas_gpu_interpret", "xla_reference"],
                    help="scan-engine backend for all GOOM recurrences "
                         "(repro.core.engine; auto = Pallas kernels on "
                         "TPU/GPU, XLA elsewhere; concrete names force a "
                         "path)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep per-op kernel tilings for the resolved "
                         "backend before training and persist winners to "
                         "the autotune cache (consumed automatically by "
                         "every engine call; see docs/engine.md)")
    ap.add_argument("--seq-shards", type=int, default=1,
                    help="sequence-shard GOOM scans over the 'model' mesh "
                         "axis (maps the scan_seq logical axis there; the "
                         "host mesh is reshaped to (ndev/N, N)); 1 = off")
    ap.add_argument("--devices", type=int, default=None,
                    help="host mesh: use the first N devices (default: all)")
    ap.add_argument("--grad-compression", default=None, choices=[None, "int8"])
    ap.add_argument("--straggler-factor", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    if args.mesh == "host":
        mesh = make_host_mesh(seq_shards=args.seq_shards,
                              n_devices=args.devices)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh.endswith("multipod"))
        if args.seq_shards > 1 and mesh.shape["model"] != args.seq_shards:
            raise ValueError(
                f"--seq-shards {args.seq_shards} must equal the production "
                f"mesh 'model' axis ({mesh.shape['model']})")
    # scan_seq -> "model" turns on sequence-sharded GOOM scans inside the
    # train step (the engine reads the active rules; see core/engine.py).
    rules = make_rules(
        mesh,
        overrides={"scan_seq": "model"} if args.seq_shards > 1 else None,
    )

    cfg = get_config(args.arch, smoke=args.smoke)
    model = DecoderLM(cfg)
    opt = AdamW(cosine_schedule(args.lr, args.warmup, args.steps))
    step_fn = make_train_step(model, opt, microbatches=args.microbatches,
                              grad_compression=args.grad_compression)

    key = jax.random.PRNGKey(args.seed)
    data_cfg = DataConfig(
        task=args.task, vocab=cfg.vocab, seq_len=args.seq_len,
        global_batch=args.batch, seed=args.seed,
        process_index=jax.process_index(), process_count=jax.process_count(),
    )
    stream = SyntheticStream(data_cfg)

    # shardings
    params_abs, axes = model.init_shapes(key)
    p_shard = param_shardings(rules, params_abs, axes)
    state_abs = jax.eval_shape(lambda k: init_train_state(model, opt, k), key)
    s_shard = state_shardings(rules, state_abs, p_shard)
    batch_sharding = rules.sharding((args.batch, args.seq_len), ["batch", None])

    if args.autotune:
        # Tune on the training shapes (time = seq len; the lmme/matrix dims
        # track the model's head/state sizes only loosely — the cache is
        # bucketed, so close-enough hints land on the same winners).
        with engine.use_backend(args.backend):
            engine.autotune(
                shapes={"diagonal_scan": (args.seq_len, cfg.d_model),
                        "matrix_scan": (args.seq_len, 16, 16),
                        "cumulative_lmme": (args.seq_len, 16),
                        "lmme": (args.seq_len, cfg.d_model, cfg.d_model)},
                verbose=True)

    with mesh, use_rules(rules), engine.use_backend(args.backend):
        jit_step = jax.jit(step_fn, in_shardings=(s_shard, None),
                           out_shardings=(s_shard, NamedSharding(mesh, P())),
                           donate_argnums=(0,))
        init_fn = jax.jit(
            lambda k: init_train_state(model, opt, k), out_shardings=s_shard
        )

        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start_step = 0
        state = None
        if mgr is not None:
            restored = mgr.restore_latest(state_abs, s_shard)
            if restored is not None:
                start_step, state, extra = restored
                stream.load_state_dict(extra.get("data", {"step": start_step}))
                print(f"resumed from checkpoint step {start_step}")
        if state is None:
            state = init_fn(key)

        # preemption: checkpoint synchronously on SIGTERM, then exit
        preempted = {"flag": False}

        def on_sigterm(sig, frame):
            preempted["flag"] = True

        signal.signal(signal.SIGTERM, on_sigterm)

        def put(batch):
            return {
                k: jax.device_put(v, batch_sharding) for k, v in batch.items()
            }

        times = []
        t_start = time.time()
        for step in range(start_step, args.steps):
            batch = put(stream.generate(step))
            t0 = time.time()
            state, metrics = jit_step(state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                m = jax.device_get(metrics)
                dt = time.time() - t0
                print(f"step {step:5d}  loss {float(m['loss']):.4f}  "
                      f"ce {float(m['ce_loss']):.4f}  "
                      f"gnorm {float(m['grad_norm']):.3f}  {dt*1e3:.0f} ms")
            times.append(time.time() - t0)
            # straggler detection (per-host; at scale the controller compares)
            if len(times) > 20:
                med = float(np.median(times[-20:]))
                if times[-1] > args.straggler_factor * med:
                    print(f"[straggler-watch] step {step} took "
                          f"{times[-1]:.2f}s vs median {med:.2f}s")
            if mgr is not None and (
                (step + 1) % args.ckpt_every == 0 or preempted["flag"]
            ):
                stream_state = stream.state_dict()
                stream_state["step"] = step + 1
                mgr.save(step + 1, state, extra={"data": stream_state})
                if preempted["flag"]:
                    mgr.wait()
                    print(f"preempted: checkpointed at step {step + 1}")
                    sys.exit(0)

        if mgr is not None:
            mgr.save(args.steps, state, extra={"data": stream.state_dict()})
            mgr.wait()
        total = time.time() - t_start
        print(f"done: {args.steps - start_step} steps in {total:.1f}s")
        return state


if __name__ == "__main__":
    main()
