"""The pjit train step: forward/backward + optimizer, with microbatching.

The returned step function is a pure function of (state, batch) suitable for
``jax.jit`` with in/out shardings from the sharding rules.  Distribution is
GSPMD: batch arrives sharded over ("pod","data"); parameters arrive
FSDP/TP-sharded; XLA inserts the all-gathers/reduce-scatters.

Microbatching (gradient accumulation) runs a ``lax.scan`` over microbatches,
accumulating f32 gradients — needed when the per-device batch doesn't fit
(e.g. long-context training).  Compute/comm overlap is XLA's latency-hiding
scheduler; we keep one dot product's worth of work between collectives by
scanning layers (see models/blocks.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.model import DecoderLM
from .optimizer import clip_by_global_norm, global_norm


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array
    rng: jax.Array


def init_train_state(model: DecoderLM, optimizer, key: jax.Array) -> TrainState:
    from ..models.common import unzip

    params, _ = unzip(model.init(key))
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
        rng=key,
    )


def state_shardings(rules, state_abs: TrainState, p_shard) -> TrainState:
    """Shardings of a train state: optimizer moments follow the params,
    everything else is replicated."""
    rep = NamedSharding(rules.mesh, P())
    opt_shard = {k: p_shard if k in ("mu", "nu") else rep
                 for k in state_abs.opt_state}
    return TrainState(params=p_shard, opt_state=opt_shard, step=rep, rng=rep)


def make_train_step(
    model: DecoderLM,
    optimizer,
    *,
    max_grad_norm: float = 1.0,
    microbatches: int = 1,
    grad_compression: Optional[str] = None,
    cast_params_bf16: bool = False,
    grad_shardings=None,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict]]:
    """Build the train step.  ``batch`` carries tokens/labels (+ frontend
    stubs); all arrays have the global batch leading dim.

    Perf options (see EXPERIMENTS.md §Perf):
      cast_params_bf16 — cast f32 master params to bf16 *before* the layer
        scan, so FSDP all-gathers move bf16 (half the ring bytes) and the
        backward's weight-gradient reductions happen in bf16.
      grad_shardings — tree of NamedShardings (the params' shardings):
        constrains per-microbatch gradients so GSPMD emits reduce-scatters
        into the sharded accumulator instead of full all-reduces.
    """

    def cast(params):
        if not cast_params_bf16:
            return params
        return jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if p.dtype == jnp.float32 else p,
            params,
        )

    def constrain_grads(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                            grad_shardings)

    def loss_fn(params, batch):
        kw = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
        return model.loss(cast(params), batch["tokens"], batch["labels"], **kw)

    def compute_grads(params, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            return constrain_grads(grads), metrics

        def micro(b):
            def r(k, x):
                if k == "mrope_positions":  # (3, B, S): batch is dim 1
                    return x.reshape(
                        (x.shape[0], microbatches, -1) + x.shape[2:]
                    ).swapaxes(0, 1)
                return x.reshape((microbatches, -1) + x.shape[1:])

            return {k: r(k, v) for k, v in b.items()}

        mb = micro(batch)

        def body(acc, b):
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, b
            )
            grads = constrain_grads(grads)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / microbatches, acc, grads
            )
            # pin the scan carry: without this the accumulator's sharding
            # resolves to replicated and every per-layer dW becomes a full
            # f32 all-reduce instead of a reduce-scatter into the shard
            return constrain_grads(acc), metrics

        zero = constrain_grads(
            jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        )
        grads, metrics_stack = jax.lax.scan(body, zero, mb)
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics_stack)
        return grads, metrics

    def train_step(state: TrainState, batch):
        grads, metrics = compute_grads(state.params, batch)

        if grad_compression == "int8":
            # quantize -> (implicit all-reduce happens on the quantized
            # values' dequantized form) -> dequantize.  Under GSPMD the
            # reduction is fused into the backward; this bounds the bytes
            # any cross-pod reduce moves.
            from .optimizer import compress_int8, decompress_int8

            grads = decompress_int8(compress_int8(grads))
            grads = jax.tree.map(
                lambda g, p: g.astype(p.dtype), grads, state.params
            )

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        params, opt_state = optimizer.update(grads, state.opt_state, state.params)
        metrics = dict(metrics, grad_norm=gnorm,
                       lr=optimizer.schedule(state.step + 1))
        new_state = TrainState(
            params=params,
            opt_state=opt_state,
            step=state.step + 1,
            rng=jax.random.fold_in(state.rng, state.step),
        )
        return new_state, metrics

    return train_step
