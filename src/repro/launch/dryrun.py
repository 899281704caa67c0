import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real jitted step (train_step for train shapes;
prefill/decode serve steps for inference shapes) with explicit in/out
shardings on the production mesh, compiles it, and records:

  * memory_analysis()    — per-device bytes: proves the cell fits HBM;
  * cost_analysis()      — per-device FLOPs/bytes for the roofline;
  * the collective schedule parsed from the compiled HLO.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out f.json]
"""

import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, input_specs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    Roofline,
    collective_bytes_per_device,
    model_flops,
)
from repro.models.common import unzip
from repro.models.model import DecoderLM
from repro.serve.steps import abstract_caches, make_decode_step, make_prefill_step
from repro.sharding.rules import make_rules, use_rules
from repro.train.optimizer import AdamW, cosine_schedule
from repro.train.train_loop import init_train_state, make_train_step, state_shardings


# ---------------------------------------------------------------------------
# sharding of the various trees
# ---------------------------------------------------------------------------
def param_shardings(rules, params_abs, axes):
    return jax.tree.map(
        lambda sds, names: rules.sharding(sds.shape, list(names)),
        params_abs,
        axes,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(e is None or isinstance(e, str) for e in x),
    )


def batch_shardings(rules, batch_abs):
    def spec_for(name, sds):
        if name == "mrope_positions":
            return rules.sharding(sds.shape, [None, "batch", "act_seq"])
        names = ["batch", "act_seq", "act_embed"][: len(sds.shape)]
        return rules.sharding(sds.shape, names)

    return {k: spec_for(k, v) for k, v in batch_abs.items()}


_CACHE_AXES = [
    # (path substring, logical names for trailing dims)
    ("attn.k", ("batch", "cache_seq", "kv_cache_heads", None)),
    ("attn.v", ("batch", "cache_seq", "kv_cache_heads", None)),
    ("index", ("batch",)),  # per-slot (B,) position vector
    ("wkv", ("batch", "act_heads", None, None)),
    ("x_prev", ("batch", None, "act_embed")),
    ("cm_x_prev", ("batch", None, "act_embed")),
    ("conv", ("batch", None, "act_mlp")),
    ("ssm", ("batch", "act_mlp", None)),
    ("x_log", ("batch", "act_heads", None, None)),
    ("x_sign", ("batch", "act_heads", None, None)),
]


def cache_shardings(rules, caches_abs):
    import re as _re

    flat = jax.tree_util.tree_flatten_with_path(caches_abs)
    out = []
    for path, sds in flat[0]:
        # normalize "[0]['b0']['attn']['k']" -> "0.b0.attn.k"
        key = _re.sub(r"['\]]", "", jax.tree_util.keystr(path)).replace("[", ".")
        names = None
        for sub, ax in _CACHE_AXES:
            if sub in key:
                names = list(ax)
                break
        if names is None:
            names = [None] * len(sds.shape)
        # stacked-period leading dim(s)
        while len(names) < len(sds.shape):
            names = [None] + names
        names = names[-len(sds.shape):] if len(sds.shape) else []
        out.append(rules.sharding(sds.shape, names))
    return jax.tree.unflatten(flat[1], out)


# ---------------------------------------------------------------------------
# cell runners
# ---------------------------------------------------------------------------
def lower_cell(arch: str, shape_name: str, mesh, *, verbose=True,
               perf=None, rules_overrides=None):
    """Returns (Roofline, compiled, compile_s).

    ``perf`` (dict) toggles §Perf optimizations:
      banded=True           — exact 2-block banded SWA for windowed layers
      cast_params_bf16=True — bf16 FSDP gathers / grad reductions
      constrain_grads=True  — force reduce-scatter into sharded grad accum
      microbatches=N        — override the per-cell heuristic
      remat=...             — override the remat policy
    """
    import dataclasses as _dc

    perf = dict(perf or {})
    cfg = get_config(arch)
    if perf.get("banded"):
        from repro.configs.base import transform_blocks

        def _banded(blk):
            if blk.attn is not None and blk.attn.window is not None:
                return _dc.replace(
                    blk, attn=_dc.replace(blk.attn, use_banded=True))
            return blk

        cfg = transform_blocks(cfg, _banded)
    if perf.get("seq_parallel"):
        # Megatron-style SP: residual-stream activations shard their seq
        # dim over "model", turning per-block dX all-reduces into
        # reduce-scatter + all-gather pairs (half the ring bytes) and
        # sharding the norms' work.
        rules_overrides = dict(rules_overrides or {}, act_seq="model")
    if perf.get("pure_fsdp"):
        # ZeRO-3 logicalization: batch over BOTH mesh axes (1 row/device at
        # global 256), weights stay 2D-sharded for storage and are gathered
        # at use; no tensor-parallel activation all-reduces at all.
        rules_overrides = dict(
            rules_overrides or {},
            batch=("data", "model"),
            act_heads=None, act_kv_heads=None, act_mlp=None, act_vocab=None,
            act_expert=None,
        )
    if "remat" in perf:
        cfg = _dc.replace(cfg, remat=perf["remat"])
    if "logit_chunk" in perf:
        cfg = _dc.replace(cfg, logit_chunk=perf["logit_chunk"])
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise SkipCell(why)

    overrides = dict(rules_overrides or {})
    # KV cache sharding: heads over "model" when divisible; otherwise the
    # cache sequence dim takes "model" (context-parallel cache) so GQA archs
    # with few KV heads (glm4 kv=2, qwen2-vl kv=4, phi3.5 kv=8) still shard
    # their dominant buffer 256-ways.
    min_kv = min(
        (blk.attn.n_kv_heads for blk in cfg.layer_list if blk.attn is not None),
        default=0,
    )
    model_size = mesh.shape.get("model", 1)
    kv_divisible = min_kv > 0 and min_kv % model_size == 0
    overrides.setdefault("kv_cache_heads", "model" if kv_divisible else None)
    if shape.kind == "long_decode":
        # context parallelism: the cache sequence dim shards over "data"
        overrides.setdefault(
            "cache_seq", "data" if kv_divisible else ("data", "model"))
    elif not kv_divisible:
        overrides.setdefault("cache_seq", "model")
    rules = make_rules(mesh, overrides)

    model = DecoderLM(cfg)
    key = jax.random.PRNGKey(0)
    params_abs, axes = model.init_shapes(key)
    p_shard = param_shardings(rules, params_abs, axes)

    with mesh, use_rules(rules):
        if shape.kind == "train":
            opt = AdamW(cosine_schedule(3e-4, 100, 10_000))
            state_abs = jax.eval_shape(
                lambda k: init_train_state(model, opt, k), key
            )
            s_shard = state_shardings(rules, state_abs, p_shard)
            batch_abs = input_specs(cfg, shape)
            b_shard = batch_shardings(rules, batch_abs)
            step = make_train_step(
                model, opt,
                microbatches=perf.get(
                    "microbatches", _pick_microbatches(cfg, shape, mesh)),
                cast_params_bf16=perf.get("cast_params_bf16", False),
                grad_shardings=p_shard if perf.get("constrain_grads") else None,
            )
            jitted = jax.jit(
                step,
                in_shardings=(s_shard, b_shard),
                out_shardings=(s_shard, NamedSharding(mesh, P())),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_abs, batch_abs)
        elif shape.kind == "prefill":
            batch_abs = input_specs(cfg, shape)
            b_shard = batch_shardings(rules, batch_abs)
            caches_abs = abstract_caches(model, shape.global_batch, shape.seq_len)
            c_shard = cache_shardings(rules, caches_abs)
            step = make_prefill_step(model, fresh_caches=True)

            def prefill(params, tokens, caches, extra):
                return step(params, tokens, caches, **extra)

            extra_abs = {k: v for k, v in batch_abs.items() if k != "tokens"}
            extra_shard = {k: v for k, v in b_shard.items() if k != "tokens"}
            jitted = jax.jit(
                prefill,
                in_shardings=(p_shard, b_shard["tokens"], c_shard, extra_shard),
                out_shardings=(
                    NamedSharding(mesh, P()),
                    c_shard,
                ),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(
                params_abs, batch_abs["tokens"], caches_abs, extra_abs
            )
        else:  # decode / long_decode
            batch_abs = input_specs(cfg, shape)
            b_shard = batch_shardings(rules, batch_abs)
            caches_abs = abstract_caches(model, shape.global_batch, shape.seq_len)
            c_shard = cache_shardings(rules, caches_abs)
            step = make_decode_step(model)
            rep = NamedSharding(mesh, P())
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, b_shard["token"], c_shard, rep),
                out_shardings=(b_shard["token"], c_shard),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(
                params_abs, batch_abs["token"], caches_abs,
                jax.ShapeDtypeStruct((), jnp.int32),
            )

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

        # bytes of donated inputs (train state / serve caches).  XLA:CPU
        # ignores buffer donation, so the CPU memory analysis carries one
        # extra copy of these that a TPU compile aliases away.
        if shape.kind == "train":
            donated = state_abs
        else:
            donated = caches_abs
        donated_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(donated)
        )
        sh_list = jax.tree.leaves(
            s_shard if shape.kind == "train" else c_shard,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )
        # per-device: divide each leaf by its shard count
        donated_per_dev = 0.0
        for l, sh in zip(jax.tree.leaves(donated), sh_list):
            donated_per_dev += (
                int(np.prod(l.shape)) * l.dtype.itemsize
                / _shard_count(sh, l.shape)
            )

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # newer jax: one dict per computation
        ca = ca[0] if ca else {}
    hlo = compiled.as_text()
    # trip-count-aware costs walked from the compiled HLO graph (XLA's own
    # cost_analysis counts while bodies once — useless for scanned layers)
    from repro.launch.hlo_cost import analyze as hlo_analyze

    costs = hlo_analyze(hlo)
    chips = mesh.devices.size

    mem = None
    if ma is not None:
        peak_cpu = float(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        )
        mem = {
            "argument_bytes": float(ma.argument_size_in_bytes),
            "output_bytes": float(ma.output_size_in_bytes),
            "temp_bytes": float(ma.temp_size_in_bytes),
            "alias_bytes": float(ma.alias_size_in_bytes),
            "peak_bytes": peak_cpu,
            # XLA:CPU ignores donation; on TPU the donated state/cache
            # aliases its output and this copy disappears
            "donated_per_dev_bytes": float(donated_per_dev),
            "peak_tpu_est_bytes": max(0.0, peak_cpu - donated_per_dev),
        }

    rf = Roofline(
        arch=arch,
        shape=shape_name,
        mesh="x".join(str(s) for s in mesh.devices.shape),
        chips=chips,
        hlo_flops=costs.flops * chips,
        hlo_bytes=costs.bytes_hbm_est * chips,
        hlo_bytes_upper=costs.bytes_accessed * chips,
        collective_bytes=costs.collective_ring_bytes,
        collective_by_kind=costs.collective_by_kind,
        model_flops=model_flops(cfg, shape),
        memory_per_device=mem,
        xla_flops_once=float(ca.get("flops", 0.0)) * chips,
        unknown_loops=costs.unknown_loops,
    )
    if verbose:
        print(f"[{arch} × {shape_name} × {rf.mesh}] compiled in {compile_s:.1f}s")
        if mem:
            print(f"  per-device: args {mem['argument_bytes']/2**30:.2f} GiB, "
                  f"temps {mem['temp_bytes']/2**30:.2f} GiB, "
                  f"peak {mem['peak_bytes']/2**30:.2f} GiB "
                  f"[TPU est. {mem['peak_tpu_est_bytes']/2**30:.2f} GiB after "
                  f"donation] (HBM 16 GiB)")
        print(f"  per-device FLOPs {rf.hlo_flops/chips:.3e}, "
              f"bytes {rf.hlo_bytes/chips:.3e}, "
              f"collective ring-bytes {rf.collective_bytes:.3e}"
              + (f" [{rf.unknown_loops} unknown loop bounds]"
                 if rf.unknown_loops else ""))
        print(f"  roofline: compute {rf.compute_s*1e3:.2f} ms | "
              f"memory {rf.memory_s*1e3:.2f} ms | "
              f"collective {rf.collective_s*1e3:.2f} ms "
              f"→ bottleneck: {rf.bottleneck}; "
              f"useful/HLO flops {rf.useful_fraction:.2f}; MFU {rf.mfu:.2%}")
    return rf, compiled, compile_s


class SkipCell(Exception):
    pass


def _shard_count(sh: NamedSharding, shape) -> int:
    """Number of distinct shards (devices dividing the array)."""
    n = 1
    mesh_shape = dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))
    for entry in sh.spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            n *= mesh_shape[a]
    return max(n, 1)


def _pick_microbatches(cfg, shape, mesh) -> int:
    """Gradient accumulation so the per-device residual-stream stack
    (n_layers × B_local × S × d_model × 2 bytes, saved once per layer under
    full remat) stays under ~2 GiB of HBM."""
    data_shards = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    b_local = max(1, shape.global_batch // data_shards)
    stack = cfg.n_layers * b_local * shape.seq_len * cfg.d_model * 2
    # hybrid (mamba state expansion) carries heavier per-layer transients
    target = (1 if cfg.family == "hybrid" else 2) * 2**30
    mb = 1
    while stack / mb > target and mb < b_local:
        mb *= 2
    return mb


def serve_cache_report(archs, max_slots: int, page_len: int):
    """Cost serving configs from shapes alone (no allocation, no compile).

    Per arch: bytes of the slot-managed decode state at (max_slots,
    page_len) — split into KV pages (scales with page_len) vs fixed-size
    recurrent state — via ``serve.abstract_slot_caches``/``jax.eval_shape``.
    """
    from repro.serve import slot_cache_bytes

    print(f"# serve cache report: {max_slots} slots x page {page_len}")
    print("arch,per_slot_MiB,kv_pages_MiB,recurrent_MiB,total_GiB")
    rows = []
    for arch in archs:
        model = DecoderLM(get_config(arch))
        sb = slot_cache_bytes(model, max_slots, page_len)
        rows.append({"arch": arch, **sb})
        print(f"{arch},{sb['per_slot']/2**20:.1f},{sb['kv_pages']/2**20:.1f},"
              f"{sb['recurrent']/2**20:.1f},{sb['total']/2**30:.2f}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSON results here")
    ap.add_argument("--serve-cache-report", action="store_true",
                    help="print slot-cache byte costs (eval_shape only; "
                         "no allocation or compilation) and exit")
    ap.add_argument("--serve-slots", type=int, default=128)
    ap.add_argument("--serve-page-len", type=int, default=32_768)
    args = ap.parse_args(argv)

    if args.serve_cache_report:
        serve_cache_report(
            ASSIGNED_ARCHS if args.all or not args.arch else [args.arch],
            args.serve_slots, args.serve_page_len)
        return

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    cells = []
    archs = ASSIGNED_ARCHS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    for mesh in meshes:
        for arch in archs:
            for shape in shapes:
                cells.append((arch, shape, mesh))

    results, failures = [], []
    for arch, shape, mesh in cells:
        mesh_name = "x".join(str(s) for s in mesh.devices.shape)
        try:
            rf, compiled, compile_s = lower_cell(arch, shape, mesh)
            d = rf.to_dict()
            d["compile_s"] = compile_s
            results.append(d)
        except SkipCell as e:
            print(f"[{arch} × {shape} × {mesh_name}] SKIP: {e}")
            results.append({
                "arch": arch, "shape": shape, "mesh": mesh_name,
                "skipped": str(e),
            })
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape, mesh_name, repr(e)))

    if args.out:
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        # replace same-cell entries
        keyf = lambda d: (d["arch"], d["shape"], d["mesh"])
        keep = {keyf(d): d for d in existing}
        for d in results:
            keep[keyf(d)] = d
        with open(args.out, "w") as f:
            json.dump(list(keep.values()), f, indent=1)
        print(f"wrote {len(results)} results to {args.out}")

    if failures:
        print("FAILURES:")
        for f_ in failures:
            print(" ", f_)
        sys.exit(1)


if __name__ == "__main__":
    main()
