import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (jax locks the device count on first
#   init).  512 host placeholder devices let jax.make_mesh build the
#   production meshes; nothing here ever allocates real tensors.

"""Multi-pod dry-run: lower + compile every (arch x input-shape) pair on
the production mesh, prove it fits, and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

For each combination this:
  1. builds abstract params/optimizer/cache trees (ShapeDtypeStruct — no
     allocation; a 235B model "loads" in milliseconds),
  2. jits the family's train/prefill/serve step with explicit in/out
     shardings and ``.lower().compile()``s it against the mesh,
  3. records ``memory_analysis()`` (fits-on-chip proof),
     ``cost_analysis()`` (FLOPs/bytes) and the partitioned-HLO collective
     bytes into a JSON report consumed by EXPERIMENTS.md §Dry-run/§Roofline.

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system — the matrix must be green before §Perf starts.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    make_prefill_step, make_serve_step, make_train_step, pick_microbatches)
from repro.models import model as M
from repro.roofline.analysis import (
    estimate_hbm_per_chip, model_flops_estimate, roofline_terms)

# long_500k policy (DESIGN.md §3): native for ssm/hybrid/SWA archs; dense
# archs run the sliding-window variant; whisper skipped (448-pos decoder).
LONG_WINDOW = 8192
SKIP: dict[tuple[str, str], str] = {
    ("whisper-large-v3", "long_500k"):
        "decoder max position is 448 (learned embedding); 500k decode is architecturally meaningless",
    ("damoldqn", "prefill_32k"): "fingerprint MLP has no sequence dim",
    ("damoldqn", "decode_32k"): "fingerprint MLP has no KV cache",
    ("damoldqn", "long_500k"): "fingerprint MLP has no sequence dim",
}
_PURE_FULL_ATTN = {"stablelm-1.6b", "granite-34b", "granite-20b", "yi-34b", "paligemma-3b"}


def prepare(arch: str, shape_name: str, multi_pod: bool):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if (arch, shape_name) in SKIP:
        return None
    if shape_name == "long_500k" and arch in _PURE_FULL_ATTN:
        cfg = cfg.with_window(LONG_WINDOW)  # beyond-paper SWA variant
    return cfg, shape


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            zero_opt: bool = False, seq_shard: bool = False,
            verbose: bool = True) -> dict:
    t0 = time.time()
    prep = prepare(arch, shape_name, multi_pod)
    if prep is None:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": SKIP[(arch, shape_name)]}
    cfg, shape = prep
    if seq_shard:
        import dataclasses
        cfg = dataclasses.replace(cfg, seq_shard=True)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_desc = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size

    ambient = jax.set_mesh(mesh)

    params = M.abstract_params(cfg)
    # FSDP for the big archs: params+opt at TP-only exceed the HBM budget
    fsdp = M.count_params(cfg) > 8e9
    p_shard = S.param_shardings(cfg, mesh, fsdp=fsdp)
    mb = 1

    if shape.kind == "train":
        dp = chips // mesh.shape.get("model", 1)
        mb = pick_microbatches(cfg, shape, dp)
        step_fn, opt = make_train_step(cfg, microbatches=mb)
        opt_state = jax.eval_shape(opt.init, params)
        pspecs = S.param_pspecs_for(cfg, mesh, fsdp=fsdp)
        if zero_opt and not fsdp:
            opt_pspecs_tree = S.zero_opt_shardings(cfg, mesh, pspecs)
        else:
            opt_pspecs_tree = pspecs
        from repro.optim.adam import OptState
        o_shard = OptState(
            step=S._shard(mesh, jax.sharding.PartitionSpec()),
            mu=jax.tree_util.tree_map(lambda s: S._shard(mesh, s), opt_pspecs_tree),
            nu=jax.tree_util.tree_map(lambda s: S._shard(mesh, s), opt_pspecs_tree),
        )
        if cfg.family == "qnet":
            batch, b_shard = S.qnet_batch_specs(shape, mesh)
            jitted = jax.jit(step_fn,
                             in_shardings=(p_shard, p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 2))
            with ambient:
                lowered = jitted.lower(params, params, opt_state, batch)
        else:
            batch, b_shard = S.train_batch_specs(cfg, shape, mesh)
            jitted = jax.jit(step_fn,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            with ambient:
                lowered = jitted.lower(params, opt_state, batch)
    elif shape.kind == "prefill":
        step_fn = make_prefill_step(cfg)
        batch, b_shard = S.train_batch_specs(cfg, shape, mesh)
        batch = {k: v for k, v in batch.items() if k not in ("labels", "mask")}
        b_shard = {k: v for k, v in b_shard.items() if k in batch}
        jitted = jax.jit(step_fn, in_shardings=(p_shard, b_shard))
        with ambient:
            lowered = jitted.lower(params, batch)
    else:  # decode
        step_fn = make_serve_step(cfg)
        tokens, cache, tok_shard, cache_shard = S.decode_specs(cfg, shape, mesh)
        jitted = jax.jit(step_fn,
                         in_shardings=(p_shard, cache_shard, tok_shard),
                         out_shardings=(None, cache_shard),
                         donate_argnums=(1,))
        with ambient:
            lowered = jitted.lower(params, cache, tokens)

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    mem_bytes = getattr(mem, "temp_size_in_bytes", 0) + getattr(mem, "argument_size_in_bytes", 0) \
        + getattr(mem, "output_size_in_bytes", 0) - getattr(mem, "alias_size_in_bytes", 0)
    report = roofline_terms(
        arch=arch, shape=shape_name, mesh_desc=mesh_desc, chips=chips,
        cost=cost, hlo_text=hlo,
        model_flops=model_flops_estimate(cfg, shape),
        memory_per_chip=float(mem_bytes),
    )
    out = report.to_dict()
    out.update({
        "status": "ok",
        "kind": shape.kind,
        "microbatches": mb if shape.kind == "train" else None,
        "fsdp": fsdp,
        "zero_opt": zero_opt,
        "seq_shard": seq_shard,
        "window": cfg.attn_window,
        "params_total": M.count_params(cfg),
        "params_active": M.active_params(cfg),
        "compile_s": round(time.time() - t0, 1),
        # measured (CPU backend, bf16->f32 legalization inflates ~2x)
        "hbm_gb_per_chip_cpu": round(mem_bytes / 2**30, 3),
    })
    hbm_est = estimate_hbm_per_chip(
        cfg, shape, tp=mesh.shape.get("model", 1),
        dp=chips // mesh.shape.get("model", 1), zero_opt=zero_opt,
        microbatches=mb if shape.kind == "train" else 1, fsdp=fsdp)
    out["hbm_gb_per_chip"] = round(hbm_est["total"] / 2**30, 3)
    out["hbm_breakdown_gb"] = {k: round(v / 2**30, 3) for k, v in hbm_est.items()}
    out["fits_16gb"] = out["hbm_gb_per_chip"] <= 16.0
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} on {mesh_desc}: OK "
              f"({out['compile_s']}s compile, {out['hbm_gb_per_chip']} GiB/chip, "
              f"dominant={out['dominant']})", flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--zero-opt", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}" + \
                    ("_zero" if args.zero_opt else "") + \
                    ("_seqshard" if args.seq_shard else "")
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[dryrun] {tag}: cached", flush=True)
                    continue
                try:
                    res = run_one(arch, shape, multi_pod=mp, zero_opt=args.zero_opt,
                                  seq_shard=args.seq_shard)
                except Exception as e:  # noqa: BLE001 — must report every combo
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape, "status": "FAIL",
                           "error": f"{type(e).__name__}: {e}"}
                    n_fail += 1
                res["mesh"] = "2x16x16" if mp else "16x16"
                with open(path, "w") as f:
                    json.dump(res, f, indent=2, default=str)
    print(f"[dryrun] done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
