import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) cell, lower + compile the step
(train_step / prefill / decode) against ShapeDtypeStruct inputs on the
single-pod (16x16) and multi-pod (2x16x16) production meshes, then record:

  - memory_analysis()  — per-device bytes (proves it fits),
  - cost_analysis()    — FLOPs / bytes for the roofline,
  - collective bytes   — parsed from the post-SPMD HLO,
  - the derived three-term roofline.

Results land as JSON under experiments/dryrun/; the run is resumable (cells
with existing JSON are skipped unless --force).

NOTE: the XLA_FLAGS line above MUST run before any other import — jax locks
the device count at first init.  Do not set this flag anywhere global.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCHS, SHAPES, RunConfig, cell_enabled, get_arch
from repro.models import input_specs, make_model
from repro.launch import hlo_cost
from repro.launch import roofline as RL
from repro.launch.mesh import PRODUCTION_KIND, make_production_mesh
from repro.launch.steps import (build_decode_step, build_prefill_step,
                                build_train_step, jit_decode_step,
                                jit_prefill_step, jit_train_step)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mem_dict(mem) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             run: RunConfig | None = None, verbose: bool = True,
             mesh_shape: str = "") -> dict:
    cfg = get_arch(arch_name)
    kind, seq, batch = SHAPES[shape_name]
    run = run or RunConfig(seq_len=seq, global_batch=batch, remat="dots")
    if mesh_shape:
        # per-arch mesh factorization (same 256 chips, different DPxTP split)
        dims = tuple(int(x) for x in mesh_shape.split("x"))
        from repro.compat import make_mesh
        mesh = make_mesh(dims, ("data", "model")[:len(dims)])
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    specs = input_specs(cfg, shape_name, run)
    if kind == "train":
        built = build_train_step(cfg, run, mesh)
        params_abs, opt_abs = built["abstract_state"]
        step = jit_train_step(built, mesh, specs["batch"])
        lowered = step.lower(params_abs, opt_abs, specs["batch"],
                             jax.ShapeDtypeStruct((), np.int32))
        tokens = batch * seq
        mflops = RL.train_model_flops(cfg.active_param_count(), tokens)
    elif kind == "prefill":
        built = build_prefill_step(cfg, run, mesh)
        step = jit_prefill_step(built, mesh, specs["batch"],
                                jax.eval_shape(
                                    lambda: make_model(cfg)["init_cache"](
                                        run, batch, seq)))
        lowered = step.lower(built["abstract_params"], specs["batch"])
        mflops = 2.0 * cfg.active_param_count() * batch * seq
    else:  # decode
        built = build_decode_step(cfg, run, mesh)
        step = jit_decode_step(built, mesh, specs["cache"])
        lowered = step.lower(built["abstract_params"], specs["cache"],
                             specs["tokens"], specs["pos"])
        mflops = RL.decode_model_flops(cfg.active_param_count(), batch)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):      # older jax: list of per-device dicts
        cost = cost[0] if cost else {}
    mem = _mem_dict(compiled.memory_analysis())
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    # loop-aware costs (cost_analysis counts while bodies once; see hlo_cost)
    dyn_hint = max(1.0, seq / (2.0 * run.attn_chunk))
    parsed = hlo_cost.analyze(hlo, dynamic_trip_hint=dyn_hint)
    coll = parsed.as_dict()["collectives"]
    coll["total_bytes"] = parsed.as_dict()["collective_bytes"]
    corrected = {"flops": parsed.flops, "bytes accessed": parsed.traffic}
    roof = RL.roofline(corrected, {"total_bytes": coll["total_bytes"]},
                       n_chips, model_flops=mflops,
                       device_kind=PRODUCTION_KIND)
    roof["dynamic_loops_hinted"] = parsed.dynamic_loops

    result = {
        "arch": arch_name, "shape": shape_name,
        "mesh": mesh_shape or ("2x16x16" if multi_pod else "16x16"),
        "n_chips": n_chips, "step_kind": kind,
        "seq_len": seq, "global_batch": batch,
        "run_config": {"remat": run.remat, "fsdp": run.fsdp,
                       "attn_chunk": run.attn_chunk,
                       "microbatch": run.microbatch, "dtype": run.dtype,
                       "moe_groups": run.moe_groups,
                       "act_shard": run.act_shard},
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost_raw": {k: cost.get(k) for k in
                     ("flops", "bytes accessed", "optimal_seconds")
                     if k in cost},
        "cost": corrected,
        "collectives": coll,
        "roofline": roof,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    if verbose:
        hbm = (mem.get("argument_size_in_bytes", 0)
               + mem.get("temp_size_in_bytes", 0)
               + mem.get("output_size_in_bytes", 0)
               - mem.get("alias_size_in_bytes", 0)) / 1e9
        print(f"[dryrun] {arch_name:22s} {shape_name:12s} "
              f"{'multi' if multi_pod else 'single':6s} "
              f"OK  mem/dev={hbm:7.2f}GB  "
              f"compute={roof['compute_s']:.3e}s "
              f"mem={roof['memory_s']:.3e}s "
              f"coll={roof['collective_s']:.3e}s "
              f"bott={roof['bottleneck']:10s} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--moe-groups", type=int, default=0)
    ap.add_argument("--act-shard", default="none")
    ap.add_argument("--bf16-scores", action="store_true")
    ap.add_argument("--mesh-shape", default="",
                    help='custom DPxTP factorization, e.g. "64x4"')
    ap.add_argument("--tag", default="", help="suffix for output JSONs")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for a in archs:
        for s in shapes:
            ok, why = cell_enabled(ARCHS[a], s)
            if not ok:
                print(f"[dryrun] {a:22s} {s:12s} SKIP   ({why})")
                n_skip += 1
                continue
            for mp in meshes:
                tag = f"{a}__{s}__{'multi' if mp else 'single'}"
                if args.tag:
                    tag += "__" + args.tag
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    n_skip += 1
                    continue
                kind, seq, batch = SHAPES[s]
                run = RunConfig(seq_len=seq, global_batch=batch,
                                remat=args.remat, fsdp=args.fsdp,
                                microbatch=args.microbatch,
                                moe_groups=args.moe_groups,
                                act_shard=args.act_shard,
                                attn_f32_scores=not args.bf16_scores)
                try:
                    res = run_cell(a, s, mp, run=run,
                                   mesh_shape=args.mesh_shape)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    n_ok += 1
                except Exception as e:
                    n_fail += 1
                    print(f"[dryrun] {a:22s} {s:12s} "
                          f"{'multi' if mp else 'single':6s} FAIL  {e}")
                    traceback.print_exc()
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
