"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: sharding
propagation, collective schedule, memory fit — all from the compiled SPMD
artifact on 512 placeholder host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --profile tuned --out results/dryrun
"""
# The VERY FIRST lines, before ANY other import (jax locks the device count
# at first init):
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, get_config, list_archs
from repro.distributed import meshes as M
from repro.launch import hlo_cost as H
from repro.launch import roofline as R
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.launch.tuning import cell_config
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig


def _active_params(cfg, params_spec) -> int:
    """Active (per-token) parameter count from the abstract pytree."""
    total = 0
    routed = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_spec)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        n = int(np.prod(leaf.shape))
        total += n
        if "/moe/" in key and "/shared/" not in key and "router" not in key:
            routed += n
    if cfg.moe is not None and cfg.moe.n_experts:
        active = total - routed + int(routed * cfg.moe.top_k / cfg.moe.n_experts)
        return active
    return total


def lower_cell(arch: str, shape_name: str, mesh, profile: str = "tuned",
               overrides: Optional[Dict[str, Any]] = None,
               opt_overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Lower + compile one cell; returns the artifact record."""
    shape = SHAPES[shape_name]
    cfg0 = get_config(arch)
    if overrides:                      # before tuning so vocab/dims are real
        cfg0 = cfg0.replace(**overrides)
    cfg, opts = cell_config(cfg0, shape_name, profile)
    if overrides:                      # and after, so explicit overrides win
        cfg = cfg.replace(**overrides)
    if opt_overrides:
        opts.update(opt_overrides)
    chips = int(np.prod(list(mesh.shape.values())))

    params_spec = S.param_specs(cfg)
    p_pspec = M.param_pspecs(cfg, params_spec, mesh)
    p_sh = M.named(p_pspec, mesh)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": dict(mesh.shape),
        "profile": profile, "chips": chips, "kind": shape.kind,
        "config": {"attention_impl": cfg.attention_impl,
                   "attention_chunk": cfg.attention_chunk,
                   "vocab_loss_chunk": cfg.vocab_loss_chunk,
                   "remat_policy": cfg.remat_policy,
                   "sequence_parallel": cfg.sequence_parallel,
                   "grad_accum": opts.get("grad_accum", 1)},
    }
    t0 = time.time()
    ctx = jax.set_mesh(mesh)          # ambient mesh for sequence_shard
    ctx.__enter__()

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        step_fn = S.make_train_step(cfg, opt_cfg, opts.get("grad_accum", 1))
        opt_spec = S.abstract_opt_state(params_spec)
        o_pspec = M.opt_pspecs(cfg, params_spec, mesh)
        from repro.optim.adamw import OptState
        o_sh = OptState(mu=M.named(o_pspec, mesh), nu=M.named(o_pspec, mesh),
                        step=NamedSharding(mesh, P()))
        batch = S.batch_specs(cfg, shape)
        b_sh = M.named(M.batch_pspecs(batch, mesh), mesh)
        lowered = jax.jit(
            step_fn,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        ).lower(params_spec, opt_spec, batch)
    elif shape.kind == "prefill":
        step_fn = S.make_prefill_step(cfg)
        batch = S.batch_specs(cfg, shape)
        b_sh = M.named(M.batch_pspecs(batch, mesh), mesh)
        lowered = jax.jit(step_fn, in_shardings=(p_sh, b_sh)).lower(
            params_spec, batch)
    else:  # decode
        step_fn = S.make_decode_step(cfg)
        d = S.decode_specs(cfg, shape)
        c_pspec = M.cache_pspecs(cfg, d["cache"], mesh, shape.seq_len)
        c_sh = M.named(c_pspec, mesh)
        tok_pspec = M.batch_pspecs({"t": d["tokens"]}, mesh)["t"]
        tok_sh = NamedSharding(mesh, tok_pspec)
        batch_ax = tok_pspec[0] if len(tok_pspec) else None
        next_rank = 2 if cfg.frontend == "audio" else 1   # [B,K] vs [B]
        next_sh = NamedSharding(
            mesh, P(*((batch_ax,) + (None,) * (next_rank - 1))))
        pos_sh = NamedSharding(mesh, P())
        lowered = jax.jit(
            step_fn,
            in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
            out_shardings=(next_sh, c_sh),
            donate_argnums=(1,),
        ).lower(params_spec, d["cache"], d["tokens"], d["pos"])

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ctx.__exit__(None, None, None)

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # jax 0.4.x: one dict per device
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    hc = H.analyze(hlo)                    # trip-count-corrected HLO cost

    n_active = _active_params(cfg, params_spec)
    n_total = int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(params_spec)))
    mf = R.model_flops_for(cfg, shape, n_active, shape.kind)
    corrected = {"flops": hc.flops, "bytes accessed": hc.traffic_bytes}
    coll = R.CollectiveStats(
        bytes_by_op={k: int(v) for k, v in hc.collective_by_op.items()})
    terms = R.derive_terms(corrected, coll, chips, mf)

    rec.update({
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "params_total": n_total, "params_active": n_active,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
                                   + mem.temp_size_in_bytes
                                   + mem.output_size_in_bytes
                                   - mem.alias_size_in_bytes,
        },
        "cost": {"flops": hc.flops,                      # trip-corrected
                 "bytes_accessed": hc.traffic_bytes,
                 "xla_flops_raw": cost.get("flops", 0.0),
                 "xla_bytes_raw": cost.get("bytes accessed", 0.0),
                 "unknown_trip_loops": hc.unknown_trip_loops},
        "collectives": {"bytes_by_op": coll.bytes_by_op,
                        "total_bytes": coll.total_bytes},
        "roofline": {
            "compute_s": terms.compute_s, "memory_s": terms.memory_s,
            "collective_s": terms.collective_s, "dominant": terms.dominant,
            "model_flops_global": mf, "useful_ratio": terms.useful_ratio,
            "roofline_fraction": terms.roofline_fraction,
        },
    })
    return rec


def run_cells(archs, shapes, mesh_modes, profile: str, out_dir: str,
              stop_on_error: bool = False):
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for mesh_mode in mesh_modes:
        mesh = make_production_mesh(multi_pod=(mesh_mode == "multipod"))
        for arch in archs:
            cfg = get_config(arch)
            for shape_name in shapes:
                tag = f"{arch}__{shape_name}__{mesh_mode}__{profile}"
                path = os.path.join(out_dir, tag + ".json")
                if shape_name not in cfg.shapes():
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh_mode": mesh_mode, "ok": False,
                           "skipped": True,
                           "reason": "pure full-attention arch; long-context "
                                     "decode requires sub-quadratic mixer "
                                     "(DESIGN.md §Arch-applicability)"}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"[skip] {tag}: inapplicable shape")
                    continue
                if os.path.exists(path):
                    with open(path) as f:
                        old = json.load(f)
                    if old.get("ok"):
                        print(f"[cached] {tag}")
                        results.append(old)
                        continue
                print(f"[lower+compile] {tag} ...", flush=True)
                try:
                    rec = lower_cell(arch, shape_name, mesh, profile)
                    rec["mesh_mode"] = mesh_mode
                    rl = rec["roofline"]
                    print(f"    ok: compile={rec['compile_s']}s "
                          f"dominant={rl['dominant']} "
                          f"compute={rl['compute_s']:.4f}s "
                          f"memory={rl['memory_s']:.4f}s "
                          f"coll={rl['collective_s']:.4f}s "
                          f"frac={rl['roofline_fraction']:.3f}", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh_mode": mesh_mode, "profile": profile,
                           "ok": False, "error": str(e)[-2000:],
                           "traceback": traceback.format_exc()[-4000:]}
                    print(f"    FAILED: {str(e)[:300]}", flush=True)
                    if stop_on_error:
                        raise
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                results.append(rec)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--profile", default="tuned", choices=["baseline", "tuned"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--stop-on-error", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    mesh_modes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    results = run_cells(archs, shapes, mesh_modes, args.profile, args.out,
                        stop_on_error=args.stop_on_error)
    ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{ok}/{len(results)} cells compiled OK")


if __name__ == "__main__":
    main()
