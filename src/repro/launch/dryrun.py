"""Multi-pod dry run: lower + compile every (architecture x input shape) on
the production meshes, print memory/cost analysis, and dump roofline terms.

Roofline methodology: XLA's HloCostAnalysis counts ``while`` (lax.scan)
bodies ONCE regardless of trip count, so the deep scanned stacks would be
undercounted. We therefore compile THREE programs per pair:
  1. the full config (scanned)        -> compile proof + memory_analysis;
  2. depth = 1 period, scans unrolled -> f1 (per-device flops/bytes/colls);
  3. depth = 2 periods, unrolled      -> f2;
and extrapolate  total = f1 + (n_periods - 1) * (f2 - f1)
(periods are structurally identical, so f2 - f1 is exactly one period body).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes --out experiments/dryrun.json

``main`` asks the CPU backend for 512 host devices (``XLA_FLAGS``) before
JAX creates its backends; importing this module changes nothing.
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, applicable_shapes, get_config
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.launch.roofline import Roofline, collective_stats, model_flops_for
from repro.launch.steps import (
    make_prefill_step,
    make_serve_step,
    make_train_step,
    prefill_input_specs,
    serve_input_specs,
    serve_shardings,
    train_input_specs,
    train_shardings,
)
from repro.models import flags
from repro.models.model import period_length
from repro.sharding.specs import activation_sharding, infer_pytree_specs, set_mesh


def _act_spec(mode_flag, mesh, train=True):
    dp = ("data",) if train else dp_axes(mesh)
    if mode_flag == "seq":
        return P(dp if not train else "data", "model", None)
    if mode_flag == "dmodel":
        return P(dp if not train else "data", None, "model")
    return None  # batch-only


def _compile_step(cfg, shape, mesh, *, algorithm, seq_parallel, tp2d=False):
    """Lower + compile one program; returns the compiled object.
    ``seq_parallel``: True/"seq" | False/None (batch-only) | "dmodel".
    ``tp2d``: decode-only 2D tensor-parallel weight sharding (H4)."""
    if seq_parallel is True:
        seq_parallel = "seq"
    if shape.mode == "train":
        state, batches = train_input_specs(cfg, shape, mesh)
        st_specs, b_specs = train_shardings(state, batches, mesh)
        step = make_train_step(cfg, mesh, algorithm=algorithm)
        act = _act_spec(seq_parallel, mesh) if seq_parallel else None
        with activation_sharding(act):
            lowered = jax.jit(step, in_shardings=(st_specs, b_specs),
                              out_shardings=(st_specs, None)).lower(state, batches)
    elif shape.mode == "prefill":
        params, tokens, memory = prefill_input_specs(cfg, shape, mesh)
        p_specs = infer_pytree_specs(params, mesh)
        dp = dp_axes(mesh)
        tok_spec = NamedSharding(mesh, P(dp, None))
        args = (params, tokens) + ((memory,) if memory is not None else ())
        in_sh = (p_specs, tok_spec) + (
            (NamedSharding(mesh, P(dp, None, None)),) if memory is not None else ())
        step = make_prefill_step(cfg)
        act = (P(dp, "model", None) if seq_parallel in (True, "seq")
               else P(dp, None, "model") if seq_parallel == "dmodel" else None)
        with activation_sharding(act):
            lowered = jax.jit(step, in_shardings=in_sh,
                              out_shardings=None).lower(*args)
    else:  # decode
        params, cache, token, pos, memory = serve_input_specs(cfg, shape, mesh)
        p_specs, c_specs, tok_spec = serve_shardings(
            params, cache, mesh, shape.global_batch, tp2d=tp2d)
        pos_spec = NamedSharding(mesh, P())
        args = (params, cache, token, pos) + ((memory,) if memory is not None else ())
        in_sh = (p_specs, c_specs, tok_spec, pos_spec) + (
            (NamedSharding(mesh, P(None, None, None)),) if memory is not None else ())
        step = make_serve_step(cfg)
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=None).lower(*args)
    return lowered.compile()


def _metrics(compiled):
    cost = compiled.cost_analysis()
    coll = collective_stats(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": dict(coll.bytes_by_kind),
        "coll_count": dict(coll.count_by_kind),
    }


def _depth_variant(cfg, k: int):
    """Config with k periods of depth (and k encoder layers for audio)."""
    P_ = period_length(cfg)
    kw = {"num_layers": k * P_}
    if cfg.encoder_layers:
        kw["encoder_layers"] = k
    return dataclasses.replace(cfg, **kw)


def _extrapolate(f1, f2, n):
    out = {"flops": f1["flops"] + (n - 1) * (f2["flops"] - f1["flops"]),
           "bytes": f1["bytes"] + (n - 1) * (f2["bytes"] - f1["bytes"])}
    kinds = set(f1["coll_bytes"]) | set(f2["coll_bytes"])
    cb, cc = {}, {}
    for k in kinds:
        b1 = f1["coll_bytes"].get(k, 0)
        b2 = f2["coll_bytes"].get(k, 0)
        cb[k] = max(0, b1 + (n - 1) * (b2 - b1))
        c1 = f1["coll_count"].get(k, 0)
        c2 = f2["coll_count"].get(k, 0)
        cc[k] = max(0, c1 + (n - 1) * (c2 - c1))
    out["coll_bytes"] = cb
    out["coll_count"] = cc
    return out


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
               verbose: bool = True, algorithm: str = "fedpbc",
               dispatch: str = None, seq_parallel: bool = True,
               analyze: bool = True, tp2d: bool = False):
    cfg = get_config(arch)
    if dispatch and cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=dispatch))
    shape = INPUT_SHAPES[shape_name]
    if shape.name not in [s.name for s in applicable_shapes(cfg)]:
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "mesh": "2x16x16" if multi_pod else "16x16",
                "reason": "full-attention arch at 500k / enc-dec long decode"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh(mesh)
    t0 = time.time()
    try:
        with mesh:
            compiled = _compile_step(cfg, shape, mesh, algorithm=algorithm,
                                     seq_parallel=seq_parallel, tp2d=tp2d)
            t_full = time.time() - t0
            if analyze:
                n_periods = cfg.num_layers // period_length(cfg)
                with flags.analysis():
                    c1 = _compile_step(_depth_variant(cfg, 1), shape, mesh,
                                       algorithm=algorithm,
                                       seq_parallel=seq_parallel, tp2d=tp2d)
                    f1 = _metrics(c1)
                    del c1
                    c2 = _compile_step(_depth_variant(cfg, 2), shape, mesh,
                                       algorithm=algorithm,
                                       seq_parallel=seq_parallel, tp2d=tp2d)
                    f2 = _metrics(c2)
                    del c2
                est = _extrapolate(f1, f2, n_periods)
            else:
                est = _metrics(compiled)
    except Exception as e:
        set_mesh(None)
        return {"arch": arch, "shape": shape_name, "status": "FAIL",
                "mesh": "2x16x16" if multi_pod else "16x16",
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2500:]}
    set_mesh(None)

    mem = compiled.memory_analysis()
    chips = 512 if multi_pod else 256
    rf = Roofline(
        flops=est["flops"],
        hbm_bytes=est["bytes"],
        coll_bytes=float(sum(est["coll_bytes"].values())),
        chips=chips,
        model_flops=model_flops_for(cfg, shape, mode=shape.mode),
    )
    result = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": shape.mode,
        "compile_s": round(time.time() - t0, 1),
        "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "collectives": {k: [est["coll_count"][k], est["coll_bytes"][k]]
                        for k in est["coll_bytes"]},
        **rf.row(),
    }
    if verbose:
        print(f"== {arch} x {shape_name} mesh={result['mesh']} ==")
        print("memory_analysis:", mem)
        print("cost_analysis (extrapolated): flops=%.3e bytes=%.3e"
              % (rf.flops, rf.hbm_bytes))
        print("collectives:", result["collectives"])
        print("roofline: compute=%.4fs memory=%.4fs collective=%.4fs -> %s"
              % (rf.t_compute, rf.t_memory, rf.t_collective, rf.bottleneck))
        print("useful fraction (model/HLO flops): %.3f" % rf.useful_fraction)
    return result


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--algorithm", default="fedpbc")
    ap.add_argument("--dispatch", default=None, help="override MoE dispatch")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--act-spec", default=None, choices=["seq", "dmodel"])
    ap.add_argument("--tp2d", action="store_true",
                    help="decode: 2D tensor-parallel weights (H4)")
    ap.add_argument("--no-analyze", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for mp in meshes:
        for a in archs:
            for s in shapes:
                sp = args.act_spec or (not args.no_seq_parallel)
                r = lower_pair(a, s, multi_pod=mp, algorithm=args.algorithm,
                               dispatch=args.dispatch,
                               seq_parallel=sp,
                               analyze=not args.no_analyze, tp2d=args.tp2d)
                print(json.dumps({k: v for k, v in r.items() if k != "trace"}),
                      flush=True)
                if r["status"] == "FAIL":
                    print(r.get("trace", ""), flush=True)
                results.append(r)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_fail = sum(r["status"] == "FAIL" for r in results)
    print(f"DONE ok={n_ok} skip={n_skip} fail={n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
