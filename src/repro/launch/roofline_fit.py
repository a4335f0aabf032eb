import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ["REPRO_UNROLL_SCANS"] = "1"  # cost_analysis must see every layer

"""Depth-extrapolated roofline measurement (§Roofline correctness fix).

XLA's ``cost_analysis()`` counts a while-loop body ONCE, so the scan-over-
layers models underreport FLOPs/bytes by ~n_layers.  This tool lowers each
(arch x shape) cell at TWO reduced depths with every structural scan fully
unrolled, fits   cost(u) = intercept + slope * u   (exact for identical
layers), and extrapolates to the full depth.  Collective bytes are fitted
the same way per collective kind.

Depth units per family (chosen so the reduced configs are structurally
valid and the remainder blocks sit in the intercept):
  dense/moe/vlm : u = layers                (fit at 2, 4)
  hybrid        : u = mamba+shared groups   (fit at P+rem, 2P+rem layers)
  ssm           : u = mLSTM/sLSTM groups    (fit at P, 2P layers)
  audio         : u = enc+dec layer pairs   (fit at 2, 4; enc==dec depth)

    PYTHONPATH=src python -m repro.launch.roofline_fit --all
    PYTHONPATH=src python -m repro.launch.roofline_fit --arch qwen3-14b \
        --shape train_4k

Writes artifacts/roofline/<arch>__<shape>__single.json; resumable.
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.dryrun import build_cell, cell_is_applicable  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def depth_variants(cfg):
    """[(reduced_cfg, u), ...], u_full for the linear depth fit."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return [(dataclasses.replace(cfg, n_layers=u), u) for u in (2, 4)], \
            cfg.n_layers
    if fam == "hybrid":
        P = cfg.shared_attn_period
        rem = cfg.n_layers % P
        pts = [
            (dataclasses.replace(cfg, n_layers=u * P + rem), u)
            for u in (1, 2)
        ]
        return pts, cfg.n_layers // P
    if fam == "ssm":
        P = cfg.xlstm.slstm_period
        assert cfg.n_layers % P == 0
        pts = [
            (dataclasses.replace(cfg, n_layers=u * P), u) for u in (1, 2)
        ]
        return pts, cfg.n_layers // P
    if fam == "audio":
        assert cfg.encoder_layers == cfg.n_layers, "audio fit assumes enc==dec"
        pts = [
            (dataclasses.replace(cfg, n_layers=u, encoder_layers=u), u)
            for u in (2, 4)
        ]
        return pts, cfg.n_layers
    raise ValueError(fam)


def measure_point(arch, shape_name, mesh, cfg):
    from repro.launch.act_sharding import policy_from_env

    with mesh, policy_from_env(mesh):
        jfn, args, _cfg, shape, params_shapes = build_cell(
            arch, shape_name, mesh, cfg=cfg
        )
        lowered = jfn.lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = rl.parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": {k: float(coll[k]) for k in _COLL_KINDS},
        "coll_total": float(coll["total"]),
        "coll_counts": coll["counts"],
    }


def linfit(p1, p2, u1, u2, u_full):
    slope = (p2 - p1) / (u2 - u1)
    intercept = p1 - slope * u1
    return max(0.0, intercept + slope * u_full)


def run_cell(arch, shape_name, out_dir="artifacts/roofline"):
    os.makedirs(out_dir, exist_ok=True)
    pol = os.environ.get("REPRO_SHARDING", "baseline")
    suffix = "single" if pol == "baseline" else f"single_{pol}"
    if os.environ.get("REPRO_KV_CACHE", "int4") == "bf16":
        suffix += "_bf16cache"
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}__{suffix}.json")
    if os.path.exists(out_path):
        print(f"[skip] {out_path}")
        return
    ok, why = cell_is_applicable(arch, shape_name)
    if not ok:
        json.dump({"arch": arch, "shape": shape_name, "status": "skipped",
                   "reason": why}, open(out_path, "w"), indent=2)
        return
    cfg = get_config(arch)
    mesh = make_production_mesh()
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name, "mesh": "single",
              "chips": n_chips, "method": "depth_fit_unrolled",
              "sharding": pol}
    try:
        pts, u_full = depth_variants(cfg)
        (c1, u1), (c2, u2) = pts
        m1 = measure_point(arch, shape_name, mesh, c1)
        m2 = measure_point(arch, shape_name, mesh, c2)
        record["points"] = [
            {"u": u1, **m1}, {"u": u2, **m2},
        ]
        record["u_full"] = u_full
        fitted = {
            "flops": linfit(m1["flops"], m2["flops"], u1, u2, u_full),
            "bytes": linfit(m1["bytes"], m2["bytes"], u1, u2, u_full),
            "coll_total": linfit(m1["coll_total"], m2["coll_total"],
                                 u1, u2, u_full),
            "coll": {
                k: linfit(m1["coll"][k], m2["coll"][k], u1, u2, u_full)
                for k in _COLL_KINDS
            },
        }
        record["fitted"] = fitted
        record["roofline"] = rl.roofline_terms(
            fitted["flops"], fitted["bytes"], fitted["coll_total"]
        )
        # MODEL_FLOPS from the FULL config (eval_shape only, no compile)
        from repro.models import build_model
        model = build_model(cfg)
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        record["model_flops"] = rl.model_flops_estimate(
            cfg, SHAPES[shape_name], params_shapes
        )
        hlo_global = fitted["flops"] * n_chips
        record["model_flops"]["useful_ratio"] = (
            record["model_flops"]["model_flops"] / hlo_global
            if hlo_global else None
        )
        record["status"] = "ok"
        record["t_total_s"] = round(time.time() - t0, 1)
        r = record["roofline"]
        print(f"[ok] {arch} x {shape_name}: flops/dev {fitted['flops']:.3e} "
              f"bytes {fitted['bytes']:.3e} coll {fitted['coll_total']:.3e} "
              f"-> {r['bottleneck']} ({record['t_total_s']}s)")
    except Exception as e:
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch} x {shape_name}: {record['error']}")
    json.dump(record, open(out_path, "w"), indent=2, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/roofline")
    args = ap.parse_args()
    if args.all:
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                run_cell(arch, shape_name, args.out)
    else:
        assert args.arch and args.shape
        run_cell(args.arch, args.shape, args.out)


if __name__ == "__main__":
    main()
