import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, record memory/cost/collective analysis (EXPERIMENTS.md
§Dry-run, §Roofline).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

Writes one JSON per cell to artifacts/dryrun/.  Cells already present are
skipped (resumable).
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_IDS, LONG_CONTEXT_ARCHS, SHAPES, get_config,
)
from repro.launch import partitioning as pt  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs, serve_cache_shapes  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    make_decode_step, make_prefill_step, make_train_step,
)
from repro.models import build_model  # noqa: E402
from repro.optim.adam import adam_init  # noqa: E402


def cell_is_applicable(arch: str, shape_name: str) -> tuple[bool, str]:
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return False, (
            "long_500k needs a sub-quadratic backbone; skipped for pure "
            "full-attention archs (DESIGN.md §3)"
        )
    return True, ""


def build_cell(arch: str, shape_name: str, mesh, cfg=None):
    """Returns (jitted_fn, example_args, donate) for the cell.

    ``cfg`` overrides the registry config (roofline_fit lowers reduced-
    depth unrolled variants of the same arch through this hook).
    """
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)

    params_shapes = jax.eval_shape(model.init, key)
    params_spec = pt.param_specs(params_shapes, mesh)
    params_sh = pt.make_shardings(params_spec, mesh)

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(adam_init, params_shapes)
        opt_sh = pt.make_shardings(pt.param_specs(opt_shapes.mu, mesh), mesh)
        opt_sh = opt_shapes.__class__(
            step=pt.make_shardings(pt.auto_spec((), mesh), mesh),
            mu=opt_sh,
            nu=pt.make_shardings(pt.param_specs(opt_shapes.nu, mesh), mesh),
        )
        batch_shapes = input_specs(cfg, shape)
        batch_sh = pt.make_shardings(pt.batch_specs(batch_shapes, mesh), mesh)
        fn = make_train_step(model)
        args = (params_shapes, opt_shapes, batch_shapes)
        in_sh = (params_sh, opt_sh, batch_sh)
        jfn = jax.jit(fn, in_shardings=in_sh, donate_argnums=(0, 1))
        return jfn, args, cfg, shape, params_shapes

    # serving cells: rotation state rides inside the cache pytree
    # (cache_specs replicates rot_k/rot_v leaves -- small d x d per layer)
    cache_shapes = serve_cache_shapes(model, cfg, shape)
    cache_sh = pt.make_shardings(pt.cache_specs(cache_shapes, mesh), mesh)

    if shape.kind == "prefill":
        batch_shapes = input_specs(cfg, shape)
        batch_sh = pt.make_shardings(pt.batch_specs(batch_shapes, mesh), mesh)
        fn = make_prefill_step(model)
        args = (params_shapes, batch_shapes, cache_shapes)
        in_sh = (params_sh, batch_sh, cache_sh)
        jfn = jax.jit(fn, in_shardings=in_sh, donate_argnums=(2,))
        return jfn, args, cfg, shape, params_shapes

    # decode
    tok_shapes = input_specs(cfg, shape)["token"]
    tok_sh = pt.make_shardings(pt.batch_specs({"t": tok_shapes}, mesh)["t"], mesh)
    fn = make_decode_step(model)
    args = (params_shapes, tok_shapes, cache_shapes)
    in_sh = (params_sh, tok_sh, cache_sh)
    jfn = jax.jit(fn, in_shardings=in_sh, donate_argnums=(2,))
    return jfn, args, cfg, shape, params_shapes


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{mesh_kind}.json"
    )
    if os.path.exists(out_path):
        print(f"[skip] {out_path} exists")
        return
    ok, why = cell_is_applicable(arch, shape_name)
    if not ok:
        json.dump(
            {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
             "status": "skipped", "reason": why},
            open(out_path, "w"), indent=2,
        )
        print(f"[skip-cell] {arch} x {shape_name}: {why}")
        return

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "chips": n_chips,
    }
    try:
        with mesh:
            jfn, args, cfg, shape, params_shapes = build_cell(
                arch, shape_name, mesh
            )
            lowered = jfn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        try:
            mem = compiled.memory_analysis()
            record["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes",
                )
                if hasattr(mem, k)
            }
        except Exception as e:  # CPU backend may not implement it
            record["memory_analysis"] = {"error": str(e)}

        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            record["cost_analysis"] = {
                k: float(v) for k, v in cost.items()
                if k in ("flops", "bytes accessed", "optimal_seconds",
                         "transcendentals")
            }
        except Exception as e:
            record["cost_analysis"] = {"error": str(e)}

        hlo = compiled.as_text()
        record["collectives"] = rl.parse_collective_bytes(hlo)
        record["hlo_bytes"] = len(hlo)

        flops = record.get("cost_analysis", {}).get("flops", 0.0)
        nbytes = record.get("cost_analysis", {}).get("bytes accessed", 0.0)
        record["roofline"] = rl.roofline_terms(
            flops, nbytes, record["collectives"]["total"]
        )
        record["model_flops"] = rl.model_flops_estimate(
            cfg, shape, params_shapes
        )
        hlo_global = flops * n_chips
        record["model_flops"]["useful_ratio"] = (
            record["model_flops"]["model_flops"] / hlo_global
            if hlo_global else None
        )
        record["status"] = "ok"
        record["t_lower_s"] = round(t_lower, 2)
        record["t_compile_s"] = round(t_compile, 2)
        print(
            f"[ok] {arch} x {shape_name} x {mesh_kind}: "
            f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
            f"flops/dev {flops:.3e} bytes/dev {nbytes:.3e} "
            f"coll {record['collectives']['total']:.3e}B"
        )
    except Exception as e:
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch} x {shape_name} x {mesh_kind}: {record['error']}")
    json.dump(record, open(out_path, "w"), indent=2, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    if args.all:
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                run_cell(arch, shape_name, args.mesh, args.out)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        run_cell(args.arch, args.shape, args.mesh, args.out)


if __name__ == "__main__":
    main()
