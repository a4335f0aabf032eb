import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ["REPRO_UNROLL_SCANS"] = "1"

"""HLO attribution probe (§Perf profiling tool).

Parses the optimized per-device HLO of one reduced-depth unrolled cell
and attributes bytes/flops to op categories, answering 'what is the
memory term actually made of?' -- the dry-run analogue of a profiler
trace.  Top-K op lines by bytes are printed with their metadata source
lines so the fix target is visible.

    PYTHONPATH=src python -m repro.launch.hlo_probe --arch qwen3-14b \
        --shape train_4k [--layers 2] [--top 25]
"""
import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import re  # noqa: E402

from repro.configs import SHAPES, get_config  # noqa: E402
from repro.launch.dryrun import build_cell  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import _ARRAY_RE, _array_bytes  # noqa: E402

_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*([a-z0-9\[\],\s()]*?)"
                    r"([a-z][\w\-]*)\(")


def shapes_bytes(sig: str) -> int:
    return sum(_array_bytes(dt, dims) for dt, dims in _ARRAY_RE.findall(sig))


def analyze(hlo: str, top: int = 25, entry_only: bool = True):
    per_op = collections.Counter()
    per_op_count = collections.Counter()
    lines_by_bytes = []
    in_entry = not entry_only
    for line in hlo.splitlines():
        if entry_only:
            if line.startswith("ENTRY "):
                in_entry = True
                continue
            if in_entry and line.startswith("}"):
                in_entry = False
            if not in_entry:
                continue
        s = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", s)
        if not m:
            continue
        rhs = m.group(1)
        om = re.match(r"^([a-z0-9\[\],\s{}()]*?)\s*([a-z][\w\-]*)\(", rhs)
        if not om:
            continue
        opname = om.group(2)
        if opname in ("parameter", "constant", "tuple", "get-tuple-element"):
            continue
        # output shape(s): before the op name; operand shapes: inside parens
        out_b = shapes_bytes(om.group(1))
        args = rhs[om.end():]
        # operands are %name refs; their shapes are not inline in optimized
        # HLO text, so attribute OUTPUT bytes (lower bound, unambiguous).
        per_op[opname] += out_b
        per_op_count[opname] += 1
        meta = ""
        mm = re.search(r'op_name="([^"]+)"', rhs)
        if mm:
            meta = mm.group(1)[-90:]
        lines_by_bytes.append((out_b, opname, meta))
    lines_by_bytes.sort(reverse=True)
    return per_op, per_op_count, lines_by_bytes[:top]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if cfg.family in ("dense", "moe", "vlm"):
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    else:  # structural reductions per family (same rules as roofline_fit)
        from repro.launch.roofline_fit import depth_variants
        cfg = depth_variants(cfg)[0][0][0]
    mesh = make_production_mesh()
    with mesh:
        jfn, cell_args, *_ = build_cell(args.arch, args.shape, mesh, cfg=cfg)
        compiled = jfn.lower(*cell_args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    print(f"cost_analysis: flops/dev={cost.get('flops'):.4g} "
          f"bytes/dev={cost.get('bytes accessed'):.4g}")
    per_op, per_cnt, top_lines = analyze(compiled.as_text(), args.top)
    total = sum(per_op.values())
    print(f"\n-- OUTPUT bytes by op kind (total {total:.3g}) --")
    for op, b in per_op.most_common(18):
        print(f"  {op:24s} {b:.3e}  ({per_cnt[op]} ops)")
    print(f"\n-- top {args.top} single ops by output bytes --")
    for b, op, meta in top_lines:
        print(f"  {b:.3e}  {op:18s} {meta}")


if __name__ == "__main__":
    main()
