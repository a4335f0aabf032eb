"""Read the numbers that a cell's correctness limit is set from, on the
chip: for each seed, one run of the cell (a shorter window is enough to
finish and check as many tokens as a benchmark run does) that checks its
sample against the float32 reference and also reads the control, the
reference computed in fp8, on the same prompts and served tokens.

    python3 bench/calibrate.py --workload <cell> --seconds 20 \
        --seeds 11 12 13

Prints one JSON line per seed: the program's ``max_logit_gap`` (the
lower reading) and the control's ``control_max_gap`` (the upper).  The
benchmark's own runs never read the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (bench/run.py: sets up the import path)
from bench import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"error: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    t_start = T_START
    for seed in args.seeds:
        res = run.execute(cell, seed, args.seconds, False, t_start=t_start,
                          devices=devs[:cell.chips], control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "check": res["check"],
                          "metrics": res["metrics"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
