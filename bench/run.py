"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
their files under ``bench/`` say what to build and what to send.  One
process: the model is built on the device from ``--seed`` with the
benchmark's weights, the program's ``BatchEngine`` serves it behind its
``ServingPipeline`` and ``CompletionServer`` on a localhost port, set-up
warms every shape the mix uses, and client threads drive the window over
HTTP/SSE.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window with the JAX profiler and reports its
per-layer metrics.  After the window the served tokens of a sample of
requests are checked against the plain float32 reference.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``check``: each number compared with its limit).  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes only
# inside its checkout and its own HOME/TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

TRACE_SECONDS = 20.0  # longest stretch of the window that is traced
FIRST_WAVE_TIMEOUT = 900.0  # set-up admissions
REQUEST_TIMEOUT = 600.0  # a client's socket timeout, past the window
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        say(f"error: needs a TPU, JAX found {devs[0].platform!r}")
        return 2
    if len(devs) < cell.chips:
        say(f"error: {cell.name} needs {cell.chips} chips, JAX sees "
            f"{len(devs)}")
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=T_START, devices=devs[:cell.chips])
    for name, c in result["check"].items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


class CompileClock:
    """Times of the compilations in this process: XLA backend compiles
    and programs loaded from the persistent cache (either is a new
    executable), and, apart, traces of Python functions."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax

        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name, secs, **_):
        if name in (self.COMPILE, self.TRACE):
            self.events.append((time.perf_counter(), name))

    def _on_event(self, name, **_):
        if name == self.CACHE_HIT:
            self.events.append((time.perf_counter(), name))

    def between(self, t0: float, t1: float) -> dict:
        out = {"compiled": 0, "loaded_from_cache": 0, "traced": 0}
        key = {self.COMPILE: "compiled", self.CACHE_HIT: "loaded_from_cache",
               self.TRACE: "traced"}
        for t, name in self.events:
            if t0 <= t < t1:
                out[key[name]] += 1
        return out


def _enable_compile_cache() -> str:
    import jax
    from repro.launch.serve import enable_compile_cache

    path = enable_compile_cache()
    # cache every program, however fast it compiled: set-up then does
    # the same work on every run after a cell's first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@dataclasses.dataclass
class Window:
    driver: Any  # bench.client.ClosedLoop
    t_open: float
    t_close: float
    prof: Any = None  # bench.devtrace.Profiler of a traced window


def drive(sysm, plan, seconds: float, trace: bool) -> Window:
    """Open the window on a serving system and drive it for ``seconds``.

    The clients start, and the window opens once every client's first
    request decodes.  After the close no request is sent."""
    from bench import client, devtrace

    host, port = sysm.url_parts
    driver = client.ClosedLoop(host, port, plan,
                               seconds + REQUEST_TIMEOUT).start()
    deadline = time.monotonic() + FIRST_WAVE_TIMEOUT
    while not driver.all_decoding():
        if time.monotonic() > deadline:
            raise RuntimeError("set-up: the first wave never decoded")
        time.sleep(0.01)
    t_open = time.perf_counter()
    t_close = t_open + seconds
    prof = None
    if trace:
        prof = devtrace.Profiler(TRACE_DIR)
        prof.start()
        time.sleep(max(min(t_close, t_open + TRACE_SECONDS)
                       - time.perf_counter(), 0.0))
        prof.stop()
    time.sleep(max(t_close - time.perf_counter(), 0.0))
    driver.stop.set()
    return Window(driver, t_open, t_close, prof)


def execute(cell, seed: int, seconds: float, trace: bool, *, t_start: float,
            devices, control: bool = False) -> dict:
    """Build, warm, serve, measure and check one run of ``cell``.
    ``control`` also reads the control on the checked sample (the
    calibration of the limits; benchmark runs never do)."""
    from bench import check, devtrace, readers, system
    from bench.traffic import generator

    conf = cell.config
    cache_dir = _enable_compile_cache()
    clock = CompileClock()
    plan = generator.generate(cell.traffic, seed, conf["vocab_size"])
    t = time.perf_counter()
    sysm = system.build(conf, seed, plan)
    t_built = time.perf_counter()
    n_warm = system.warm_up(sysm, plan, seed)
    t_warm = time.perf_counter()
    system.serve(sysm)
    win = drive(sysm, plan, seconds, trace)
    t_open, t_close = win.t_open, win.t_close
    setup_s = t_open - t_start
    in_window = clock.between(t_open, t_close)
    spans = readers.program_spans(sysm.engine.trace)
    system.stop(sysm)
    win.driver.join(timeout=30.0)
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    ctx = readers.Context(
        cfg=sysm.cfg, device_kind=devices[0].device_kind,
        window=(t_open, t_close), spans=spans, steps=sysm.steps,
        records=list(win.driver.records),
        trace=win.prof.load() if win.prof is not None else None,
        setup_s=setup_s)
    attempted = readers.sent_in_window(ctx)
    failed = [r for r in attempted if r.error]
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        read = spec.metric_reader(m["name"], "metrics" if trace else "e2e")
        value = read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    say(f"cell {cell.name} seed {seed}: device {devices[0].device_kind} "
        f"x{len(devices)}, compile cache {cache_dir}")
    say(f"set-up {setup_s:.3f} s: engine built in {t_built - t:.3f} s, "
        f"{n_warm} warm-up requests in {t_warm - t_built:.3f} s, "
        f"window opened {t_open - t_warm:.3f} s later")
    say(f"compilations inside the window: {in_window['compiled']} "
        f"compiled, {in_window['loaded_from_cache']} loaded from the "
        f"compile cache ({in_window['traced']} Python traces, which "
        f"include the program's eager vmapped code)")
    say(f"requests: {len(attempted)} attempted in the window, "
        f"{len(failed)} failed; peak device memory {mem_peak} bytes")
    if ctx.trace is not None:
        say(f"device trace: {ctx.trace.nbytes} bytes, window "
            f"{ctx.trace.window[1] - ctx.trace.window[0]:.3f} s")

    checked = check.run(cell, sysm, ctx.records, seed, plan, control=control)
    say(f"check: {checked['summary']}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(mem_peak)}
    result = {"correct": checked["correct"], "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics, "device": device}
    if ctx.trace is not None:
        device["busy_s"] = devtrace.busy_seconds(ctx.trace)
        device["window_s"] = ctx.trace.window[1] - ctx.trace.window[0]
        result["breakdown"] = readers.breakdown(ctx)
    result["check"] = checked["numbers"]
    return result


if __name__ == "__main__":
    sys.exit(main())
