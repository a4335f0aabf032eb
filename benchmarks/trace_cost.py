"""Host cost of the engine's tracing, per ``engine.step``.

Drives a small ``BatchEngine`` (smol-d64, dense bf16, one decode step a
quantum, so the host's share of a step is as large as it gets) for
``--steps`` steps with an enabled ``TraceRecorder`` whose ``span_at`` is
timed from inside, and with the runtime hooks (the ``gc`` callback and
the JAX monitoring listener) timed the same way.  It reports, per
``engine.step``:

* ``new_spans_us``: time inside the ``span_at`` calls of the engine's
  leaf spans (``decode.dispatch``, ``decode.wait``, ``decode.post``);
* ``all_spans_us``: time inside every ``span_at`` call;
* ``gc_hook_us`` / ``jax_hook_us``: time inside the two hooks, and how
  often each fired;
* ``step_on_us`` / ``step_off_us``: median wall time of a step with the
  recorder on and off, in alternating blocks (a bound on the whole
  recorder's cost, with the device's own noise in it).

    PYTHONPATH=src python benchmarks/trace_cost.py [--steps 1200]

The last stdout line is one JSON object, with the device it ran on.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import time

import jax
import numpy as np

from repro.configs.paper_models import SMOL_D64
from repro.launch.batch_engine import BatchEngine, Request
from repro.launch.server import TraceRecorder
from repro.launch.server import tracing
from repro.models import build_model

NEW_SPANS = ("decode.dispatch", "decode.wait", "decode.post")


class Timer:
    def __init__(self):
        self.s = 0.0
        self.n = 0

    def wrap(self, fn):
        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                self.s += time.perf_counter() - t0
                self.n += 1
        return timed


def _refill(eng, rid: int, prompt, new_tokens: int) -> int:
    while eng.n_free_slots and not eng.pending:
        eng.submit(Request(rid=rid, prompt=prompt,
                           max_new_tokens=new_tokens))
        rid += 1
    return rid


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--steps", type=int, default=1200)
    ap.add_argument("--block", type=int, default=100)
    args = ap.parse_args(argv)

    model = build_model(SMOL_D64)
    params = model.init(jax.random.PRNGKey(0))
    on = TraceRecorder(capacity=1 << 16)
    off = TraceRecorder(capacity=1, enabled=False)
    eng = BatchEngine(model, params, capacity=4, s_max=512, policy="bf16",
                      chunk=1, key=jax.random.PRNGKey(7), trace=on)
    prompt = np.arange(32, dtype=np.int32) % SMOL_D64.vocab_size
    rid = 0
    # warm up: every program compiles before anything is timed
    rid = _refill(eng, rid, prompt, 64)
    for _ in range(80):
        rid = _refill(eng, rid, prompt, 64)
        eng.step()

    spans: dict = {}

    def span_at(name, t0, cat="server", **kw):
        t = time.perf_counter()
        try:
            return real_span_at(name, t0, cat, **kw)
        finally:
            spans[name] = spans.get(name, 0.0) + time.perf_counter() - t

    real_span_at = on.span_at
    on.span_at = span_at
    gc_t, jax_t = Timer(), Timer()
    gc.callbacks[:] = [gc_t.wrap(cb) if cb is tracing._on_gc else cb
                       for cb in gc.callbacks]
    listeners = jax._src.monitoring._event_duration_secs_listeners
    listeners[:] = [jax_t.wrap(cb) if cb is tracing._on_jax_duration
                    else cb for cb in listeners]

    walls = {True: [], False: []}
    n_on = 0
    for b in range(2 * (args.steps // args.block)):
        enabled = b % 2 == 0
        eng.trace = on if enabled else off
        for _ in range(args.block):
            rid = _refill(eng, rid, prompt, 64)
            t0 = time.perf_counter()
            eng.step()
            walls[enabled].append(time.perf_counter() - t0)
        n_on += args.block if enabled else 0

    dev = jax.devices()[0]
    us = lambda s: 1e6 * s / n_on  # noqa: E731
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "steps_traced": n_on,
        "new_spans_us": us(sum(spans.get(n, 0.0) for n in NEW_SPANS)),
        "all_spans_us": us(sum(spans.values())),
        "gc_hook_us": us(gc_t.s), "gc_hook_calls": gc_t.n,
        "jax_hook_us": us(jax_t.s), "jax_hook_calls": jax_t.n,
        "step_on_us": 1e6 * statistics.median(walls[True]),
        "step_off_us": 1e6 * statistics.median(walls[False]),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
