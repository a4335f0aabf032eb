"""Reductions over what the program names itself: its jitted programs
on the device trace's "XLA Modules" line (each engine program is jitted
under a fixed name, so its module is ``jit_<name>``), the slots its
decode quanta fill, and the host runtime's stalls (``jax.*`` and
``py.gc`` spans, recorded by the program's trace recorder).

A program older than these names and spans gives none of them to read:
then every reader here returns ``None``.
"""
from __future__ import annotations

import re
from typing import Optional

from bench import devtrace, readers

DECODE_QUANTUM = "jit_decode_quantum"
STALLS = ("jax.trace", "jax.lower", "jax.compile", "py.gc")
# a span the engine records only where it records runtime stalls too
STALL_MARKER = "decode.dispatch"

_NAME = re.compile(r"[A-Za-z0-9_]*")


def program_name(event_name: str) -> str:
    """A module event's program: its name up to the first character
    that cannot occur in one (an id or a suffix may follow)."""
    return _NAME.match(event_name).group()


def decode_device_ms(ctx: readers.Context) -> Optional[float]:
    """Device time of the decode quantum's module per decode step, over
    the quanta inside the traced window; an event belongs to a quantum
    when it starts between the quantum's dispatch and its step record.
    With several devices, their mean."""
    if ctx.trace is None:
        return None
    per_dev = [[e for e in evs if program_name(e.name) == DECODE_QUANTUM]
               for evs in ctx.trace.modules.values()]
    per_dev = [evs for evs in per_dev if evs]
    if not per_dev:
        return None
    qs = readers.quanta_within(ctx, *ctx.trace.window)
    steps = sum(q.chunk["args"]["steps"] for q in qs)
    if not steps:
        return None
    dev = sum(e.t1 - e.t0 for evs in per_dev for q in qs for e in evs
              if q.chunk["t0"] <= e.t0 <= q.t_step) / len(per_dev)
    return 1e3 * dev / steps


def slot_occupancy(ctx: readers.Context) -> Optional[float]:
    """Share of the slot-steps the decode quanta in the window ran with
    a live row, %: sum(rows x steps) / sum(capacity x steps)."""
    t0, t1 = ctx.window
    rows = slots = 0
    for s in readers.spans_named(ctx, "decode.chunk"):
        a = s["args"]
        if t0 <= s["t0"] and s["t1"] <= t1 and "capacity" in a:
            rows += a["rows"] * a["steps"]
            slots += a["capacity"] * a["steps"]
    if not slots:
        return None
    return 100.0 * rows / slots


def host_stall_ms(ctx: readers.Context) -> Optional[float]:
    """Host time stalled in the runtime: the union of the ``jax.*`` and
    ``py.gc`` spans that start inside the window, so a span nested in
    another (or overlapping one on another thread) counts once.  0.0
    when there was none."""
    if not readers.spans_named(ctx, STALL_MARKER):
        return None
    t0, t1 = ctx.window
    spans = [devtrace.Event(s["name"], s["t0"], s["t1"]) for s in ctx.spans
             if s["name"] in STALLS and t0 <= s["t0"] < t1]
    if not spans:
        return 0.0
    lo = min(e.t0 for e in spans)
    hi = max(e.t1 for e in spans)
    return 1e3 * devtrace.union_seconds(spans, lo, hi)
