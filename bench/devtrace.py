"""The device trace of a traced run: the JAX profiler's trace of the
measured window, read back with ``jax.profiler.ProfileData`` and put on
the host's ``perf_counter`` clock.

At start a ``TraceAnnotation`` is opened and closed around a
``perf_counter`` reading; its position in the trace's host plane ties
the trace's nanoseconds to the host clock, so the program's spans (which
the engine's trace recorder takes on ``perf_counter``) can be laid over
the device's operations.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import time

ANCHOR = "bench.anchor"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    t0: float  # perf_counter seconds
    t1: float


@dataclasses.dataclass
class DeviceTrace:
    window: tuple  # (t0, t1) of the traced window, perf_counter seconds
    ops: dict  # device name -> [Event] of the "XLA Ops" line
    modules: dict  # device name -> [Event] of the "XLA Modules" line
    nbytes: int  # size of the trace file

    def all_ops(self) -> list:
        return [e for evs in self.ops.values() for e in evs]


class Profiler:
    """Start and stop the JAX profiler around a window; ``load`` reads
    the trace and deletes it from disk."""

    def __init__(self, directory: str):
        self.dir = directory

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation(ANCHOR):
            self.t_anchor = time.perf_counter()
        self.t_start = self.t_anchor

    def stop(self) -> None:
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def load(self) -> DeviceTrace:
        from jax.profiler import ProfileData

        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        nbytes = os.path.getsize(files[-1])
        data = ProfileData.from_file(files[-1])
        anchor_ns = None
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor_ns = ev.start_ns
        if anchor_ns is None:
            raise RuntimeError("the trace holds no anchor annotation")

        def clock(ns):
            return self.t_anchor + (ns - anchor_ns) * 1e-9

        ops, modules = {}, {}
        for plane in data.planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [Event(ev.name, clock(ev.start_ns),
                             clock(ev.start_ns + ev.duration_ns))
                       for ev in line.events]
                (ops if line.name == OPS_LINE else modules)[plane.name] = evs
        shutil.rmtree(self.dir, ignore_errors=True)
        return DeviceTrace((self.t_start, self.t_stop), ops, modules, nbytes)


def op_name(name: str) -> str:
    """The HLO instruction of an "XLA Ops" event, whose name is the
    instruction's text: ``%<instruction> = <shape> <opcode>(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaf_ops(events) -> list:
    """The ops that hold no other op: on the "XLA Ops" line a loop's
    event spans the ops of its body, which have events of their own."""
    evs = sorted(events, key=lambda e: (e.t0, -e.t1))
    parents, open_ = set(), []
    for i, e in enumerate(evs):
        while open_ and evs[open_[-1]].t1 <= e.t0:
            open_.pop()
        if open_ and e.t1 <= evs[open_[-1]].t1:
            parents.add(open_[-1])
        open_.append(i)
    return [e for i, e in enumerate(evs) if i not in parents]


def union_seconds(events, t0: float, t1: float) -> float:
    """Length of the union of the events' intervals, clipped to
    ``[t0, t1]``."""
    iv = sorted((max(e.t0, t0), min(e.t1, t1)) for e in events
                if e.t1 > t0 and e.t0 < t1)
    busy, end = 0.0, t0
    for a, b in iv:
        if b <= end:
            continue
        busy += b - max(a, end)
        end = b
    return busy


def busy_seconds(trace: DeviceTrace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    t0, t1 = trace.window
    per = [union_seconds(evs, t0, t1) for evs in trace.ops.values()]
    return sum(per) / len(per) if per else 0.0


def idle_gaps(events, t0: float, t1: float) -> list:
    """(start, end) of each stretch of ``[t0, t1]`` with no event."""
    gaps, end = [], t0
    for e in sorted(events, key=lambda e: e.t0):
        if e.t0 > end:
            gaps.append((end, min(e.t0, t1)))
        end = max(end, e.t1)
        if end >= t1:
            break
    if end < t1:
        gaps.append((end, t1))
    return [g for g in gaps if g[1] > g[0]]
