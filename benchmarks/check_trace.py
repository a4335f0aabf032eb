"""Validate a Chrome trace-event JSON export from the flight recorder.

``TraceRecorder.export`` (DESIGN.md §15) promises a file that loads in
Perfetto / chrome://tracing AND carries enough structure to diagnose a
serving stall.  This checker enforces that contract so CI catches a
malformed exporter before a human pastes a broken file into a viewer:

1. **shape** -- ``traceEvents`` is a list of dicts, every event has
   ``name``/``ph``/``ts``/``pid``/``tid``, complete events (``"X"``)
   carry a non-negative ``dur``, instants carry a scope, async
   begin/end events carry an ``id``;
2. **nesting** -- per (pid, tid) track, complete events form a proper
   span tree: sorted by start (ties broken longest-first), every span
   either contains or is disjoint from its neighbours (1 us epsilon
   for clock rounding).  Overlap without containment means the
   exporter emitted garbage timestamps;
3. **request coverage** -- every ``detok`` span (one per streamed
   token batch) must fall inside its request's async ``b``/``e``
   window (matched by ``args.rid``): the recorder deliberately closes
   the request track only after the final tokens streamed, so a token
   batch outside its request span is an instrumentation bug.  A
   missing ``e`` means the request was in flight at snapshot time
   (open window tolerated); a missing ``b`` is tolerated only when
   the ring dropped events or the export was windowed
   (``otherData.dropped > 0`` / ``window_s``);
4. **bound** -- the buffer honored its capacity: recorded events in
   the file never exceed ``otherData.capacity`` (metadata ``M``
   events are synthesized at export and do not count).

Library use: ``problems = check_trace(obj)`` returns a list of
human-readable defects (empty = valid).  CLI use::

    python benchmarks/check_trace.py trace.json [more.json ...]

exits non-zero if any file fails.  server_smoke.py runs this over the
live ``/debug/trace`` snapshot, the SIGUSR1 flight dump and the final
``--trace-out`` file.
"""
from __future__ import annotations

import json
import sys

_EPS_US = 1.0  # clock-rounding tolerance for span containment


def _shape_problems(events) -> list[str]:
    out = []
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            out.append(f"event[{i}] is not an object: {ev!r}")
            continue
        for key in ("name", "ph", "pid", "tid"):
            if key not in ev:
                out.append(f"event[{i}] ({ev.get('name')!r}) missing {key!r}")
        ph = ev.get("ph")
        if ph != "M" and "ts" not in ev:
            out.append(f"event[{i}] ({ev.get('name')!r}) missing 'ts'")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                out.append(f"span[{i}] {ev.get('name')!r} bad dur: {dur!r}")
        elif ph == "i":
            if ev.get("s") not in ("t", "p", "g"):
                out.append(f"instant[{i}] {ev.get('name')!r} bad scope: "
                           f"{ev.get('s')!r}")
        elif ph in ("b", "e"):
            if "id" not in ev:
                out.append(f"async[{i}] {ev.get('name')!r} missing 'id'")
        elif ph not in ("M",):
            out.append(f"event[{i}] {ev.get('name')!r} unknown ph {ph!r}")
    return out


def _nesting_problems(events) -> list[str]:
    """Complete events on one thread must nest or be disjoint."""
    out = []
    tracks: dict[tuple, list] = {}
    for ev in events:
        if ev.get("ph") == "X" and isinstance(ev.get("dur"), (int, float)):
            key = (ev.get("pid"), ev.get("tid"))
            tracks.setdefault(key, []).append(ev)
    for key, spans in tracks.items():
        spans.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list = []  # (end_ts, name)
        for ev in spans:
            t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
            while stack and stack[-1][0] <= t0 + _EPS_US:
                stack.pop()
            if stack and t1 > stack[-1][0] + _EPS_US:
                out.append(
                    f"tid {key[1]}: span {ev['name']!r} "
                    f"[{t0:.1f}, {t1:.1f}]us overlaps enclosing "
                    f"{stack[-1][1]!r} ending at {stack[-1][0]:.1f}us "
                    f"without nesting"
                )
                continue
            stack.append((t1, ev["name"]))
    return out


def _coverage_problems(events, other) -> list[str]:
    """Every detok span lies inside its request's b/e window."""
    out = []
    lossy = bool(other.get("dropped")) or other.get("window_s") is not None
    begin: dict = {}
    end: dict = {}
    batches: list = []
    for ev in events:
        ph = ev.get("ph")
        if ph == "b" and ev.get("name") == "request":
            begin.setdefault(ev["id"], ev["ts"])
        elif ph == "e" and ev.get("name") == "request":
            end[ev["id"]] = ev["ts"]
        elif ph == "X" and ev.get("name") == "detok":
            batches.append(ev)
    for ev in batches:
        rid = (ev.get("args") or {}).get("rid")
        if rid is None:
            out.append(f"detok at {ev['ts']:.1f}us has no args.rid")
            continue
        if rid not in begin:
            if lossy:
                continue  # the 'b' fell off the ring / outside the window
            out.append(f"detok rid={rid} has no request 'b' event "
                       f"(and the export is complete: dropped=0, "
                       f"no window)")
            continue
        t0 = begin[rid]
        t1 = end.get(rid, float("inf"))  # in-flight at snapshot time
        s0, s1 = ev["ts"], ev["ts"] + ev["dur"]
        if not (t0 - _EPS_US <= s0 and s1 <= t1 + _EPS_US):
            out.append(f"detok rid={rid} [{s0:.1f}, {s1:.1f}]us outside "
                       f"its request span [{t0:.1f}, "
                       f"{'inf' if t1 == float('inf') else f'{t1:.1f}'}]us")
    for rid, t1 in end.items():
        if rid in begin and t1 + _EPS_US < begin[rid]:
            out.append(f"request rid={rid} ends ({t1:.1f}us) before it "
                       f"begins ({begin[rid]:.1f}us)")
    return out


def check_trace(obj) -> list[str]:
    """Return a list of human-readable defects (empty = valid)."""
    if not isinstance(obj, dict):
        return [f"top level is {type(obj).__name__}, expected object"]
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        return [f"traceEvents is {type(events).__name__}, expected list"]
    other = obj.get("otherData") or {}
    problems = _shape_problems(events)
    if problems:
        return problems  # structural defects make the rest unreliable
    problems += _nesting_problems(events)
    problems += _coverage_problems(events, other)
    cap = other.get("capacity")
    recorded = sum(1 for e in events if e.get("ph") != "M")
    if isinstance(cap, int) and recorded > cap:
        problems.append(f"{recorded} recorded events exceed the declared "
                        f"ring capacity {cap}")
    return problems


def check_trace_file(path: str) -> list[str]:
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: not readable JSON: {e}"]
    return check_trace(obj)


def main(argv: list[str]) -> int:
    if not argv:
        print("usage: check_trace.py trace.json [more.json ...]",
              file=sys.stderr)
        return 2
    failed = 0
    for path in argv:
        problems = check_trace_file(path)
        if problems:
            failed += 1
            print(f"[check_trace] FAIL {path}:")
            for p in problems:
                print(f"  - {p}")
        else:
            with open(path) as f:
                n = len(json.load(f)["traceEvents"])
            print(f"[check_trace] OK {path} ({n} events)")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
