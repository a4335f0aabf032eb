"""What the per-layer readers (``bench/metrics/<metric>.py``) share: the
context of a traced run, and the reductions from its spans, step
records and device trace to counts and times.

A reader is ``read(ctx) -> float | None``: ``None`` when it finds
nothing to read (then the metric is left out of the result line).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from bench import devtrace, work


@dataclasses.dataclass
class Context:
    cfg: Any  # the program's ModelConfig, as run
    device_kind: str
    window: tuple  # (open, close) of the measured window, perf_counter s
    spans: list  # program spans and instants: dicts name/t0/t1/args
    steps: list  # engine steps: (t, [(rid, n_tokens)]) in order
    records: list  # client records (bench.client.Record)
    trace: Optional[devtrace.DeviceTrace] = None
    setup_s: float = 0.0

    @property
    def peak(self) -> dict:
        """Published peaks of this device kind (an unknown kind raises)."""
        return work.peaks(self.device_kind)


def program_spans(recorder) -> list:
    """The engine trace recorder's events on the perf_counter clock."""
    out = []
    for ev in recorder.export()["traceEvents"]:
        if ev["ph"] not in ("X", "i"):
            continue
        t0 = recorder.t0 + ev["ts"] * 1e-6
        out.append({"name": ev["name"], "t0": t0,
                    "t1": t0 + ev.get("dur", 0.0) * 1e-6,
                    "tid": ev["tid"], "args": ev.get("args") or {}})
    return out


def spans_named(ctx: Context, name: str) -> list:
    return [s for s in ctx.spans if s["name"] == name]


def prompt_lens(ctx: Context) -> dict:
    """rid -> prompt length, from the pipeline's ``req.submit`` marks."""
    return {s["args"]["rid"]: s["args"]["prompt_len"]
            for s in spans_named(ctx, "req.submit")}


@dataclasses.dataclass
class Quantum:
    """One engine step that decoded: its ``decode.chunk`` span and the
    context length of every token it decoded."""

    chunk: dict
    t_step: float
    contexts: list


def quanta(ctx: Context) -> list:
    """Every decoding engine step, in order.  A step record (taken by
    the engine's step listener right after the step) owns the
    ``decode.chunk`` span that ended since the previous record.  Token
    ``j`` of a request with an ``S``-token prompt (``j >= 1``; token 0
    comes from prefill) was decoded over ``S + j`` cached positions."""
    plen = prompt_lens(ctx)
    chunks = sorted(spans_named(ctx, "decode.chunk"), key=lambda s: s["t1"])
    seen: dict = {}
    out, ci, t_prev = [], 0, -np.inf
    for t, events in ctx.steps:
        chunk = None
        while ci < len(chunks) and chunks[ci]["t1"] <= t:
            if chunks[ci]["t1"] > t_prev:
                chunk = chunks[ci]
            ci += 1
        contexts = []
        for rid, n in events:
            c = seen.get(rid, 0)
            seen[rid] = c + n
            if rid in plen:
                contexts.extend(plen[rid] + j for j in range(max(c, 1), c + n))
        if chunk is not None:
            out.append(Quantum(chunk, t, contexts))
        t_prev = t
    return out


def quanta_within(ctx: Context, t0: float, t1: float) -> list:
    return [q for q in quanta(ctx)
            if q.chunk["t0"] >= t0 and q.t_step <= t1]


def decode_step_ms(ctx: Context) -> Optional[float]:
    qs = quanta_within(ctx, *ctx.window)
    steps = sum(q.chunk["args"]["steps"] for q in qs)
    if not steps:
        return None
    return 1e3 * sum(q.chunk["t1"] - q.chunk["t0"] for q in qs) / steps


def decode_mfu(ctx: Context) -> Optional[float]:
    """Decoded tokens' FLOPs over the decode quanta's time at peak, %."""
    qs = quanta_within(ctx, *ctx.window)
    t = sum(q.chunk["t1"] - q.chunk["t0"] for q in qs)
    flops = sum(work.decode_token_flops(ctx.cfg, c)
                for q in qs for c in q.contexts)
    if not t or not flops:
        return None
    return 100.0 * flops / (t * ctx.peak["bf16_flops_per_s"])


def kernel_events(ctx: Context, match) -> list:
    """The device ops whose name ``match`` accepts."""
    if ctx.trace is None:
        return []
    return [e for e in ctx.trace.all_ops() if match(e.name)]


def attn_roofline(ctx: Context, match) -> Optional[float]:
    """Least time of the int4 decode kernel's calls (bytes the algorithm
    needs, ``work.attn_row_bytes``) over the device time of its events,
    %, over the decode quanta that lie inside the traced window.  Every
    call is bound by bytes at these shapes (a few FLOP per byte against
    a ridge of ~240), so the sum of the calls' least times is the total's
    bound."""
    if ctx.trace is None:
        return None
    evs = kernel_events(ctx, match)
    qs = quanta_within(ctx, *ctx.trace.window)
    if not evs or not qs:
        return None
    dev = 0.0
    for q in qs:
        dev += sum(e.t1 - e.t0 for e in evs
                   if q.chunk["t0"] <= e.t0 <= q.t_step)
    nbytes = ctx.cfg.n_layers * sum(work.attn_row_bytes(ctx.cfg, c)
                                    for q in qs for c in q.contexts)
    flops = ctx.cfg.n_layers * sum(work.attn_row_flops(ctx.cfg, c)
                                   for q in qs for c in q.contexts)
    if not dev or not nbytes:
        return None
    return 100.0 * work.least_time(flops, nbytes, ctx.peak) / dev


def device_idle(ctx: Context) -> Optional[float]:
    if ctx.trace is None or not ctx.trace.ops:
        return None
    t0, t1 = ctx.trace.window
    return 100.0 * (1.0 - devtrace.busy_seconds(ctx.trace) / (t1 - t0))


def sent_in_window(ctx: Context) -> list:
    """The requests sent inside the window."""
    t0, t1 = ctx.window
    return [r for r in ctx.records if r.sent is not None
            and t0 <= r.sent < t1]


def breakdown(ctx: Context, top: int = 10) -> dict:
    """The device operations that took most time (leaf ops, named by
    the first 120 characters of their HLO text), and the longest idle
    gaps of the device, each named by the program spans open at its
    middle."""
    if ctx.trace is None or not ctx.trace.ops:
        return {}
    t0, t1 = ctx.trace.window
    dev = next(iter(sorted(ctx.trace.ops)))
    ops = ctx.trace.ops[dev]
    totals: dict = {}
    for e in devtrace.leaf_ops(ops):
        a, b = max(e.t0, t0), min(e.t1, t1)
        if b > a:
            totals[e.name[:120]] = totals.get(e.name[:120], 0.0) + (b - a)
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(devtrace.idle_gaps(ops, t0, t1), key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        mid = 0.5 * (a + b)
        open_ = sorted({s["name"] for s in ctx.spans
                        if s["t1"] > s["t0"] and s["t0"] <= mid <= s["t1"]})
        named.append([" + ".join(open_) or "no program span", b - a])
    return {"device_ops": [[n, s] for n, s in top_ops], "idle_gaps": named}
