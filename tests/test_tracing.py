"""Request-scoped tracing + engine flight recorder (DESIGN.md §15).

Correctness bar, in three layers:

* **Recorder unit contract** -- bounded ring (drop-oldest, capacity
  validated), disabled recorder is a no-op, Chrome trace-event export
  shape (``X``/``i``/``b``/``e``/``M`` phases, microsecond timestamps,
  thread tracks), ``last_s`` flight-recorder windowing, per-request
  lifecycle marks folding into the ``timing`` breakdown.
* **Zero-interference** -- token streams with tracing ON must be
  byte-identical to tracing OFF (instrumentation is host-side timing
  only; no device work or PRNG stream may move).  The heavy sweep
  covers every policy x dense/paged; a light single-policy parity test
  runs in the fast lane.
* **Exported structure** -- a traced pipeline run must pass
  ``benchmarks/check_trace.py``: spans nest per thread, every streamed
  token falls inside its request's async span, the buffer honored its
  bound.  The validator itself is tested against hand-built defective
  traces so it cannot silently pass garbage.

Plus the observability satellites: strict-Prometheus ``/metrics``
rendering (HELP/TYPE per family, sanitized names, labelled tier
counters) and the spec-decode rejection counter.
"""
import gc
import importlib.util
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.paper_models import SMOL_D64
from repro.core.cache_api import available_policies
from repro.kernels.quant_attention.quant_attention import paged_tile_pages
from repro.launch.batch_engine import BatchEngine, Request
from repro.launch.server import (
    ServingPipeline,
    SyncServer,
    TraceRecorder,
    make_requests,
)
from repro.launch.server import tracing
from repro.launch.server.pipeline import drain_stream
from repro.launch.server.stats import ServerMetrics, sanitize_metric_name
from repro.models import build_model


def _load_check_trace():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "check_trace.py")
    spec = importlib.util.spec_from_file_location("check_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check_trace = _load_check_trace().check_trace

S_MAX = 48
CAPACITY = 3


@pytest.fixture(scope="module")
def lm():
    model = build_model(SMOL_D64)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _mk_engine(model, params, *, policy="bf16", paged=False,
               capacity=CAPACITY, s_max=S_MAX, **kw):
    if paged:
        kw.setdefault("page_size", 16)
    return BatchEngine(model, params, capacity=capacity, s_max=s_max,
                       policy=policy, backend="gather", chunk=4,
                       key=jax.random.PRNGKey(7), paged=paged, **kw)


def _requests(model, n, *, policy, new_tokens=4):
    window = getattr(model.cache_policy(policy), "window", 1)
    return make_requests(n, prompt_len=32, new_tokens=new_tokens,
                         seed=0, align=window, run_len=2)


# --------------------------------------------------------------------------
# recorder unit contract
# --------------------------------------------------------------------------
def test_capacity_validation():
    for bad in (0, -1, -100):
        with pytest.raises(ValueError, match="capacity"):
            TraceRecorder(capacity=bad)


def test_ring_drops_oldest_and_counts():
    tr = TraceRecorder(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    names = [e["name"] for e in tr.export()["traceEvents"]
             if e["ph"] == "i"]
    assert names == ["e6", "e7", "e8", "e9"]  # oldest fell off first
    assert tr.export()["otherData"]["dropped"] == 6
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_disabled_recorder_is_noop():
    tr = TraceRecorder(capacity=8, enabled=False)
    with tr.span("s", cat="x", k=1):
        pass
    tr.span_at("s2", time.perf_counter())
    tr.instant("i")
    tr.req_mark(1, "submit")
    tr.req_add(1, "prefill_s", 0.5)
    tr.req_done(1)
    assert tr.req_timing(1) is None
    assert len(tr) == 0
    assert tr.export()["traceEvents"] == []


def test_span_and_span_at_record_durations():
    tr = TraceRecorder(capacity=16)
    with tr.span("ctx", cat="a", k=1):
        time.sleep(0.002)
    t0 = time.perf_counter()
    time.sleep(0.002)
    tr.span_at("at", t0, cat="b", rid=5)
    evs = [e for e in tr.export()["traceEvents"]
           if e["ph"] == "X" and e["cat"] != "runtime"]  # not a py.gc
    assert [e["name"] for e in evs] == ["ctx", "at"]
    for e in evs:
        assert e["dur"] >= 1500  # us: the sleep is visible
    assert evs[0]["cat"] == "a" and evs[0]["args"] == {"k": 1}
    assert evs[1]["args"] == {"rid": 5}


def test_export_chrome_trace_shape():
    tr = TraceRecorder(capacity=64)
    tr.req_mark(9, "submit")
    tr.instant("mark", cat="c", rid=9)
    tr.req_done(9)
    tr.req_timing(9)  # pop -> emits the "e" event
    out = tr.export()
    assert out["displayTimeUnit"] == "ms"
    od = out["otherData"]
    assert od["capacity"] == 64 and od["clock"] == "perf_counter"
    evs = out["traceEvents"]
    assert json.loads(json.dumps(out)) == out  # JSON-serializable
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
        assert e["pid"] == 1 and "tid" in e and "name" in e
        if e["ph"] != "M":
            assert isinstance(e["ts"], float)
            assert e["ts"] >= 0  # relative to recorder construction
    assert by_ph["i"][0]["s"] == "t"
    assert by_ph["b"][0]["id"] == 9 and by_ph["e"][0]["id"] == 9
    # one thread_name metadata event for the recording thread
    assert any(e["args"]["name"] for e in by_ph["M"])
    assert not check_trace(out)


def test_export_last_s_windows_the_ring():
    tr = TraceRecorder(capacity=64)
    tr.instant("old")
    time.sleep(0.05)
    tr.instant("new")
    full = tr.export()
    windowed = tr.export(last_s=0.03)
    names = [e["name"] for e in windowed["traceEvents"]
             if e["ph"] == "i"]
    assert names == ["new"]
    assert windowed["otherData"]["window_s"] == 0.03
    assert len(full["traceEvents"]) > len(windowed["traceEvents"])


def test_thread_tracks_are_tagged():
    tr = TraceRecorder(capacity=16)
    tr.instant("main-side")

    def other():
        tr.instant("other-side")

    t = threading.Thread(target=other, name="trace-test-worker")
    t.start()
    t.join()
    evs = tr.export()["traceEvents"]
    tids = {e["tid"] for e in evs if e["ph"] == "i"}
    assert len(tids) == 2
    meta = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert any("trace-test-worker" in n or "thread-" in n for n in meta)


def test_req_timing_breakdown_and_first_wins():
    tr = TraceRecorder(capacity=32)
    tr.req_mark(3, "submit")
    time.sleep(0.002)
    tr.req_mark(3, "admit")
    tr.req_add(3, "prefill_s", 0.25)
    tr.req_add(3, "prefill_s", 0.25)  # accumulates
    tr.req_mark(3, "first_token")
    first = None
    with tr._req_lock:
        first = tr._req[3]["first_token"]
    tr.req_mark(3, "first_token")  # preemption-resume: first wins
    with tr._req_lock:
        assert tr._req[3]["first_token"] == first
    time.sleep(0.002)
    tr.req_done(3)
    timing = tr.req_timing(3)
    assert set(timing) == {"queue_wait_s", "prefill_s", "decode_s",
                           "detok_s", "total_s"}
    assert timing["prefill_s"] == pytest.approx(0.5)
    assert timing["queue_wait_s"] >= 0.001
    assert timing["decode_s"] >= 0.001
    assert timing["total_s"] >= timing["queue_wait_s"]
    # popped: a second read finds nothing, unknown rids return None
    assert tr.req_timing(3) is None
    assert tr.req_timing(999) is None


def test_req_registry_bounded():
    tr = TraceRecorder(capacity=8)
    tr._req_cap = 4
    for rid in range(10):
        tr.req_mark(rid, "submit")
    with tr._req_lock:
        assert len(tr._req) == 4
        assert set(tr._req) == {6, 7, 8, 9}  # oldest evicted


def test_write_roundtrip(tmp_path):
    tr = TraceRecorder(capacity=16)
    tr.instant("x")
    path = str(tmp_path / "t.json")
    n = tr.write(path)
    with open(path) as f:
        obj = json.load(f)
    assert len(obj["traceEvents"]) == n
    assert not check_trace(obj)


def test_span_at_t1_shares_a_boundary():
    tr = TraceRecorder(capacity=16)
    t0 = time.perf_counter()
    t1 = t0 + 0.25
    tr.span_at("first", t0, t1=t1)
    tr.span_at("second", t1, t1=t1 + 0.5, k=2)
    evs = [e for e in tr.export()["traceEvents"]
           if e["ph"] == "X" and e["cat"] != "runtime"]
    a, b = evs
    assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-3)
    assert b["dur"] == pytest.approx(0.5e6, abs=1e-3)
    assert b["args"] == {"k": 2}  # t1 is not an arg
    assert not check_trace(tr.export())


# --------------------------------------------------------------------------
# runtime spans: JAX traces/lowerings/compiles and long GC pauses
# --------------------------------------------------------------------------
def _runtime(tr, name=None):
    return [e for e in tr.export()["traceEvents"]
            if e["ph"] == "X" and e["cat"] == "runtime"
            and (name is None or e["name"] == name)]


def test_runtime_spans_name_traces_and_compiles():
    tr = TraceRecorder(capacity=1 << 12)

    def runtime_span_probe(x):
        return x * 3 + 1

    t0 = time.perf_counter()
    jax.jit(runtime_span_probe)(np.arange(7.0)).block_until_ready()
    t1 = time.perf_counter()
    traced = [e for e in _runtime(tr, "jax.trace")
              if e["args"]["fun"] == "runtime_span_probe"]
    compiled = [e for e in _runtime(tr, "jax.compile")
                if "runtime_span_probe" in e["args"]["fun"]]
    lowered = [e for e in _runtime(tr, "jax.lower")
               if "runtime_span_probe" in e["args"]["fun"]]
    assert traced and compiled and lowered
    for e in traced + compiled + lowered:
        start = tr.t0 + e["ts"] * 1e-6
        assert t0 - 1e-3 <= start
        assert start + e["dur"] * 1e-6 <= t1 + 1e-3


def test_disabled_recorder_records_no_runtime_spans():
    on = TraceRecorder(capacity=1 << 12)  # the hooks fire meanwhile
    off = TraceRecorder(capacity=64, enabled=False)

    def disabled_probe(x):
        return x - 2

    jax.jit(disabled_probe)(np.arange(5.0)).block_until_ready()
    junk = [[i] for i in range(50_000)]
    gc.collect()
    del junk
    assert any(e["args"]["fun"] == "disabled_probe"
               for e in _runtime(on, "jax.trace"))
    assert len(off) == 0 and off.export()["traceEvents"] == []


def test_many_recorders_share_one_runtime_hook():
    recs = [TraceRecorder(capacity=8) for _ in range(50)]
    listeners = jax._src.monitoring.get_event_duration_listeners()
    assert sum(cb is tracing._on_jax_duration for cb in listeners) == 1
    assert sum(cb is tracing._on_gc for cb in gc.callbacks) == 1
    # each live enabled recorder is forwarded to; disabled ones are not
    live = tracing._runtime_recorders()
    assert all(r in live for r in recs)
    del recs, live
    gc.collect()


def test_long_gc_is_recorded_as_py_gc():
    tr = TraceRecorder(capacity=1 << 12)
    # enough tracked containers that a full collection takes > 1 ms
    junk = [[i] for i in range(400_000)]
    t0 = time.perf_counter()
    gc.collect()
    took = time.perf_counter() - t0
    del junk
    assert took > tracing.GC_MIN_S
    spans = [e for e in _runtime(tr, "py.gc") if e["args"]["gen"] == 2]
    assert spans, _runtime(tr)
    assert max(e["dur"] for e in spans) >= 1e6 * tracing.GC_MIN_S
    assert spans[-1]["tid"] == threading.get_ident()


# --------------------------------------------------------------------------
# the validator must reject hand-built garbage
# --------------------------------------------------------------------------
def _ev(name, ph, ts, *, dur=None, tid=1, args=None, **extra):
    e = {"name": name, "ph": ph, "ts": ts, "pid": 1, "tid": tid}
    if dur is not None:
        e["dur"] = dur
    if ph == "i":
        e.setdefault("s", "t")
    if ph in ("b", "e"):
        e["id"] = (args or {}).get("rid", 0)
    if args:
        e["args"] = args
    e.update(extra)
    return e


def test_check_trace_flags_overlapping_spans():
    bad = {"traceEvents": [
        _ev("a", "X", 0.0, dur=100.0),
        _ev("b", "X", 50.0, dur=100.0),  # overlaps a without nesting
    ], "otherData": {"capacity": 10, "dropped": 0}}
    assert any("overlaps" in p for p in check_trace(bad))
    ok = {"traceEvents": [
        _ev("a", "X", 0.0, dur=100.0),
        _ev("b", "X", 10.0, dur=50.0),  # nested
        _ev("c", "X", 200.0, dur=10.0),  # disjoint
    ], "otherData": {"capacity": 10, "dropped": 0}}
    assert not check_trace(ok)


def test_check_trace_flags_uncovered_tokens():
    span = [_ev("request", "b", 100.0, args={"rid": 1}),
            _ev("request", "e", 200.0, args={"rid": 1})]
    outside = {"traceEvents": span + [
        _ev("detok", "X", 300.0, dur=5.0, args={"rid": 1})],
        "otherData": {"capacity": 10, "dropped": 0}}
    assert any("outside" in p for p in check_trace(outside))
    # a batch that starts inside but runs past the request's end
    straddle = {"traceEvents": span + [
        _ev("detok", "X", 195.0, dur=20.0, args={"rid": 1})],
        "otherData": {"capacity": 10, "dropped": 0}}
    assert any("outside" in p for p in check_trace(straddle))
    inside = {"traceEvents": span + [
        _ev("detok", "X", 150.0, dur=5.0, args={"rid": 1})],
        "otherData": {"capacity": 10, "dropped": 0}}
    assert not check_trace(inside)
    # no "b" at all: a defect in a complete export...
    orphan = {"traceEvents": [
        _ev("detok", "X", 150.0, dur=5.0, args={"rid": 2})],
        "otherData": {"capacity": 10, "dropped": 0}}
    assert any("no request" in p for p in check_trace(orphan))
    # ...but tolerated when the ring dropped events or was windowed
    lossy = {"traceEvents": [
        _ev("detok", "X", 150.0, dur=5.0, args={"rid": 2})],
        "otherData": {"capacity": 10, "dropped": 5}}
    assert not check_trace(lossy)
    # in-flight request: open window extends to +inf
    inflight = {"traceEvents": [
        _ev("request", "b", 100.0, args={"rid": 3}),
        _ev("detok", "X", 500.0, dur=5.0, args={"rid": 3})],
        "otherData": {"capacity": 10, "dropped": 0}}
    assert not check_trace(inflight)


def test_check_trace_flags_malformed_shapes():
    assert check_trace([])  # not an object
    assert check_trace({"traceEvents": "nope"})
    assert check_trace({"traceEvents": [{"ph": "X", "ts": 0.0}]})
    bad_dur = {"traceEvents": [_ev("a", "X", 0.0, dur=-5.0)]}
    assert any("dur" in p for p in check_trace(bad_dur))
    over = {"traceEvents": [_ev(f"e{i}", "i", float(i))
                            for i in range(5)],
            "otherData": {"capacity": 3, "dropped": 0}}
    assert any("capacity" in p for p in check_trace(over))


# --------------------------------------------------------------------------
# zero-interference: tracing on/off streams are byte-identical
# --------------------------------------------------------------------------
def _pipeline_streams_traced(model, params, reqs, *, policy, paged,
                             enabled):
    eng = _mk_engine(model, params, policy=policy, paged=paged)
    trace = TraceRecorder(capacity=1 << 14, enabled=enabled)
    eng.trace = trace
    pipe = ServingPipeline(eng, max_group=eng.capacity,
                           admit_queue=max(len(reqs), 8), trace=trace)
    streams = {r.rid: pipe.submit(r) for r in reqs}
    pipe.start()
    out = {rid: drain_stream(q, timeout=120.0)
           for rid, q in streams.items()}
    assert pipe.shutdown(timeout=60.0)
    return out, trace


def test_streams_identical_tracing_on_off(lm):
    """Fast-lane single-config parity; the full policy x layout sweep
    is the slow test below."""
    model, params = lm
    reqs = _requests(model, 4, policy="int4-srft")
    on, trace = _pipeline_streams_traced(model, params, reqs,
                                         policy="int4-srft", paged=False,
                                         enabled=True)
    off, _ = _pipeline_streams_traced(model, params, reqs,
                                      policy="int4-srft", paged=False,
                                      enabled=False)
    assert on == off
    assert len(trace) > 0  # the ON run actually recorded


@pytest.mark.slow
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("policy", available_policies())
def test_streams_identical_tracing_on_off_all(lm, policy, paged):
    model, params = lm
    reqs = _requests(model, 6, policy=policy)
    on, _ = _pipeline_streams_traced(model, params, reqs, policy=policy,
                                     paged=paged, enabled=True)
    off, _ = _pipeline_streams_traced(model, params, reqs, policy=policy,
                                      paged=paged, enabled=False)
    assert set(on) == set(off)
    for rid in off:
        assert on[rid] == off[rid], (
            f"rid {rid}: tracing-on {on[rid]} != tracing-off {off[rid]}"
        )


# --------------------------------------------------------------------------
# a traced pipeline run exports valid, covered, timed structure
# --------------------------------------------------------------------------
def _drain_events(q, timeout=120.0):
    evs = []
    deadline = time.monotonic() + timeout
    while True:
        ev = q.get(timeout=max(deadline - time.monotonic(), 0.001))
        evs.append(ev)
        if ev.finish_reason is not None:
            return evs


def test_pipeline_trace_validates_and_carries_timing(lm):
    model, params = lm
    reqs = _requests(model, 4, policy="bf16")
    eng = _mk_engine(model, params, policy="bf16")
    trace = TraceRecorder(capacity=1 << 14)
    eng.trace = trace
    pipe = ServingPipeline(eng, max_group=eng.capacity,
                           admit_queue=8, trace=trace)
    streams = {r.rid: pipe.submit(r) for r in reqs}
    pipe.start()
    events = {rid: _drain_events(q) for rid, q in streams.items()}
    assert pipe.shutdown(timeout=60.0)

    # every final StreamEvent carries the timing breakdown, and the
    # SSE payload mirrors it (what http.py writes to the wire)
    for rid, evs in events.items():
        final = evs[-1]
        assert final.finish_reason == "length"
        timing = final.timing
        assert timing is not None, f"rid {rid}: no timing on final event"
        assert set(timing) == {"queue_wait_s", "prefill_s", "decode_s",
                               "detok_s", "total_s"}
        assert all(v >= 0 for v in timing.values())
        assert timing["total_s"] > 0
        assert json.loads(final.sse)["timing"] == timing

    out = trace.export()
    problems = check_trace(out)
    assert not problems, "\n".join(problems)
    names = {e["name"] for e in out["traceEvents"]}
    for need in ("request", "req.submit", "detok", "engine.step",
                 "decode.chunk", "decode.dispatch", "decode.wait",
                 "decode.post", "req.retire"):
        assert need in names, f"missing {need!r} (have {sorted(names)})"
    assert "tok.stream" not in names  # detok spans carry the batches
    assert names & {"engine.prefill", "prefill.packed", "prefill.chunk"}
    # one async b/e pair per request
    b = [e for e in out["traceEvents"] if e["ph"] == "b"]
    e_ = [e for e in out["traceEvents"] if e["ph"] == "e"]
    assert {x["id"] for x in b} == {r.rid for r in reqs}
    assert {x["id"] for x in e_} == {r.rid for r in reqs}


def test_sync_server_records_through_same_recorder(lm):
    model, params = lm
    reqs = _requests(model, 2, policy="bf16")
    eng = _mk_engine(model, params, policy="bf16")
    srv = SyncServer(eng, max_group=eng.capacity)
    assert srv.trace.enabled  # on by default
    assert eng.trace is srv.trace  # one recorder per serving stack
    streams = {r.rid: srv.submit(r) for r in reqs}
    srv.run_until_drained()
    for q in streams.values():
        drain_stream(q, timeout=10.0)
    srv.close()
    assert not check_trace(srv.trace.export())


def test_pipeline_adopts_enabled_engine_recorder(lm):
    model, params = lm
    eng = _mk_engine(model, params, policy="bf16")
    mine = TraceRecorder(capacity=128)
    eng.trace = mine
    pipe = ServingPipeline(eng, admit_queue=4)
    assert pipe.trace is mine  # adopted, not replaced
    eng.step_listeners.clear()
    # a disabled engine default gets upgraded to a live recorder
    eng2 = _mk_engine(model, params, policy="bf16")
    assert not eng2.trace.enabled
    pipe2 = ServingPipeline(eng2, admit_queue=4)
    assert pipe2.trace.enabled and eng2.trace is pipe2.trace
    # ...unless the caller pins one explicitly (serve.py --no-trace)
    eng3 = _mk_engine(model, params, policy="bf16")
    off = TraceRecorder(capacity=1, enabled=False)
    pipe3 = ServingPipeline(eng3, admit_queue=4, trace=off)
    assert pipe3.trace is off and not pipe3.trace.enabled
    eng2.step_listeners.clear()
    eng3.step_listeners.clear()


def _nested_in(inner, outer) -> bool:
    return (outer["ts"] - 1.0 <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1.0)


def test_engine_step_records_leaf_spans(lm):
    """One step with an admission: the admission's and the decode
    quantum's leaf spans, each inside the step's engine.step span."""
    model, params = lm
    tr = TraceRecorder(capacity=1 << 14)
    eng = _mk_engine(model, params, policy="int4-srft", paged=True,
                     prefill_chunk=32, trace=tr)
    prompt = np.arange(32, dtype=np.int32) % SMOL_D64.vocab_size
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    eng.step()
    out = tr.export()
    assert not check_trace(out)
    evs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    (step,) = [e for e in evs if e["name"] == "engine.step"]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    leaves = ("admit.start", "prefill.chunk", "admit.insert",
              "decode.dispatch", "decode.wait", "decode.chunk",
              "decode.post")
    for name in leaves:
        assert name in by, f"missing {name!r} (have {sorted(by)})"
        for e in by[name]:
            assert _nested_in(e, step), name
            assert e["tid"] == step["tid"]
    # in order, and the quantum splits into dispatch and wait
    starts = [by[n][0]["ts"] for n in leaves if n != "decode.chunk"]
    assert starts == sorted(starts)
    (chunk,) = by["decode.chunk"]
    (disp,) = by["decode.dispatch"]
    (wait,) = by["decode.wait"]
    (post,) = by["decode.post"]
    assert disp["ts"] == chunk["ts"]
    assert wait["ts"] == pytest.approx(disp["ts"] + disp["dur"], abs=1e-2)
    assert wait["ts"] + wait["dur"] == pytest.approx(
        chunk["ts"] + chunk["dur"], abs=1e-2)
    assert post["ts"] == pytest.approx(chunk["ts"] + chunk["dur"], abs=1e-2)
    assert chunk["args"] == {"steps": 4, "rows": 1, "capacity": CAPACITY,
                             "spec": False}
    assert by["admit.insert"][0]["args"]["inserted"] is True
    # the quantum's first call compiled inside its dispatch
    compiles = [e for e in _runtime(tr, "jax.compile")
                if e["args"]["fun"] == "jit(decode_quantum)"]
    assert compiles and all(_nested_in(e, disp) for e in compiles)


def test_decode_chunk_counts_live_kernel_tiles(lm):
    """On the paged kernel path each ``decode.chunk`` span carries the
    kernel's live and total grid tiles at the quantum's first step, from
    the host's own lengths: they agree with the device's row lengths,
    including a quantum whose first step lands exactly on a flush."""
    model, params = lm
    tr = TraceRecorder(capacity=1 << 14)
    # 20 pages a row: 16-page (256-token) tiles, two a row
    eng = BatchEngine(model, params, capacity=3, s_max=320,
                      policy="int4-srft", backend="kernel", chunk=4,
                      key=jax.random.PRNGKey(7), paged=True, page_size=16,
                      trace=tr)
    W = model.cache_policy("int4-srft").window
    tile = paged_tile_pages(16, eng.max_pages) * 16
    assert (W, tile) == (16, 256)
    # the long row's quanta start at 268, 272 (a flush), 276 tokens
    for rid, n in enumerate((267, 30)):
        eng.submit(Request(rid=rid, prompt=np.arange(n, dtype=np.int32)
                           % SMOL_D64.vocab_size, max_new_tokens=20))
    expected = []
    for _ in range(3):
        eng.step()
        after = np.asarray(eng.cache["attn"].data.kv.length[0])
        first = after[:2] - 4 + 1  # both rows ran the whole quantum
        expected.append(int(sum(-(-(n - n % W) // tile) for n in first)))
    chunks = [e["args"] for e in tr.export()["traceEvents"]
              if e["name"] == "decode.chunk"]
    assert [c["kv_tiles_live"] for c in chunks] == expected == [2, 3, 3]
    assert all(c["kv_tiles_grid"] == 3 * 2 for c in chunks)


def test_admit_hold_is_one_span_per_hold(lm):
    """A partial group that arrives while the engine decodes is held
    for up to ``admit_hold_s`` in ~1 ms beats; the hold is one span from
    its first beat to the admit, not an instant per beat."""
    model, params = lm
    eng = _mk_engine(model, params, policy="bf16")
    trace = TraceRecorder(capacity=1 << 14)
    eng.trace = trace
    pipe = ServingPipeline(eng, max_group=eng.capacity, admit_queue=8,
                           admit_hold_s=0.05, trace=trace).start()
    prompt = np.arange(16, dtype=np.int32)
    busy = pipe.submit(Request(rid=0, prompt=prompt, max_new_tokens=30))
    deadline = time.monotonic() + 60.0
    while not eng.active.any():  # the engine is decoding
        assert time.monotonic() < deadline
        time.sleep(0.001)
    held = pipe.submit(Request(rid=1, prompt=prompt, max_new_tokens=2))
    drain_stream(held, timeout=60.0)
    drain_stream(busy, timeout=60.0)
    assert pipe.shutdown(timeout=60.0)
    out = trace.export()
    assert not check_trace(out)
    holds = [e for e in out["traceEvents"] if e["name"] == "admit.hold"]
    assert holds and all(e["ph"] == "X" for e in holds)
    assert len(holds) <= 2  # one per admission at most
    assert max(e["dur"] for e in holds) >= 10_000  # us: beats merged
    assert set(holds[0]["args"]) == {"head_group", "depth"}


# --------------------------------------------------------------------------
# satellites: tier attribution, spec rejection counter, strict /metrics
# --------------------------------------------------------------------------
def test_tier_outcome_attribution_dense(lm):
    model, params = lm
    reqs = _requests(model, 3, policy="bf16")
    eng = _mk_engine(model, params, policy="bf16")
    for _ in eng.run(reqs):
        pass
    assert set(eng.tier_outcomes) == {"none"}  # dense: no prefix tiers
    assert eng.tier_outcomes["none"] == {"length": 3}


def test_tier_outcome_attribution_paged(lm):
    model, params = lm
    reqs = _requests(model, 4, policy="int4-srft")
    eng = _mk_engine(model, params, policy="int4-srft", paged=True)
    for _ in eng.run(reqs):
        pass
    total = sum(n for byo in eng.tier_outcomes.values()
                for n in byo.values())
    assert total == len(reqs)
    assert set(eng.tier_outcomes) <= {"device", "host", "miss", "none"}
    for byo in eng.tier_outcomes.values():
        assert set(byo) <= {"length", "eos", "cancelled"}


def test_spec_rejected_counter(lm):
    model, params = lm
    eng = _mk_engine(model, params, policy="bf16")
    assert eng.n_rejected == 0  # no spec decoding configured
    spec = _mk_engine(model, params, policy="bf16", spec_k=2)
    reqs = _requests(model, 2, policy="bf16", new_tokens=6)
    for _ in spec.run(reqs):
        pass
    assert spec.n_drafted > 0
    assert spec.n_rejected == spec.n_drafted - spec.n_accepted
    assert spec.n_rejected >= 0
    eng.step_listeners.clear()


def test_sanitize_metric_name():
    assert sanitize_metric_name("ok_name:x9") == "ok_name:x9"
    assert sanitize_metric_name("bad-name.x") == "bad_name_x"
    assert sanitize_metric_name("0starts_bad") == "_0starts_bad"
    assert sanitize_metric_name("") == "_"


def test_render_prometheus_labeled_families():
    m = ServerMetrics()
    text = m.render_prometheus(labeled={
        "prefix_tier_requests_total": (
            "counter", "Requests by tier and outcome",
            [({"tier": "host", "outcome": "length"}, 3),
             ({"tier": "miss", "outcome": 'quo"te'}, 1)],
        ),
    })
    assert "# HELP server_prefix_tier_requests_total " \
           "Requests by tier and outcome" in text
    assert "# TYPE server_prefix_tier_requests_total counter" in text
    # labels render sorted by key, values escaped
    assert 'server_prefix_tier_requests_total' \
           '{outcome="length",tier="host"} 3' in text
    assert r'{outcome="quo\"te",tier="miss"} 1' in text


def _parse_prometheus_strict(text):
    """Minimal strict parser: every sample must belong to a family
    declared by HELP+TYPE above it, and every name must match the
    Prometheus charset."""
    import re
    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    families: dict[str, str] = {}
    helped: set[str] = set()
    n_samples = 0
    for line in text.strip().split("\n"):
        if line.startswith("# HELP "):
            fam = line.split()[2]
            assert name_re.match(fam), f"bad family name {fam!r}"
            helped.add(fam)
        elif line.startswith("# TYPE "):
            _, _, fam, typ = line.split(None, 3)
            assert typ in ("counter", "gauge", "summary", "histogram")
            assert fam in helped, f"TYPE before HELP for {fam}"
            families[fam] = typ
        else:
            assert not line.startswith("#"), f"stray comment: {line!r}"
            sample_name = re.split(r"[{\s]", line, 1)[0]
            assert name_re.match(sample_name), (
                f"bad sample name {sample_name!r}"
            )
            base = sample_name
            for suffix in ("_count", "_sum"):
                if sample_name.endswith(suffix) \
                        and sample_name[: -len(suffix)] in families:
                    base = sample_name[: -len(suffix)]
            assert base in families, f"undeclared family for {line!r}"
            float(line.rsplit(None, 1)[1])  # value parses
            n_samples += 1
    return families, n_samples


def test_metrics_text_is_strict_prometheus(lm):
    model, params = lm
    eng = _mk_engine(model, params, policy="int4-srft", paged=True)
    reqs = _requests(model, 3, policy="int4-srft")
    pipe = ServingPipeline(eng, max_group=eng.capacity, admit_queue=8)
    streams = {r.rid: pipe.submit(r) for r in reqs}
    pipe.start()
    for q in streams.values():
        drain_stream(q, timeout=120.0)
    assert pipe.shutdown(timeout=60.0)
    text = pipe.metrics_text()
    families, n_samples = _parse_prometheus_strict(text)
    assert n_samples > 10
    # counters typed counter, point-in-time values typed gauge
    assert families["server_requests_completed_total"] == "counter"
    assert families["server_ttft_seconds"] == "summary"
    assert families["server_slots_active"] == "gauge"
    assert families["server_trace_events"] == "gauge"
    assert families["server_trace_dropped_total"] == "counter"
    # tier attribution rendered as a labelled counter family
    assert families["server_prefix_tier_requests_total"] == "counter"
    assert 'server_prefix_tier_requests_total{outcome="length"' in text
