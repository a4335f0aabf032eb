"""The readers of ``bench/programs.py`` (decode device time per step,
slot occupancy, host runtime stalls) on a hand-made window whose
answers are worked out by hand (tests/bench/data/program_window.json),
and on the older window of a program that records none of what they
read (tests/bench/data/small_window.json), where they read nothing."""
import json
import os
import types

import pytest

from bench import devtrace, programs, readers, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = types.SimpleNamespace(n_layers=2, d_model=64, head_dim=32, n_heads=4,
                            n_kv_heads=2, d_ff=128, vocab_size=256,
                            kv_window=16, kv_group=32)


def _ctx(name, **over):
    with open(os.path.join(HERE, "data", name)) as f:
        d = json.load(f)
    d.update(over)
    ev = lambda rows: [devtrace.Event(n, a, b) for n, a, b in rows]
    trace = devtrace.DeviceTrace(tuple(d["trace_window"]),
                                 {"/device:TPU:0": ev(d.get("ops", []))},
                                 {"/device:TPU:0": ev(d["modules"])}, 0)
    return readers.Context(
        cfg=CFG, device_kind="TPU v5 lite", window=tuple(d["window"]),
        spans=d["spans"], steps=[(t, [tuple(e) for e in evs])
                                 for t, evs in d["steps"]],
        records=[], trace=trace)


@pytest.fixture(scope="module")
def ctx():
    return _ctx("program_window.json")


@pytest.mark.parametrize("name, program", [
    ("jit_decode_quantum(7)", "jit_decode_quantum"),
    ("jit_decode_quantum.3", "jit_decode_quantum"),
    ("jit_decode_quantum", "jit_decode_quantum"),
    ("jit_decode_quantum_extra", "jit_decode_quantum_extra"),
    ("jit_spec_quantum(12)", "jit_spec_quantum"),
])
def test_program_name(name, program):
    assert programs.program_name(name) == program


def test_decode_device_ms(ctx):
    # the two quanta inside the traced window: 0.19 + 0.18 s of
    # jit_decode_quantum over 4 + 4 steps; neither the decoy nor the
    # prefill chunk counts, nor the third quantum (after the trace)
    got = spec.metric_reader("decode_device_ms.tput")(ctx)
    assert got == pytest.approx(1e3 * 0.37 / 8)


def test_slot_occupancy(ctx):
    # every quantum in the window, none before: rows x steps 2x4 + 1x4 +
    # 3x2 = 18 of capacity x steps 3x4 + 3x4 + 3x2 = 30
    got = spec.metric_reader("slot_occupancy.tput")(ctx)
    assert got == pytest.approx(100 * 18 / 30)


def test_host_stall_ms(ctx):
    # stalls that start in the window: [100.50, 100.52] holds the nested
    # trace, the compile on another thread reaches 100.53 (30 ms in all),
    # the gc 2 ms, the trace at the close runs on to 101.05 (100 ms); the
    # lowering that started before the window is left out
    got = spec.metric_reader("host_stall_ms.tput")(ctx)
    assert got == pytest.approx(30 + 2 + 100)


def test_host_stall_ms_reads_zero_without_stalls(ctx):
    quiet = _ctx("program_window.json", spans=[
        s for s in ctx.spans if s["name"] not in programs.STALLS])
    assert spec.metric_reader("host_stall_ms.tput")(quiet) == 0.0


@pytest.mark.parametrize("metric", ["decode_device_ms.tput",
                                    "slot_occupancy.tput",
                                    "host_stall_ms.tput"])
def test_readers_find_nothing_in_an_older_program(metric):
    # no capacity arg, no decode.dispatch, no named module: nothing to read
    old = _ctx("small_window.json")
    assert spec.metric_reader(metric)(old) is None


def test_decode_device_ms_needs_a_trace(ctx):
    untraced = readers.Context(cfg=CFG, device_kind="TPU v5 lite",
                               window=ctx.window, spans=ctx.spans,
                               steps=ctx.steps, records=[])
    assert programs.decode_device_ms(untraced) is None
