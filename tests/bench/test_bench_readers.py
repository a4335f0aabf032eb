"""The reductions from a traced run's spans, step records and device
trace to per-layer metrics, on a small window whose answers are worked
out by hand (tests/bench/data/small_window.json)."""
import json
import os
import types

import numpy as np
import pytest

from bench import devtrace, readers, spec
from bench.client import Record

HERE = os.path.dirname(os.path.abspath(__file__))

# internlm2-shaped at tiny widths
CFG = types.SimpleNamespace(n_layers=2, d_model=64, head_dim=32, n_heads=4,
                            n_kv_heads=2, d_ff=128, vocab_size=256,
                            kv_window=16, kv_group=32)


@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(HERE, "data", "small_window.json")) as f:
        d = json.load(f)
    ev = lambda rows: [devtrace.Event(n, a, b) for n, a, b in rows]
    trace = devtrace.DeviceTrace(tuple(d["trace_window"]),
                                 {"/device:TPU:0": ev(d["ops"])},
                                 {"/device:TPU:0": ev(d["modules"])}, 0)
    recs = []
    for i, r in enumerate(d["records"]):
        rec = Record(i, np.zeros(4, np.int32), 8, sent=r["sent"])
        recs.append(rec)
    return readers.Context(
        cfg=CFG, device_kind="TPU v5 lite", window=tuple(d["window"]),
        spans=d["spans"], steps=[(t, [tuple(e) for e in evs])
                                 for t, evs in d["steps"]],
        records=recs, trace=trace)


def test_quanta_and_contexts(ctx):
    qs = readers.quanta_within(ctx, *ctx.window)
    assert [q.chunk["t0"] for q in qs] == [100.10, 100.40]
    # rid 1 (32-token prompt): token 0 from prefill, tokens 1-4 then 5-8;
    # rid 2 (64-token prompt) had 5 tokens before the window
    assert qs[0].contexts == [33, 34, 35, 36, 69, 70, 71, 72]
    assert qs[1].contexts == [37, 38, 39, 40, 73, 74]


def test_decode_step_ms(ctx):
    # two 0.2 s quanta of 4 steps each
    assert spec.metric_reader("decode_step_ms.tput")(ctx) == pytest.approx(50.0)


def test_decode_mfu(ctx):
    # 2 x matmul weights per token: 2 layers x (64x32x12 + 3x64x128)
    # + 64x256 = 114688; attention 2 layers x 4 x 4 x 32 x context,
    # contexts summing to 721 over 14 tokens
    flops = 14 * 2 * 114688 + 2 * 4 * 4 * 32 * 721
    want = 100 * flops / (0.4 * 197e12)
    assert spec.metric_reader("decode_mfu.tput")(ctx) == pytest.approx(want)


def test_attn_roofline(ctx):
    # per token and KV head 40 packed bytes (K and V: 16 code bytes and one
    # fp32 scale each) and 4608 fixed (fp32 residual K/V 4096, q and out
    # 512); 8 tokens at 32 packed positions, 6 at 64; 2 KV heads, 2 layers
    nbytes = 2 * 2 * (8 * (32 * 40 + 4608) + 6 * (64 * 40 + 4608))
    flops = 2 * 4 * 4 * 32 * (8 * 48 + 6 * 80)
    least = max(flops / 197e12, nbytes / 819e9)
    # kernel events inside the two quanta: 0.01 + 0.01 + 0.02 s (the one
    # at 100.35 lies between quanta)
    got = spec.metric_reader("attn_roofline.tput")(ctx)
    assert got == pytest.approx(100 * least / 0.04)


def test_device_idle(ctx):
    # busy 0.2 + 0.2 + 0.01 s of a 0.6 s traced window
    got = spec.metric_reader("device_idle.tput")(ctx)
    assert got == pytest.approx(100 * (1 - 0.41 / 0.6))


def test_requests_sent_in_the_window(ctx):
    # ten of the eleven records were sent inside [100, 101)
    assert len(readers.sent_in_window(ctx)) == 10


def test_end_to_end_readers_on_token_times(ctx):
    # one request streams 8-token events at 100.1, 100.5, 100.9 and 101.3,
    # another 4-token events at 99.8 and 100.2
    a = Record(0, np.zeros(4, np.int32), 32)
    a.token_times = [100.1] * 8 + [100.5] * 8 + [100.9] * 8 + [101.3] * 8
    b = Record(1, np.zeros(4, np.int32), 8)
    b.token_times = [99.8] * 4 + [100.2] * 4
    win = readers.Context(cfg=CFG, device_kind="TPU v5 lite",
                          window=(100.0, 101.0), spans=[], steps=[],
                          records=[a, b])
    # 8 + 8 + 8 + 4 tokens arrived inside the one-second window
    assert spec.metric_reader("output_tok_s", "e2e")(win) == pytest.approx(28)
    # gaps ending inside the window: 7 + 7 + 7 + 3 zeros within events,
    # 0.4 + 0.4 between a's events and 0.4 between b's
    gaps = [0.0] * 24 + [0.4] * 3
    want = 1e3 * np.percentile(gaps, 95)
    assert spec.metric_reader("itl_p95_ms", "e2e")(win) == pytest.approx(want)


def test_breakdown(ctx):
    b = readers.breakdown(ctx)
    # leaf ops only (the quanta's while loops hold the kernel's calls):
    # the kernel, 0.01 + 0.01 + 0.01 + 0.02 s, named by its HLO text
    [[name, secs]] = b["device_ops"]
    assert name.startswith("%quant_decode_attention_paged_fwd.13 = f32")
    assert len(name) == 120
    assert secs == pytest.approx(0.05)
    gaps = b["idle_gaps"]
    # idle: 100.05-100.10, 100.30-100.35 and 100.60-100.65 (0.05 each),
    # then 100.36-100.40 (0.04), inside the second engine.step
    assert [g[1] for g in gaps] == pytest.approx([0.05, 0.05, 0.05, 0.04])
    assert gaps[3][0] == "engine.step"


def test_readers_find_nothing_without_a_trace(ctx):
    bare = readers.Context(cfg=CFG, device_kind="TPU v5 lite",
                           window=ctx.window, spans=[], steps=[],
                           records=[])
    for name in ("attn_roofline.tput", "device_idle.tput", "decode_mfu.tput",
                 "decode_step_ms.tput"):
        assert spec.metric_reader(name)(bare) is None
