"""bench/work.py's counts against hand arithmetic, at internlm2-1.8b's
size as run and at qwen3-14b's widths cut to 8 layers."""
import dataclasses
import json
import os

import pytest

from bench import model as bmodel
from bench import work

from conftest import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return bmodel.model_config(json.load(f))


def test_matmul_params_internlm2():
    cfg = _cfg("internlm2-1.8b")
    # per layer: q 2048x2048, k and v 2048x1024, o 2048x2048, MLP 3x2048x8192
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
    assert layer == 62_914_560
    assert work.matmul_params(cfg) == 24 * layer + 2048 * 92544


def test_matmul_params_qwen3_8_layers():
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config("qwen3-14b"), n_layers=8)
    # q 5120x5120, k and v 5120x1024, o 5120x5120, MLP 3x5120x17408
    layer = 5120 * 5120 * 2 + 5120 * 1024 * 2 + 3 * 5120 * 17408
    assert work.matmul_params(cfg) == 8 * layer + 5120 * 151936


def test_attn_row_bytes_and_flops_by_hand():
    cfg = _cfg("internlm2-1.8b")
    # 100 cached tokens: 96 packed (6 full windows of 16) + the window.
    # Per packed token and KV head: K and V each 64 code bytes + 4 fp32
    # group scales (16 bytes) = 160.  Per KV head: the fp32 residual K
    # and V (2 x 16 x 128 x 4 = 16384) and the folded query and output
    # of its 2 query heads (2 x 2 x 128 x 4 = 2048).
    assert work.packed_len(cfg, 100) == 96
    assert work.attn_row_bytes(cfg, 100) == 8 * (96 * 160 + 16384 + 2048)
    assert work.attn_row_flops(cfg, 100) == 4 * 16 * 128 * (96 + 16)


def test_decode_token_flops_by_hand():
    cfg = _cfg("internlm2-1.8b")
    assert work.decode_token_flops(cfg, 1000) == (
        2 * work.matmul_params(cfg) + 24 * 4 * 16 * 128 * 1000)


def test_least_time_takes_the_binding_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert work.least_time(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.least_time(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
