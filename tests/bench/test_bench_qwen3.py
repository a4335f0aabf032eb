"""The qwen3-14b-8l configuration on the CPU: a tiny benchmark root built
from ``bench/configs/qwen3-14b-8l.json`` with only its sizes cut (the
query group G = 5, per-head q/k RMSNorm and the untied head stay as
published) comes out correct against the plain reference, its fp8
control does not, and runs with the program's q/k norm or cache update
taken away come out not correct."""
import dataclasses
import json
import os
import time

import jax
import pytest

from bench import run, spec

from conftest import ROOT, TINY_MIXES, TINY_SERVING
from test_bench_run import _restore_cache_config, _skip_cache_update  # noqa: F401

# 10 query heads over 2 KV heads keep the published group of 5; hidden
# size is heads x head_dim, as at 5120 = 40 x 128.  initializer_range
# keeps the full-size model's scale: std x sqrt(hidden) = 0.02 x
# sqrt(5120), about 1.43
QWEN3_TINY = dict(hidden_size=320, intermediate_size=1088,
                  num_hidden_layers=2, num_attention_heads=10,
                  num_key_value_heads=2, head_dim=32, vocab_size=256,
                  initializer_range=0.08)
# set from readings at this size on six seeds (3-8): the program (fp32
# dots, bf16 activations on the CPU) read widest gaps 0.0062-0.26 and mean
# gaps 0.00021-0.0178; the fp8 control read widest gaps from 0.79 and
# mean gaps from 0.071.  Each limit lies near the geometric middle, about
# 1.7x (widest) and 2x (mean) from either side.  The planted faults read
# mean gaps of 0.29 and more (seeds 9-11).
QWEN3_LIMITS = {"max_logit_gap": 0.45, "mean_logit_gap": 0.035}
CELL = "qwen3-tiny.closed"


def write_qwen3_root(root) -> str:
    """A benchmark root holding the one cell ``qwen3-tiny.closed``."""
    root = str(root)
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    with open(os.path.join(ROOT, "bench", "configs",
                           "qwen3-14b-8l.json")) as f:
        conf = json.load(f)
    conf.update(QWEN3_TINY, name="qwen3-tiny", serving=TINY_SERVING)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "qwen3-tiny", "source": "test",
                         "file": "bench/configs/qwen3-tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": "qwen3-tiny",
                           "traffic": "closed", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    files = {
        "BENCHMARK.json": bench,
        "bench/configs/qwen3-tiny.json": conf,
        "bench/traffic/closed.json": TINY_MIXES["closed"],
        f"bench/cells/{CELL}.json": {
            "check_tokens": 40, "check_max_requests": 4,
            **{n: {"limit": v} for n, v in QWEN3_LIMITS.items()}},
    }
    for path, body in files.items():
        with open(os.path.join(root, path), "w") as f:
            json.dump(body, f)
    return root


@pytest.fixture(scope="module")
def qwen3_root(tmp_path_factory):
    return write_qwen3_root(tmp_path_factory.mktemp("qwen3_bench"))


def _execute(root, seed, **kw):
    cell = spec.load_cell(CELL, root=root)
    return run.execute(cell, seed, 3.0, False, t_start=time.perf_counter(),
                       devices=jax.devices()[:1], **kw)


def test_configuration_keeps_the_published_shape(qwen3_root):
    from bench import model as bmodel

    conf = spec.load_cell(CELL, root=qwen3_root).config
    cfg = bmodel.model_config(conf)
    assert cfg.n_heads // cfg.n_kv_heads == 5
    assert cfg.qk_norm and not cfg.tie_embeddings
    assert cfg.norm_eps == 1e-6


def test_program_config_matches_the_configuration_file():
    """The program's own qwen3-14b agrees with the benchmark's file on
    every key the file maps, but the depth that the file cuts."""
    from bench import model as bmodel
    from repro.configs import get_config

    with open(os.path.join(ROOT, "bench", "configs",
                           "qwen3-14b-8l.json")) as f:
        conf = json.load(f)
    prog = get_config("qwen3-14b")
    for key, field in bmodel.HF_FIELDS.items():
        want = conf["reduced"].get(key, conf[key])
        assert getattr(prog, field) == want, key
    assert prog.qk_norm and not prog.qkv_bias


def test_run_is_correct_and_the_control_is_not(qwen3_root):
    res = _execute(qwen3_root, 5, control=True)
    assert res["correct"] is True
    chk = res["check"]
    for name, limit in QWEN3_LIMITS.items():
        assert chk[name]["value"] <= limit
    assert chk["control_mean_gap"]["value"] > QWEN3_LIMITS["mean_logit_gap"]
    assert chk["short_streams"]["value"] == 0


def _skip_qk_norm(monkeypatch):
    """The program alone loses its q/k RMSNorm; the reference keeps it."""
    from repro.models import attention

    orig = attention._project_qkv

    def project(p, x, cfg, positions):
        return orig(p, x, dataclasses.replace(cfg, qk_norm=False), positions)

    monkeypatch.setattr(attention, "_project_qkv", project)


@pytest.mark.parametrize("fault", [_skip_qk_norm, _skip_cache_update],
                         ids=["qk_norm_skipped", "state_unchanged"])
def test_broken_timed_path_is_not_correct(qwen3_root, monkeypatch, fault):
    fault(monkeypatch)
    res = _execute(qwen3_root, 9)
    assert res["correct"] is False
    assert any(res["check"][n]["value"] > v for n, v in QWEN3_LIMITS.items())
