"""bench/run.py end to end on the CPU at the tiny cell's size: the
refusal without a chip, a whole run past the chip check, and runs with
the timed path broken underneath that must come out not correct."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import run, spec

from conftest import ROOT, TINY_LIMITS


@pytest.fixture(scope="module", autouse=True)
def _restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def _execute(root, name, seed=5, seconds=3.0, **kw):
    cell = spec.load_cell(name, root=root)
    return run.execute(cell, seed, seconds, False, t_start=time.perf_counter(),
                       devices=jax.devices()[:1], **kw)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "internlm2-1.8b.long_docs", "--seed", "3000000001", "--seconds",
         "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_run_is_correct_and_reads_the_control(tiny_root):
    res = _execute(tiny_root, "tiny.closed", control=True)
    assert res["correct"] is True
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) >= {"setup_s", "itl_p95_ms"}
    assert res["device"]["platform"] == "cpu"
    chk = res["check"]
    for name, limit in TINY_LIMITS.items():
        assert chk[name]["value"] <= limit
    # the control (fp8) fails a limit that the program passes
    assert chk["control_mean_gap"]["value"] > TINY_LIMITS["mean_logit_gap"]
    assert chk["short_streams"]["value"] == 0
    json.dumps(res)


def _alter_tokens(monkeypatch):
    from repro.launch.engine import Sampler

    orig = Sampler.sample

    def sample(self, logits, key):
        return (orig(self, logits, key) + 1) % logits.shape[-1]

    monkeypatch.setattr(Sampler, "sample", sample)


def _skip_cache_update(monkeypatch):
    from repro.core.cache_api import Int4SRFTPolicy

    monkeypatch.setattr(Int4SRFTPolicy, "update",
                        lambda self, state, k, v, active=None: state)


@pytest.mark.parametrize("fault", [_alter_tokens, _skip_cache_update],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    res = _execute(tiny_root, "tiny.closed", seed=9)
    assert res["correct"] is False
    assert any(res["check"][n]["value"] > v for n, v in TINY_LIMITS.items())
