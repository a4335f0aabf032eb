"""Shared fixtures of the benchmark's own tests: the repository root on
the import path, and a tiny cell (CPU-sized internlm2-shaped model, one
closed-loop mix) laid out as a benchmark root of its
own, built from the real configuration file with only its sizes cut."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# initializer_range keeps the full-size model's scale: std x sqrt(width)
# about 0.9, so logits spread as they do at 2048 wide with 0.02
TINY_SIZES = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                  vocab_size=256, initializer_range=0.11)
TINY_SERVING = {"backend": "kernel", "paged": True, "page_size": 16,
                "slots": 2, "chunk": 4, "prefill_chunk": 32,
                "prefill_budget": 64}
TINY_MIXES = {
    "closed": {"loop": "closed", "clients": 2,
               "prompt_lens": [32, 64], "prompt_weights": [1, 1],
               "output": {"dist": "uniform", "min": 8, "max": 16}},
}
# set from readings at this size on six seeds (3-8): the program (fp32
# dots, bf16 activations on the CPU) read widest gaps up to 0.12 and mean
# gaps up to 0.0048; the fp8 control read widest gaps from 0.15 and mean
# gaps from 0.024.  Only the mean separates them by more than 3x here.
TINY_LIMITS = {"max_logit_gap": 0.25, "mean_logit_gap": 0.012}


def write_tiny_root(root) -> str:
    """A benchmark root holding the tiny cell ``tiny.closed``; returns
    its path."""
    root = str(root)
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    with open(os.path.join(ROOT, "bench", "configs",
                           "internlm2-1.8b.json")) as f:
        conf = json.load(f)
    conf.update(TINY_SIZES, name="tiny", serving=TINY_SERVING)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = []
    for mix, body in TINY_MIXES.items():
        with open(os.path.join(root, "bench", "traffic", mix + ".json"),
                  "w") as f:
            json.dump(body, f)
        with open(os.path.join(root, "bench", "cells",
                               f"tiny.{mix}.json"), "w") as f:
            json.dump({"check_tokens": 40, "check_max_requests": 4,
                       **{n: {"limit": v} for n, v in TINY_LIMITS.items()}},
                      f)
        bench["workloads"].append({"name": f"tiny.{mix}", "config": "tiny",
                                   "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny_root(tmp_path_factory.mktemp("tiny_bench"))
