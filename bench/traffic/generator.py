"""One generator for every traffic mix (``bench/traffic/<mix>.json``).

A mix is data: a closed loop of ``clients`` (each sends its next request
when the last one ends, until the window closes), the set of prompt
lengths with their weights, and the output-length distribution.  The
part of the program's own generator (``launch/server/trace.py``) that
carries over is the seeded prompt draw; its three fixed length buckets
do not.

Every seed gets the same sizes for the same client: in each wave (every
client's ``w``-th request) prompt lengths are apportioned to their
weights exactly, output lengths are the distribution's quantiles at
``(i + 0.5) / n``, and which client sends which pair is drawn from the
wave's index alone.  The seed draws the prompt tokens (and the weights).
So two seeds differ in what they compute, not in how much or when: in a
closed loop a client's next request waits on its last, and sizes that
moved between clients with the seed would move the admissions in the
window with them.  Prompt lengths come from a small fixed set because
each distinct length compiles a prefill program of its own; set-up warms
exactly that set.
"""
from __future__ import annotations

import dataclasses

import numpy as np

STREAM_TRAFFIC = 11


@dataclasses.dataclass
class Request:
    idx: int
    prompt: np.ndarray  # (S,) int32 token ids
    max_tokens: int
    client: int  # the client that sends it


@dataclasses.dataclass
class Plan:
    mix: dict
    seed: int
    vocab: int
    prompt_lens: tuple  # every prompt length the mix can send
    max_output: int
    clients: int

    def wave(self, w: int) -> list:
        """Wave ``w``, each client's ``w``-th request: its sizes from
        ``w`` alone, its tokens from the seed and ``w``.  Clients draw
        waves until the window closes, so however fast the server, none
        runs dry."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, STREAM_TRAFFIC, w]))
        lens, outs = _sizes(self.mix, self.clients, w)
        return [Request(w * self.clients + c,
                        _tokens(rng, lens[c], self.vocab), int(outs[c]),
                        client=c)
                for c in range(self.clients)]


def apportion(weights, n: int) -> np.ndarray:
    """Counts summing to ``n`` in proportion to ``weights`` (largest
    remainder)."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def output_quantiles(spec: dict, n: int) -> np.ndarray:
    """The output-length distribution's quantiles at (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown output distribution {spec['dist']!r}")
    vals = lo + u * (hi - lo + 1)
    return np.clip(np.floor(vals), lo, hi).astype(int)


def _sizes(mix: dict, n: int, w: int) -> tuple[np.ndarray, np.ndarray]:
    """Wave ``w``'s ``n`` (prompt length, output length) pairs, one per
    client: the same pairs in every wave (lengths apportioned to their
    weights, outputs the distribution's quantiles, matched by a fixed
    shuffle), in an order drawn from ``w``."""
    lens = np.repeat(np.asarray(mix["prompt_lens"], int),
                     apportion(mix["prompt_weights"], n))
    outs = output_quantiles(mix["output"], n)
    outs = outs[np.random.default_rng(0).permutation(n)]
    order = np.random.default_rng(
        np.random.SeedSequence([STREAM_TRAFFIC, w])).permutation(n)
    return lens[order], outs[order]


def generate(mix: dict, seed: int, vocab: int) -> Plan:
    """The requests of ``mix`` for ``seed``: a closed loop whose wave
    ``w`` holds each client's ``w``-th request.  Every seed sends the
    same sizes from the same clients, so set-up (the first wave) and the
    window are the same work for every seed."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r} (closed)")
    lens_set = tuple(sorted(int(x) for x in mix["prompt_lens"]))
    if len(mix["prompt_weights"]) != len(lens_set):
        raise ValueError("prompt_weights must match prompt_lens")
    return Plan(mix, int(seed), int(vocab), lens_set,
                int(mix["output"]["max"]), int(mix["clients"]))


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, int(n), dtype=np.int64).astype(np.int32)
