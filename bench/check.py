"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests that got tokens (drawn from the seed, with the longest
sequence always in it) is run through the configuration's plain
reference: one float32 forward over each prompt and its served tokens.
Serving is greedy, so a served token is right when no other token's
reference logit lies well above its own.  Two numbers are compared: the
widest such gap over every checked token (``max_logit_gap``, in logits)
and the mean gap over them (``mean_logit_gap``).  With random weights
the top logits lie close, so a widest gap swings from run to run; the
mean, the share of tokens that differ times how far, separates the
program from the lower precision of the control more steadily.  Their
limits are in ``cells/<cell>.json``, with the readings they were set
from.  Streams that finished by length must hold exactly the tokens
asked for (``short_streams``, limit 0).
"""
from __future__ import annotations

import gc

import numpy as np

from bench import model as bmodel
from bench import spec

STREAM_SAMPLE = 23
GAPS = ("max_logit_gap", "mean_logit_gap")


def sample(records, seed: int, tokens: int, max_requests: int) -> list:
    """Requests to check: the longest sequence, then others in an order
    drawn from the seed, until ``tokens`` served tokens or
    ``max_requests`` requests."""
    cands = [r for r in records if r.tokens and r.error is None]
    if not cands:
        return []
    longest = max(cands, key=lambda r: (len(r.prompt) + len(r.tokens), r.idx))
    rest = [r for r in sorted(cands, key=lambda r: r.idx) if r is not longest]
    order = bmodel.seed_rng(seed, STREAM_SAMPLE).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def padding(plan, q_block: int) -> tuple[int, int]:
    """(sequence, served) lengths every reference call is padded to, so
    that it compiles once per cell."""
    t = max(plan.prompt_lens) + plan.max_output
    return t + (-t) % q_block, plan.max_output


def run(cell, sysm, records, seed: int, plan, control: bool = False) -> dict:
    """Check a sample of the served tokens.  ``control`` also reads the
    control (the reference in fp8) on the same sample: the gap of the
    token it puts first at each position, widest and mean
    (``control_max_gap``, ``control_mean_gap``)."""
    conf = cell.config
    lim = cell.limits
    ref = spec.reference(conf["reference"])
    chosen = sample(records, seed, lim["check_tokens"],
                    lim["check_max_requests"])
    short = sum(1 for r in records if r.finish == "length"
                and len(r.tokens) != r.max_tokens)
    gc.collect()
    t_pad, n_pad = padding(plan, ref.Q_BLOCK)
    gaps, ctrl = [], []
    for r in chosen:
        logits = ref.served_logits(sysm.weights, sysm.tables, conf, r.prompt,
                                   r.tokens, t_pad=t_pad, n_pad=n_pad)
        gaps.append(ref.served_gaps(logits, r.tokens))
        if control:
            low = ref.served_logits(sysm.weights, sysm.tables, conf,
                                    r.prompt, r.tokens, t_pad=t_pad,
                                    n_pad=n_pad, precision="fp8")
            ctrl.append(ref.control_gaps(logits, low, len(r.tokens)))
            del low
        del logits
    n_tok = int(sum(len(g) for g in gaps))
    got = _widest_and_mean(gaps)
    numbers = {n: {"value": got.get(n), "limit": float(lim[n]["limit"])}
               for n in GAPS}
    numbers["short_streams"] = {"value": short, "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in numbers.values())
    for name, value in _widest_and_mean(ctrl).items():
        numbers["control_" + name.replace("_logit", "")] = {
            "value": value, "limit": numbers[name]["limit"]}
    summary = "; ".join(f"{n} {numbers[n]['value']} (limit "
                        f"{numbers[n]['limit']})" for n in numbers)
    return {"correct": correct, "numbers": numbers,
            "gaps": gaps, "sample": chosen,
            "summary": f"{n_tok} served tokens of {len(chosen)} requests "
                       f"checked; {summary}"}


def _widest_and_mean(gaps: list) -> dict:
    """The widest gap and the mean gap over every checked token."""
    if not gaps:
        return {}
    flat = np.concatenate(gaps)
    return dict(zip(GAPS, (float(flat.max()), float(flat.mean()))))
