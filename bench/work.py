"""Operations and bytes per call, from shapes alone.

The decode kernel's bytes are those the algorithm needs: for each live
row, the int4 codes and fp32 group scales of its packed context, its
fp32 residual window, its folded query and its output.  They are never
the pages the kernel's grid happens to walk, so a kernel that stops
walking null pages is credited with the same work.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


def matmul_params(cfg) -> int:
    """Weights that every decoded token multiplies: q/k/v/o projections,
    the SwiGLU MLP and the output head (the embedding is a lookup)."""
    d, hd = cfg.d_model, cfg.head_dim
    attn = d * hd * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
    mlp = 3 * d * cfg.d_ff
    return cfg.n_layers * (attn + mlp) + d * cfg.vocab_size


def decode_token_flops(cfg, context: int) -> int:
    """One decoded token at ``context`` cached positions: 2 per matmul
    weight, plus QK^T and PV over the context in every layer."""
    attn = 4 * cfg.n_heads * cfg.head_dim * context
    return 2 * matmul_params(cfg) + cfg.n_layers * attn


def packed_len(cfg, context: int) -> int:
    w = cfg.kv_window
    return context - context % w


def attn_row_bytes(cfg, context: int) -> int:
    """Bytes one call of the int4 decode kernel needs for one live row
    at ``context`` cached positions (one layer, every KV head)."""
    hd, g, w = cfg.head_dim, cfg.kv_group, cfg.kv_window
    per_token = 2 * (hd // 2 + 4 * (hd // g))  # K and V codes + scales
    group = cfg.n_heads // cfg.n_kv_heads
    fixed = 2 * w * hd * 4 + 2 * group * hd * 4  # residual K/V, q, out
    return cfg.n_kv_heads * (packed_len(cfg, context) * per_token + fixed)


def attn_row_flops(cfg, context: int) -> int:
    """QK^T and PV of one call for one live row: the packed context and
    the whole residual window."""
    return 4 * cfg.n_heads * cfg.head_dim * (packed_len(cfg, context)
                                            + cfg.kv_window)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute
    and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
