"""Fused SRFT + lambda + per-group abs-max + int4/int8 pack — Pallas TPU.

TPU adaptation of the paper's single-dispatch Metal kernel (§3.2, §7.1):
one HBM read of the fp32/bf16 vectors, rotation as a d x d MXU matmul
(the radix-8-DFT-is-a-matmul observation, taken to its TPU conclusion),
per-group abs-max on the VPU, round-half-even quantize, nibble pack, and
a quarter-sized HBM write.  Everything between read and write lives in
VMEM — the TPU analogue of "one Metal dispatch instead of four".

Grid: 1-D over row tiles (TN rows of d-vectors per program).
BlockSpecs: x (TN, d) VMEM; M (d, d) VMEM broadcast; outputs (TN, d//2)
uint8 (int4) or (TN, d) int8, scales (TN, d//group) fp32.

The matrix M is the *folded* rotation diag(lam) @ R @ B (ref.fold_matrix):
learned per-channel lambda costs ZERO extra kernel work on TPU, vs the
paper's +3-8% in-register multiply tax on Metal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import half_major, interpret_default

__all__ = ["srft_quant_fwd", "srft_dequant_fwd", "DEFAULT_ROW_TILE"]

DEFAULT_ROW_TILE = 256


def _col_group(shape, d: int, group: int, bits: int) -> jax.Array:
    """Quantization group of each column.  int4 columns are half-major
    (``repro.kernels.half_major``), so each half holds ``group // 2``
    columns of every group; int8 columns are in natural order."""
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if bits == 4:
        return (col % (d // 2)) // (group // 2)
    return col // group


def _quant_kernel(x_ref, m_ref, packed_ref, scales_ref, *, group: int,
                  bits: int):
    x = x_ref[...].astype(jnp.float32)  # (TN, d)
    m = m_ref[...].astype(jnp.float32)  # (d, d), rows in output order
    # rotation on the MXU: y[n, e] = sum_d x[n, d] * m[e, d]
    y = jax.lax.dot_general(
        x, m, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    tn, d = y.shape
    n_groups = d // group
    qmax = float(2 ** (bits - 1) - 1)
    # per-group abs-max by masked lane reductions: Mosaic lowers no
    # (TN, d) -> (TN, d//group, group) reshape
    col_group = _col_group((tn, d), d, group, bits)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tn, n_groups), 1)
    ay = jnp.abs(y)
    scale = jnp.zeros((tn, d), jnp.float32)
    scales = jnp.zeros((tn, n_groups), jnp.float32)
    for g in range(n_groups):
        sg = jnp.max(jnp.where(col_group == g, ay, 0.0), axis=-1,
                     keepdims=True)
        sg = jnp.maximum(sg, 1e-12) / qmax
        scale = jnp.where(col_group == g, sg, scale)
        scales = jnp.where(out_col == g, sg, scales)
    scales_ref[...] = scales
    q = jnp.rint(y / scale)
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int32)
    if bits == 4:
        # nibble pack: byte i = (q[2i+1] << 4) | (q[2i] & 0xF); the odd
        # coordinates are the upper half of the half-major columns
        half = d // 2
        packed_ref[...] = (
            ((q[:, half:] & 0xF) << 4) | (q[:, :half] & 0xF)
        ).astype(jnp.uint8)
    else:
        packed_ref[...] = q.astype(jnp.int8)


def _dequant_kernel(packed_ref, scales_ref, minv_ref, x_ref, *, group: int,
                    bits: int):
    p = packed_ref[...]
    if bits == 4:
        # codes come out half-major; minv's columns were permuted to match
        pi = p.astype(jnp.int32)
        low = pi & 0xF
        high = (pi >> 4) & 0xF
        low = jnp.where(low >= 8, low - 16, low)
        high = jnp.where(high >= 8, high - 16, high)
        codes = jnp.concatenate([low, high], axis=-1)
    else:
        codes = p.astype(jnp.int32)
    tn, d = codes.shape
    scales = scales_ref[...]  # (TN, d//group)
    col_group = _col_group((tn, d), d, group, bits)
    scale = jnp.zeros((tn, d), jnp.float32)
    for g in range(d // group):
        scale = jnp.where(col_group == g, scales[:, g:g + 1], scale)
    y = codes.astype(jnp.float32) * scale
    minv = minv_ref[...].astype(jnp.float32)
    # x[n, dd] = sum_e y[n, e] * minv[dd, e]
    x_ref[...] = jax.lax.dot_general(
        y, minv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit, static_argnames=("group", "bits", "row_tile", "interpret")
)
def srft_quant_fwd(
    x: jax.Array,  # (N, d)
    m: jax.Array,  # (d, d) folded rotation (lambda included)
    *,
    group: int = 32,
    bits: int = 4,
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool | None = None,
):
    """Fused rotate+quantize+pack.  Returns (packed, scales)."""
    if interpret is None:
        interpret = interpret_default()
    n, d = x.shape
    assert d % group == 0 and d % 2 == 0
    tn = min(row_tile, n)
    assert n % tn == 0, f"N={n} must divide row_tile={tn}"
    grid = (n // tn,)
    if bits == 4:
        m = m[half_major(d)]  # rows in the kernel's column order
    out_cols = d // 2 if bits == 4 else d
    out_dtype = jnp.uint8 if bits == 4 else jnp.int8
    return pl.pallas_call(
        functools.partial(_quant_kernel, group=group, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, d), lambda i: (i, 0)),
            pl.BlockSpec((d, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tn, out_cols), lambda i: (i, 0)),
            pl.BlockSpec((tn, d // group), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, out_cols), out_dtype),
            jax.ShapeDtypeStruct((n, d // group), jnp.float32),
        ],
        interpret=interpret,
    )(x, m)


@functools.partial(
    jax.jit, static_argnames=("group", "bits", "row_tile", "interpret")
)
def srft_dequant_fwd(
    packed: jax.Array,  # (N, d//2) uint8 or (N, d) int8
    scales: jax.Array,  # (N, d//group)
    minv: jax.Array,  # (d, d) folded inverse
    *,
    group: int = 32,
    bits: int = 4,
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool | None = None,
):
    """Fused unpack+dequantize+inverse-rotate.  Returns x (N, d) fp32."""
    if interpret is None:
        interpret = interpret_default()
    n = packed.shape[0]
    d = packed.shape[1] * 2 if bits == 4 else packed.shape[1]
    tn = min(row_tile, n)
    assert n % tn == 0
    grid = (n // tn,)
    if bits == 4:
        minv = minv[:, half_major(d)]  # columns in the codes' order
    in_cols = packed.shape[1]
    return pl.pallas_call(
        functools.partial(_dequant_kernel, group=group, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, in_cols), lambda i: (i, 0)),
            pl.BlockSpec((tn, d // group), lambda i: (i, 0)),
            pl.BlockSpec((d, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(packed, scales, minv)
