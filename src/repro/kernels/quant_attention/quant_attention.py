"""Flash-decode attention over nibble-packed int4 KV — Pallas TPU.

The deployment hot loop (paper §7): every decode step streams the stored
prefix.  With int4+scales the stream is ~3.2-3.7x smaller than bf16; this
kernel keeps the whole rotate/dequant pipeline in VMEM so the only HBM
traffic is the packed bytes (the bandwidth win is the paper's entire
mechanism, DESIGN.md §1).

Rotated-space trick (beyond-paper): K/V are stored as Q4(lam * B k), the
wrapper folds diag(1/lam_k) @ B and the softmax scale into the query, so
NO inverse rotation happens per cached token — scores are exact inner
products in rotated space.  Only the final (1-token) output vector is
inverse-rotated, outside the kernel.

Grid: (BH, S/blk) — TPU executes the minor axis sequentially per BH, so
the online-softmax state lives in VMEM scratch across KV tiles; the fp32
residual window is folded in at the last tile, then the accumulator is
normalized and written once.

Length-aware grid (DESIGN.md §8): block fetches happen for every grid
step regardless of ``pl.when`` guards, so a naive index map streams all
S_max/blk tiles from HBM even when the prefix is short.  The KV
BlockSpec index maps instead read the scalar-prefetched ``packed_len``
and clamp the tile index to the last tile holding valid tokens: grid
steps past the prefix re-request the SAME block, Pallas elides the
repeat DMA (the block revisiting rule), and per-step HBM traffic is
O(prefix), not O(S_max).  Compute guards keep using the unclamped grid
index, so masking is unchanged.

Ragged batching (DESIGN.md §9): the prefetched scalars are PER ROW --
shape (2, BH), one (packed_len, total_len) pair per batch*head slice --
so the grid clamp is per sequence.  A batch of requests with mixed
prefix lengths streams O(sum_i L_i) packed bytes per step, not
O(batch x max_i L_i): the short rows' grid steps collapse onto their
own last valid tile.  Single-request callers pass scalars; the wrapper
broadcasts them, so the uniform case is unchanged.

Paged KV (DESIGN.md §10): ``quant_decode_attention_paged_fwd`` adds a
SECOND scalar-prefetch operand -- the per-row page table (B, MP) -- and
the K/V pools arrive as ``(n_pages*H, page_size, c)`` arrays.  The
prefetch contract is one grid tile per physical page (blk ==
page_size): tile ``s`` of row ``b`` fetches block ``page_table[b,
s_eff] * H + h`` where ``s_eff`` is the same per-row length clamp as
the dense path, so HBM traffic stays O(sum prefixes) while residency
is O(allocated pages), not O(batch x s_max).  The kernel BODY is
byte-identical to the dense one (same tile contents arrive, whatever
page they were fetched from), which is what makes paged decode
bit-identical to the dense slot path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import half_major, interpret_default

__all__ = ["quant_decode_attention_fwd", "quant_decode_attention_paged_fwd"]

_NEG_INF = -1e30


def _unpack_dequant(p, scales, group):
    """(blk, d//2) uint8 + (blk, d//group) f32 -> (blk, d) f32, columns in
    ``[low nibbles | high nibbles]`` order (``repro.kernels.half_major``).

    Byte ``i`` holds coordinate ``2i`` (low nibble) and ``2i+1`` (high),
    so both halves cover the groups in the same order, ``group // 2``
    columns per group.  The per-group scales are broadcast onto lanes
    by one select per group: no 3-D reshape and no lane interleave,
    neither of which Mosaic lowers.
    """
    pi = p.astype(jnp.int32)
    low = pi & 0xF
    high = (pi >> 4) & 0xF
    low = jnp.where(low >= 8, low - 16, low).astype(jnp.float32)
    high = jnp.where(high >= 8, high - 16, high).astype(jnp.float32)
    blk, half = p.shape
    col_group = jax.lax.broadcasted_iota(jnp.int32, (blk, half), 1) // (
        group // 2)
    s = jnp.zeros((blk, half), jnp.float32)
    for g in range(scales.shape[1]):
        s = jnp.where(col_group == g, scales[:, g:g + 1], s)
    return jnp.concatenate([low * s, high * s], axis=-1)


def _kernel_impl(
    scalars_ref,  # SMEM (2, BH): per-row [packed_len, total_len]
    q_ref,  # (1, G, d) f32 — q_eff, rotation/lam/scale folded
    kp_ref,  # (1, blk, d//2) uint8
    ks_ref,  # (1, blk, d//group) f32
    vp_ref,
    vs_ref,
    kr_ref,  # (1, W, d) f32 residual K (rotated space)
    vr_ref,
    out_ref,  # (1, G, d) f32
    m_scr,  # (G, 1) f32
    l_scr,  # (G, 1) f32
    acc_scr,  # (G, d) f32
    *,
    blk: int,
    group: int,
    n_blocks: int,
):
    bh = pl.program_id(0)
    s = pl.program_id(1)
    plen = scalars_ref[0, bh]
    length = scalars_ref[1, bh]

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # (G, d)

    def online_update(kd, vd, mask):
        """kd/vd (n, d) f32, mask (n,) bool."""
        logits = jax.lax.dot_general(
            q, kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (G, n)
        logits = jnp.where(mask[None, :], logits, _NEG_INF)
        m_prev = m_scr[...]  # (G,1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)  # (G,1)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, vd, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    # skip fully-invalid tiles (everything past packed_len)
    @pl.when(s * blk < plen)
    def _packed_tile():
        kd = _unpack_dequant(kp_ref[0], ks_ref[0], group)
        vd = _unpack_dequant(vp_ref[0], vs_ref[0], group)
        pos = s * blk + jax.lax.broadcasted_iota(jnp.int32, (blk,), 0)
        online_update(kd, vd, pos < plen)

    @pl.when(s == n_blocks - 1)
    def _finalize():
        w = kr_ref.shape[1]
        pos_r = plen + jax.lax.broadcasted_iota(jnp.int32, (w,), 0)
        online_update(kr_ref[0], vr_ref[0], pos_r < length)
        out_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def _kernel(scalars_ref, *rest, blk, group, n_blocks):
    _kernel_impl(scalars_ref, *rest, blk=blk, group=group, n_blocks=n_blocks)


def _kernel_paged(scalars_ref, ptab_ref, *rest, blk, group, n_blocks):
    # ptab_ref is consumed by the BlockSpec index maps only; the body is
    # the dense body (identical tile contents => identical numerics).
    del ptab_ref
    _kernel_impl(scalars_ref, *rest, blk=blk, group=group, n_blocks=n_blocks)


@functools.partial(
    jax.jit, static_argnames=("group", "blk", "interpret")
)
def quant_decode_attention_fwd(
    q_eff: jax.Array,  # (BH, G, d) f32 — folded query (see module doc)
    k_packed: jax.Array,  # (BH, S, d//2) uint8
    k_scales: jax.Array,  # (BH, S, d//group) f32
    v_packed: jax.Array,
    v_scales: jax.Array,
    k_residual: jax.Array,  # (BH, W, d) f32
    v_residual: jax.Array,
    packed_len: jax.Array,  # () or (BH,) int32 -- per-row when ragged
    total_len: jax.Array,  # () or (BH,) int32
    *,
    group: int = 32,
    blk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns out_rot (BH, G, d) f32 in rotated space."""
    if interpret is None:
        interpret = interpret_default()
    BH, S, dh = k_packed.shape[0], k_packed.shape[1], q_eff.shape[-1]
    G = q_eff.shape[1]
    W = k_residual.shape[1]
    blk = math.gcd(min(blk, S), S)
    n_blocks = S // blk
    scalars = jnp.stack([
        jnp.broadcast_to(packed_len.astype(jnp.int32).reshape(-1), (BH,)),
        jnp.broadcast_to(total_len.astype(jnp.int32).reshape(-1), (BH,)),
    ])  # (2, BH): one (packed_len, total_len) pair per row

    def kv_tile(bh, s, scalars):
        # Length-aware fetch, PER ROW: clamp to this row's last tile
        # containing valid packed tokens.  Past-prefix grid steps
        # re-request that tile; Pallas skips the DMA for an unchanged
        # block index, so HBM traffic scales with each row's own
        # packed_len (O(sum of prefixes) across a ragged batch), not
        # S_max.  Compute for those steps is already skipped by the
        # pl.when(s * blk < plen) guard (which uses the unclamped s).
        n_valid = (scalars[0, bh] + blk - 1) // blk
        return (bh, jnp.minimum(s, jnp.maximum(n_valid - 1, 0)), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, n_blocks),
        in_specs=[
            pl.BlockSpec((1, G, dh), lambda bh, s, _: (bh, 0, 0)),
            pl.BlockSpec((1, blk, dh // 2), kv_tile),
            pl.BlockSpec((1, blk, dh // group), kv_tile),
            pl.BlockSpec((1, blk, dh // 2), kv_tile),
            pl.BlockSpec((1, blk, dh // group), kv_tile),
            pl.BlockSpec((1, W, dh), lambda bh, s, _: (bh, 0, 0)),
            pl.BlockSpec((1, W, dh), lambda bh, s, _: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, dh), lambda bh, s, _: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    perm = half_major(dh)
    out = pl.pallas_call(
        functools.partial(_kernel, blk=blk, group=group, n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, G, dh), jnp.float32),
        interpret=interpret,
    )(scalars, q_eff[..., perm], k_packed, k_scales, v_packed, v_scales,
      k_residual[..., perm], v_residual[..., perm])
    return out[..., np.argsort(perm)]


@functools.partial(
    jax.jit, static_argnames=("group", "page_size", "n_kv_heads", "interpret")
)
def quant_decode_attention_paged_fwd(
    q_eff: jax.Array,  # (BH, G, d) f32 — folded query (see module doc)
    k_packed: jax.Array,  # (n_pages*H, page_size, d//2) uint8 pool
    k_scales: jax.Array,  # (n_pages*H, page_size, d//group) f32 pool
    v_packed: jax.Array,
    v_scales: jax.Array,
    k_residual: jax.Array,  # (BH, W, d) f32 (per row, not paged)
    v_residual: jax.Array,
    packed_len: jax.Array,  # (BH,) int32 per-row
    total_len: jax.Array,  # (BH,) int32 per-row
    page_table: jax.Array,  # (B, MP) int32 physical page per logical tile
    *,
    group: int = 32,
    page_size: int = 16,
    n_kv_heads: int = 1,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged flash-decode: the grid walks physical pages.

    Prefetch contract (DESIGN.md §10): one grid tile per page (blk ==
    page_size).  Both the per-row length scalars AND the page table are
    scalar-prefetched; the KV BlockSpec index maps resolve logical tile
    ``s`` of row ``b`` to pool block ``page_table[b, s_eff] * H + h``,
    with ``s_eff`` the dense path's per-row length clamp -- steps past a
    row's prefix re-request its last valid page and Pallas elides the
    DMA, so per-step HBM traffic is O(sum of prefixes) while pool
    residency is O(allocated pages).  Returns out_rot (BH, G, d) f32.
    """
    if interpret is None:
        interpret = interpret_default()
    H = n_kv_heads
    BH, G, dh = q_eff.shape
    MP = page_table.shape[-1]
    W = k_residual.shape[1]
    blk = page_size
    assert k_packed.shape[1] == blk, (k_packed.shape, blk)
    n_blocks = MP
    scalars = jnp.stack([
        packed_len.astype(jnp.int32).reshape(-1),
        total_len.astype(jnp.int32).reshape(-1),
    ])  # (2, BH)

    def kv_tile(bh, s, scalars, ptab):
        # per-row length clamp (as the dense path), then page-table
        # indirection: the block index is the PHYSICAL page
        n_valid = (scalars[0, bh] + blk - 1) // blk
        s_eff = jnp.minimum(s, jnp.maximum(n_valid - 1, 0))
        page = ptab[bh // H, s_eff]
        return (page * H + bh % H, 0, 0)

    def per_row(bh, s, scalars, ptab):
        return (bh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, n_blocks),
        in_specs=[
            pl.BlockSpec((1, G, dh), per_row),
            pl.BlockSpec((1, blk, dh // 2), kv_tile),
            pl.BlockSpec((1, blk, dh // group), kv_tile),
            pl.BlockSpec((1, blk, dh // 2), kv_tile),
            pl.BlockSpec((1, blk, dh // group), kv_tile),
            pl.BlockSpec((1, W, dh), per_row),
            pl.BlockSpec((1, W, dh), per_row),
        ],
        out_specs=pl.BlockSpec((1, G, dh), per_row),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    perm = half_major(dh)
    out = pl.pallas_call(
        functools.partial(_kernel_paged, blk=blk, group=group,
                          n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, G, dh), jnp.float32),
        interpret=interpret,
    )(scalars, page_table.astype(jnp.int32), q_eff[..., perm],
      k_packed, k_scales, v_packed, v_scales,
      k_residual[..., perm], v_residual[..., perm])
    return out[..., np.argsort(perm)]
