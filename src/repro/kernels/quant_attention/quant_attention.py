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

Paged KV (DESIGN.md §10): ``quant_decode_attention_paged_fwd`` takes
the per-row page table (B, MP), and the K/V pools as ``(n_pages*H,
page_size, c)`` arrays, viewed as ``(n_pages, H, page_size, c)``.  One
grid step covers P = ``paged_tile_pages(page_size, MP)`` pages (256
tokens at 16-token pages) of one row, every KV head: the grid is ``(B,
ceil(MP / P))``, and each pool is passed P times, block ``i`` of step
``(b, t)`` being page ``page_table[b, t*P + i]`` with all its heads
(one contiguous copy).  The pipeline gathers the P pages of the next
step while this one computes.  The wrapper resolves, once a call, the
page each block fetches into a (B, ceil(MP / P) * P) table, the SECOND
scalar-prefetch operand: ``t`` clamps to the row's last tile holding
packed tokens, and a block past the packed prefix keeps the page it
held one tile earlier, so steps past a row's prefix repeat every block
(no copy, no compute) and a row's last tile copies only its live pages
(a row shorter than one tile re-reads its last page instead): HBM
traffic stays O(sum prefixes) while residency is O(allocated pages).
The body runs the dense body per head on the concatenated pages: a
paged call is bit-identical to a dense call that tiles at ``blk = P *
page_size``.  (Gathering the pages by hand with ``make_async_copy``
does not lower in JAX 0.9: Mosaic refuses to slice a pool whose minor
dimension, 64 code bytes or 4 scales, is narrower than a lane tile.)
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

__all__ = [
    "paged_tile_pages",
    "quant_decode_attention_fwd",
    "quant_decode_attention_paged_fwd",
]

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


def _online_update(q, kd, vd, mask, m_ref, l_ref, acc_ref):
    """One flash-decode update of the (m, l, acc) state refs by a tile.

    q (G, d), kd/vd (n, d) f32, mask (n,) bool; m/l (G, 1), acc (G, d).
    """
    logits = jax.lax.dot_general(
        q, kd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, n)
    logits = jnp.where(mask[None, :], logits, _NEG_INF)
    m_prev = m_ref[...]  # (G,1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)  # (G,1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, vd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new


def _kernel(
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

    # skip fully-invalid tiles (everything past packed_len)
    @pl.when(s * blk < plen)
    def _packed_tile():
        kd = _unpack_dequant(kp_ref[0], ks_ref[0], group)
        vd = _unpack_dequant(vp_ref[0], vs_ref[0], group)
        pos = s * blk + jax.lax.broadcasted_iota(jnp.int32, (blk,), 0)
        _online_update(q, kd, vd, pos < plen, m_scr, l_scr, acc_scr)

    @pl.when(s == n_blocks - 1)
    def _finalize():
        w = kr_ref.shape[1]
        pos_r = plen + jax.lax.broadcasted_iota(jnp.int32, (w,), 0)
        _online_update(q, kr_ref[0], vr_ref[0], pos_r < length,
                       m_scr, l_scr, acc_scr)
        out_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def paged_tile_pages(page_size: int, max_pages: int) -> int:
    """Pages one grid step of the paged kernel covers: 256 tokens' worth
    (whole pages), never more than a row's page table holds."""
    return min(max_pages, max(1, 256 // page_size))


def _kernel_paged(scalars_ref, tile_pages_ref, q_ref, *refs, pages, group,
                  n_tiles):
    """One grid step: P pages of one row, every KV head.

    ``refs`` holds the P page blocks of each pool in turn (K codes, K
    scales, V codes, V scales; each ``(1, H, page_size, c)``), then the
    residual K and V ``(1, H, W, d)``, the output ``(1, H, G, d)`` and
    the (m, l, acc) scratch ``(H, G, 1)``, ``(H, G, 1)``, ``(H, G, d)``.
    """
    del tile_pages_ref  # consumed by the index maps only
    kp, ks, vp, vs = (refs[i * pages:(i + 1) * pages] for i in range(4))
    kr_ref, vr_ref, out_ref, m_scr, l_scr, acc_scr = refs[4 * pages:]
    b = pl.program_id(0)
    t = pl.program_id(1)
    H = q_ref.shape[1]
    blk = pages * kp[0].shape[2]
    plen = scalars_ref[0, b * H]  # one pair per (row, head), equal over heads
    length = scalars_ref[1, b * H]

    def tile(blocks, h, dtype):  # P page blocks of head h -> (blk, c)
        return jnp.concatenate([r[0, h].astype(dtype) for r in blocks])

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # a tile wholly past the row's packed prefix fetches nothing new (the
    # index maps repeat the last live tile's blocks) and computes nothing
    @pl.when(t * blk < plen)
    def _packed_tile():
        pos = t * blk + jax.lax.broadcasted_iota(jnp.int32, (blk,), 0)
        mask = pos < plen

        def head(h, carry):  # a loop, not unrolled: the code stays small
            kd = _unpack_dequant(tile(kp, h, jnp.int32),
                                 tile(ks, h, jnp.float32), group)
            vd = _unpack_dequant(tile(vp, h, jnp.int32),
                                 tile(vs, h, jnp.float32), group)
            _online_update(q_ref[0, h], kd, vd, mask, m_scr.at[h],
                           l_scr.at[h], acc_scr.at[h])
            return carry

        jax.lax.fori_loop(0, H, head, 0)

    @pl.when(t == n_tiles - 1)
    def _finalize():
        w = kr_ref.shape[2]
        pos_r = plen + jax.lax.broadcasted_iota(jnp.int32, (w,), 0)

        def head(h, carry):
            _online_update(q_ref[0, h], kr_ref[0, h], vr_ref[0, h],
                           pos_r < length, m_scr.at[h], l_scr.at[h],
                           acc_scr.at[h])
            out_ref[0, h] = acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
            return carry

        jax.lax.fori_loop(0, H, head, 0)


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
    page_table: jax.Array,  # (B, MP) int32 physical page per logical page
    *,
    group: int = 32,
    page_size: int = 16,
    n_kv_heads: int = 1,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged flash-decode: each grid step gathers P pages of one row.

    Prefetch contract (DESIGN.md §10): the grid is ``(B, ceil(MP /
    P))`` with ``P = paged_tile_pages(page_size, MP)``; step ``(b, t)``
    reads pages ``page_table[b, t*P : (t+1)*P]``, every KV head of a
    page in one block, through P BlockSpecs a pool.  The scalar
    prefetch carries the length scalars and the page table resolved
    tile by tile, so an index map is one table read.  Tiles wholly past
    a row's ``packed_len`` copy and compute nothing (module doc).  The
    length scalars are one pair per (row, head), equal over a row's
    heads.  Returns out_rot (BH, G, d) f32.
    """
    if interpret is None:
        interpret = interpret_default()
    H = n_kv_heads
    BH, G, dh = q_eff.shape
    B, MP = page_table.shape
    W = k_residual.shape[1]
    assert k_packed.shape[1] == page_size, (k_packed.shape, page_size)
    pages = paged_tile_pages(page_size, MP)
    n_tiles = -(-MP // pages)
    scalars = jnp.stack([
        packed_len.astype(jnp.int32).reshape(-1),
        total_len.astype(jnp.int32).reshape(-1),
    ])  # (2, BH)

    # page i of tile t of row b, as block i of step (b, t) fetches it.
    # Past the row's last live tile the tile index clamps to it, so every
    # block repeats and Pallas starts no copy.  A block past the live
    # prefix keeps the page it held one tile earlier (no copy either),
    # or, in a row's first tile, the row's last live page.  Worked out
    # here once a call, so that an index map is one table read: the
    # kernel's code grows with each instruction of its 4P index maps.
    n_live = -(-scalars[0].reshape(B, H)[:, 0] // page_size)
    last = jnp.maximum(n_live - 1, 0)[:, None, None]
    t = jnp.minimum(jnp.arange(n_tiles)[None, :, None],
                    jnp.maximum(-(-n_live // pages) - 1, 0)[:, None, None])
    j = t * pages + jnp.arange(pages)[None, None, :]
    j = jnp.where(j <= last, j, jnp.where(t > 0, j - pages, last))
    tile_pages = jnp.take_along_axis(page_table.astype(jnp.int32),
                                     j.reshape(B, -1), axis=1)

    def page_block(i):
        return lambda b, t, scalars, tp: (tp[b, t * pages + i], 0, 0, 0)

    def per_row(b, t, scalars, tp):
        return (b, 0, 0, 0)

    # (n_pages*H, ...) -> (n_pages, H, ...): a page's heads are one block
    pools = [x.reshape((-1, H) + x.shape[1:])
             for x in (k_packed, k_scales, v_packed, v_scales)]
    page_specs = [pl.BlockSpec((1,) + pool.shape[1:], page_block(i))
                  for pool in pools for i in range(pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_tiles),
        in_specs=[pl.BlockSpec((1, H, G, dh), per_row), *page_specs,
                  pl.BlockSpec((1, H, W, dh), per_row),
                  pl.BlockSpec((1, H, W, dh), per_row)],
        out_specs=pl.BlockSpec((1, H, G, dh), per_row),
        scratch_shapes=[
            pltpu.VMEM((H, G, 1), jnp.float32),
            pltpu.VMEM((H, G, 1), jnp.float32),
            pltpu.VMEM((H, G, dh), jnp.float32),
        ],
    )
    perm = half_major(dh)

    def by_row(x):  # (B*H, n, d) -> (B, H, n, d)
        return x.reshape((B, H) + x.shape[1:])

    out = pl.pallas_call(
        functools.partial(_kernel_paged, pages=pages, group=group,
                          n_tiles=n_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, G, dh), jnp.float32),
        interpret=interpret,
    )(scalars, tile_pages, by_row(q_eff[..., perm]),
      *[pool for pool in pools for _ in range(pages)],
      by_row(k_residual[..., perm]), by_row(v_residual[..., perm]))
    return out.reshape(BH, G, dh)[..., np.argsort(perm)]
