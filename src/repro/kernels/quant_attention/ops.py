"""Public wrappers: decode attention on a QuantKVCache (dense) or a
paged int4 pool via the Pallas kernel.  Both fold rotation + 1/lam_k +
softmax scale into the query, call the kernel, and inverse-rotate the
single output vector."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import kvcache as kvc
from repro.core.kvcache import QuantKVCache
from repro.core.paged import PagedData
from repro.core.transforms import Rotation
from repro.kernels.quant_attention.quant_attention import (
    quant_decode_attention_fwd,
    quant_decode_attention_paged_fwd,
)

__all__ = ["decode_attention_kernel", "decode_attention_kernel_paged"]


def decode_attention_kernel(
    q: jax.Array,  # (B, Hq, 1, d) raw query (post-RoPE)
    cache: QuantKVCache,
    rot_k: Rotation,
    rot_v: Rotation,
    *,
    scale: float | None = None,
    blk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, Hq, 1, d) decode attention output in the original basis."""
    B, Hq, _, d = q.shape
    Hkv = cache.k_packed.shape[1]
    G = Hq // Hkv
    sm = scale if scale is not None else d ** -0.5

    q_eff = jnp.einsum(
        "...d,ed->...e", q.astype(jnp.float32), rot_k.folded_query_matrix()
    ) * sm  # (B, Hq, 1, d)
    q_eff = q_eff.reshape(B, Hkv, G, d).reshape(B * Hkv, G, d)

    def flat(x):
        return x.reshape((B * Hkv,) + x.shape[2:])

    plen, tlen = kvc.packed_len(cache), cache.length
    if tlen.ndim == 1:  # ragged (B,) lengths -> one pair per (b, h) row
        plen = jnp.repeat(plen, Hkv)
        tlen = jnp.repeat(tlen, Hkv)

    out_rot = quant_decode_attention_fwd(
        q_eff,
        flat(cache.k_packed), flat(cache.k_scales),
        flat(cache.v_packed), flat(cache.v_scales),
        flat(cache.k_residual), flat(cache.v_residual),
        plen, tlen,
        group=cache.group, blk=blk, interpret=interpret,
    )  # (B*Hkv, G, d)
    out_rot = out_rot.reshape(B, Hq, 1, d)
    return rot_v.inverse(out_rot).astype(q.dtype)


def decode_attention_kernel_paged(
    q: jax.Array,  # (B, Hq, 1, d) raw query (post-RoPE)
    pd: PagedData,  # int4 paged state: pools + page table + residual
    rot_k: Rotation,
    rot_v: Rotation,
    *,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, Hq, 1, d) decode attention over a PAGED int4 cache.

    The page table rides the scalar prefetch; each grid step of the
    kernel gathers several pages of one row from the pools in HBM (the
    paged prefetch contract, DESIGN.md §10), so the dense per-row view
    is never materialized and HBM residency is the pool, not O(B x
    s_max).
    """
    B, Hq, _, d = q.shape
    kp_pool, ks_pool, vp_pool, vs_pool = pd.pools
    Hkv = kp_pool.shape[1]
    G = Hq // Hkv
    N, _, ps, _ = kp_pool.shape
    k_res, v_res = pd.residual
    sm = scale if scale is not None else d ** -0.5
    group = d // ks_pool.shape[-1]

    q_eff = jnp.einsum(
        "...d,ed->...e", q.astype(jnp.float32), rot_k.folded_query_matrix()
    ) * sm  # (B, Hq, 1, d)
    q_eff = q_eff.reshape(B, Hkv, G, d).reshape(B * Hkv, G, d)

    def flat_pool(x):  # (N, H, ps, c) -> (N*H, ps, c); block N*H row-major
        return x.reshape((N * Hkv,) + x.shape[2:])

    def flat_row(x):  # (B, H, W, d) -> (B*H, W, d)
        return x.reshape((B * Hkv,) + x.shape[2:])

    length = pd.length  # (B,)
    plen = jnp.repeat(length - length % k_res.shape[-2], Hkv)
    tlen = jnp.repeat(length, Hkv)

    out_rot = quant_decode_attention_paged_fwd(
        q_eff,
        flat_pool(kp_pool), flat_pool(ks_pool),
        flat_pool(vp_pool), flat_pool(vs_pool),
        flat_row(k_res), flat_row(v_res),
        plen, tlen, pd.page_table,
        group=group, page_size=ps, n_kv_heads=Hkv, interpret=interpret,
    )  # (B*Hkv, G, d)
    out_rot = out_rot.reshape(B, Hq, 1, d)
    return rot_v.inverse(out_rot).astype(q.dtype)
