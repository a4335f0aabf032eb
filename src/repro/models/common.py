"""Shared model primitives: norms, dense layers, RoPE, embeddings.

Pure-functional: every module is (init(key, ...) -> params dict,
apply(params, x, ...) -> y).  Params are nested dicts of jnp arrays;
compute dtype is bf16 with fp32 accumulation, params stored bf16 (norm
scales fp32).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

PARAM_DTYPE = jnp.bfloat16
COMPUTE_DTYPE = jnp.bfloat16

# XLA cost_analysis counts while-loop bodies ONCE (no trip-count scaling).
# The roofline fit (benchmarks/roofline_measure.py) lowers small-depth
# variants with every scan fully unrolled and extrapolates; this flag
# switches all structural scans to full unroll.  Never set it for full-
# depth configs.
SCAN_UNROLL = os.environ.get("REPRO_UNROLL_SCANS", "0") == "1"


def scan(f, init, xs, **kw):
    """lax.scan honoring the roofline-fit unroll flag."""
    import jax as _jax

    return _jax.lax.scan(f, init, xs, unroll=True if SCAN_UNROLL else 1, **kw)


def shard_hint(x, kind: str):
    """Activation-sharding hint (launch.act_sharding policy; identity when
    no policy is active -- tests and the paper-faithful baseline see a
    no-op)."""
    from repro.launch.act_sharding import hint

    return hint(x, kind)


def dot_operand(x: jax.Array) -> jax.Array:
    """Cast a matmul operand to the dot dtype, chosen at trace time: bf16
    (fp32 accumulation through ``preferred_element_type``) on every
    backend but the CPU, whose dot thunk refuses some bf16 x bf16 -> f32
    contractions at run time (the batched expert einsums), so there the
    operands are fp32."""
    cpu = jax.default_backend() == "cpu"
    return x.astype(jnp.float32 if cpu else COMPUTE_DTYPE)


def einsum_f32(spec: str, *ops: jax.Array) -> jax.Array:
    """einsum with ``dot_operand`` operands and fp32 accumulation."""
    return jnp.einsum(
        spec, *(dot_operand(o) for o in ops),
        preferred_element_type=jnp.float32,
    )

__all__ = [
    "dense_init",
    "dense",
    "rmsnorm_init",
    "rmsnorm",
    "embed_init",
    "rope_freqs",
    "apply_rope",
    "sinusoidal_positions",
    "PARAM_DTYPE",
    "COMPUTE_DTYPE",
]


def dense_init(key, d_in: int, d_out, *, bias: bool = False, scale: float | None = None):
    """He-ish init; d_out may be a tuple for fused multi-head weights."""
    d_out_t = (d_out,) if isinstance(d_out, int) else tuple(d_out)
    fan_out = int(np.prod(d_out_t))
    std = scale if scale is not None else (1.0 / np.sqrt(d_in))
    w = (jax.random.normal(key, (d_in, *d_out_t), jnp.float32) * std).astype(
        PARAM_DTYPE
    )
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros(d_out_t, PARAM_DTYPE)
    return p


def dense(p, x: jax.Array) -> jax.Array:
    """x (..., d_in) @ w (d_in, *d_out) -> (..., *d_out), fp32 accumulate."""
    w = p["w"]
    d_out = w.shape[1:]
    y = jax.lax.dot_general(
        dot_operand(x),
        dot_operand(w.reshape(w.shape[0], -1)),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = y.reshape(x.shape[:-1] + d_out)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(COMPUTE_DTYPE)


def rmsnorm_init(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(p, x: jax.Array, *, eps: float = 1e-6, unit_offset: bool = True) -> jax.Array:
    """RMSNorm with (1 + w) parameterization (zeros-init scale).

    unit_offset=True matches gemma; for the others (1+w) with w zero-init
    is numerically the same parameterization, so we use it uniformly.
    """
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"])).astype(COMPUTE_DTYPE)


def layernorm_init(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return ((1.0 + p["scale"]) * y + p["bias"]).astype(COMPUTE_DTYPE)


def embed_init(key, vocab: int, d: int):
    w = (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(PARAM_DTYPE)
    return {"embedding": w}


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim // 2,) inverse frequencies."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (B, H, S, d), positions: (S,) or (B, S)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)  # (d/2,)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # (S, d/2)
        ang = ang[None, None]  # (1,1,S,d/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * inv  # (B,S,d/2)
        ang = ang[:, None]  # (B,1,S,d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Whisper-style sinusoidal absolute positions (n, d)."""
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * dim / d))
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, jnp.float32)
