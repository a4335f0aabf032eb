"""Plain float32 reference of a dense decoder served through the int4
SRFT KV cache: RMSNorm, grouped-query attention with rotary positions
(and per-head q/k RMSNorm where the configuration has it), SwiGLU MLP,
untied output head.  No cache, no kernel, no batching: one sequence at
a time, layer by layer, every matmul at ``highest`` precision.

It applies the cache's quantization as the paper states it, not as the
program codes it: each key and value, post-RoPE, is rotated by its
layer's SRFT matrix B, cut into groups of ``group`` coordinates, and
rounded to int4 with one abs-max scale per group (codes in [-7, 7]).
Which positions a query sees quantized follows the cache's lifecycle:
prompt positions attend exact keys and values (prefill attends the raw
ones); a decoded token at position ``t`` sees positions below
``W * floor((t + 1) / W)`` through int4 and the rest, the residual
window, exact.

``precision="fp8"`` is the control: every projection's operands are
rounded to float8 e4m3 with one scale per row of activations and one
per output column of weights, the step below the bfloat16 the
configuration serves in.

Imports nothing of the program; takes the benchmark's weights (a tree in
the program's parameter layout) and rotation tables.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QMAX = 7  # int4 symmetric codes
FP8_MAX = 448.0  # float8 e4m3 largest finite
Q_BLOCK = 256  # queries per attention block


def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    """x (..., k) @ w (k, n) in float32 (operands rounded to fp8 in the
    control)."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotary embedding on (T, H, d): halves (x1, x2) rotated by
    position * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _int4(y, group):
    """Per-group abs-max int4 round trip of rotated values (..., d)."""
    g = y.reshape(y.shape[:-1] + (y.shape[-1] // group, group))
    scale = jnp.maximum(jnp.max(jnp.abs(g), -1, keepdims=True),
                        1e-12) / QMAX
    q = jnp.clip(jnp.round(g / scale), -QMAX, QMAX)
    return (q * scale).reshape(y.shape)


@functools.partial(jax.jit, static_argnames=("hp", "fp8"))
def _layer(x, lw, bk, bv, n_prompt, *, hp, fp8):
    """One decoder layer over (T, d); ``hp`` holds the static sizes."""
    n_heads, n_kv, hd, eps, theta, window, group, qk_norm = hp
    T = x.shape[0]
    G = n_heads // n_kv
    pos = jnp.arange(T)

    h = _rmsnorm(x, lw["ln_attn"], eps)
    q = _mm(h, lw["wq"].reshape(h.shape[-1], -1), fp8).reshape(T, n_heads, hd)
    k = _mm(h, lw["wk"].reshape(h.shape[-1], -1), fp8).reshape(T, n_kv, hd)
    v = _mm(h, lw["wv"].reshape(h.shape[-1], -1), fp8).reshape(T, n_kv, hd)
    if qk_norm:
        q = _rmsnorm(q, lw["q_norm"], eps)
        k = _rmsnorm(k, lw["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)

    # rotated space: B is orthonormal, so q.k = (Bq).(Bk)
    kr, vr = k @ bk.T, v @ bv.T
    kq, vq = _int4(kr, group), _int4(vr, group)
    qr = (q @ bk.T).reshape(T, n_kv, G, hd) * hd ** -0.5

    def block(t0):
        qb = jax.lax.dynamic_slice_in_dim(qr, t0, Q_BLOCK, 0)
        t = t0 + jnp.arange(Q_BLOCK)
        seen = pos[None, :] <= t[:, None]  # (Q, T)
        packed = (t[:, None] >= n_prompt) & (
            pos[None, :] < window * ((t[:, None] + 1) // window))
        s_raw = jnp.einsum("qhgd,khd->hgqk", qb, kr)
        s_q = jnp.einsum("qhgd,khd->hgqk", qb, kq)
        s = jnp.where(packed, s_q, s_raw)
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        pq = jnp.where(packed, p, 0.0)
        o = (jnp.einsum("hgqk,khd->qhgd", pq, vq)
             + jnp.einsum("hgqk,khd->qhgd", p - pq, vr))
        return o @ bv  # back from rotated space

    o = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))
    o = o.reshape(T, n_heads * hd)
    x = x + _mm(o, lw["wo"], fp8)
    h = _rmsnorm(x, lw["ln_ffn"], eps)
    a = jax.nn.silu(_mm(h, lw["w_gate"], fp8)) * _mm(h, lw["w_up"], fp8)
    return x + _mm(a, lw["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, rows, ln_final, unembed, *, eps, fp8):
    h = _rmsnorm(x[rows], ln_final, eps)
    return _mm(h, unembed, fp8)


def layer_weights(weights, i: int) -> dict:
    """Layer ``i``'s matrices and norm weights from the program-layout
    tree (leading layer axis)."""
    b = weights["blocks"]
    a = b["attn"]
    lw = {"ln_attn": b["ln_attn"]["scale"][i], "ln_ffn": b["ln_ffn"]["scale"][i],
          "wq": a["wq"]["w"][i], "wk": a["wk"]["w"][i], "wv": a["wv"]["w"][i],
          "wo": a["wo"]["w"][i], "w_gate": b["ffn"]["w_gate"]["w"][i],
          "w_up": b["ffn"]["w_up"]["w"][i], "w_down": b["ffn"]["w_down"]["w"][i]}
    if "q_norm" in a:
        lw["q_norm"] = a["q_norm"]["scale"][i]
        lw["k_norm"] = a["k_norm"]["scale"][i]
    return lw


def served_logits(weights, tables, conf: dict, prompt, served, *,
                  t_pad: int, n_pad: int, precision: str = "f32"):
    """Logits (n_pad, V) at the positions that predicted each served
    token (rows past ``len(served)`` are padding), for the sequence
    ``prompt + served``."""
    fp8 = precision == "fp8"
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    T, S, n = seq.shape[0], len(prompt), len(served)
    if T > t_pad or n > n_pad:
        raise ValueError(f"sequence {T} / {n} served exceeds the padding "
                         f"{t_pad} / {n_pad}")
    toks = np.zeros((t_pad,), np.int32)
    toks[:T] = seq
    kv = conf["kv_cache"]
    hp = (conf["num_attention_heads"], conf["num_key_value_heads"],
          conf["head_dim"], float(conf["rms_norm_eps"]),
          float(conf["rope_theta"]), int(kv["window"]), int(kv["group"]),
          bool(conf["program"].get("qk_norm", False)))
    with jax.default_matmul_precision("highest"):
        x = weights["embed"]["embedding"][jnp.asarray(toks)].astype(
            jnp.float32)
        for i in range(conf["num_hidden_layers"]):
            x = _layer(x, layer_weights(weights, i),
                       jnp.asarray(tables["matrix"][0, i]),
                       jnp.asarray(tables["matrix"][1, i]),
                       jnp.asarray(S, jnp.int32), hp=hp, fp8=fp8)
        rows = np.full((n_pad,), S - 1, np.int32)
        rows[:n] = S - 1 + np.arange(n)
        return _head(x, jnp.asarray(rows), weights["ln_final"]["scale"],
                     weights["unembed"]["w"], eps=hp[3], fp8=fp8)


@jax.jit
def _gaps(ref, chosen):
    """Per row: the reference's best logit minus its logit of ``chosen``."""
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]


def served_gaps(ref_logits, served) -> np.ndarray:
    """Gap of each served token below the reference's best."""
    n = len(served)
    chosen = np.zeros((ref_logits.shape[0],), np.int32)
    chosen[:n] = served
    return np.asarray(_gaps(ref_logits, jnp.asarray(chosen)))[:n]


def control_gaps(ref_logits, ctrl_logits, n: int) -> np.ndarray:
    """Gap of the token that the control puts first, at each position."""
    chosen = jnp.argmax(ctrl_logits, axis=-1).astype(jnp.int32)
    return np.asarray(_gaps(ref_logits, chosen))[:n]
