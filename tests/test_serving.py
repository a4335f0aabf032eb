"""Serving-path integration: prefill+decode logits must agree with the
teacher-forced forward pass (bf16 cache: numerically close; int4 cache:
close after calibration-free SRFT at modest context)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.paper_models import SMOL_D64
from repro.models import build_model

B, S = 2, 47


def _setup(cfg):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (B, S + 4), 0, cfg.vocab_size
    )
    return model, params, toks


def test_bf16_cache_decode_matches_forward():
    cfg = SMOL_D64
    model, params, toks = _setup(cfg)
    logits_tf, _ = model.forward(params, toks, remat=False)

    cache = model.init_cache(B, S + 8, policy="bf16")
    lp, cache = model.prefill(params, toks[:, :S], cache)
    np.testing.assert_allclose(
        np.asarray(lp[:, 0]), np.asarray(logits_tf[:, S - 1]),
        atol=0.15, rtol=0.05,
    )
    # decode the next two ground-truth tokens and compare logits
    for i in range(2):
        ld, cache = model.decode_step(params, toks[:, S + i : S + i + 1],
                                      cache)
        np.testing.assert_allclose(
            np.asarray(ld[:, 0]), np.asarray(logits_tf[:, S + i]),
            atol=0.15, rtol=0.05,
        )


def test_int4_cache_decode_tracks_forward():
    """int4 cache adds quantization noise but must stay close in logit
    space for a freshly-initialized (near-uniform) model."""
    cfg = SMOL_D64
    model, params, toks = _setup(cfg)
    logits_tf, _ = model.forward(params, toks, remat=False)
    cache = model.init_cache(B, S + 8, policy="int4-srft",
                             key=jax.random.PRNGKey(3))
    lp, cache = model.prefill(params, toks[:, :S], cache)
    # top-1 agreement (the argmax token) rather than exact logits
    agree = (
        np.argmax(np.asarray(lp[:, 0]), -1)
        == np.argmax(np.asarray(logits_tf[:, S - 1]), -1)
    ).mean()
    assert agree >= 0.5, agree
    ld, _ = model.decode_step(params, toks[:, S : S + 1], cache)
    assert not bool(jnp.any(jnp.isnan(ld)))


def test_decode_backend_equivalence_through_model():
    """GATHER vs BLOCKWISE vs KERNEL backends give the same output
    through the full attention layer (typed AttendBackend enum)."""
    from repro.core.cache_api import AttendBackend, get_policy
    from repro.models import attention

    cfg = SMOL_D64
    d = cfg.head_dim
    p = attention.attention_init(jax.random.PRNGKey(0), cfg)
    pol = get_policy("int4-srft", group=cfg.kv_group, window=16)
    cache = pol.init_state(B, cfg.n_kv_heads, 64, d,
                           key=jax.random.PRNGKey(1))
    k = jax.random.normal(jax.random.PRNGKey(3), (B, cfg.n_kv_heads, 40, d))
    cache = pol.prefill(cache, k, k)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, 1, cfg.d_model)).astype(
        jnp.bfloat16
    )
    pos = jnp.asarray(40)
    outs = {}
    for backend in AttendBackend:
        y, _ = attention.attention_decode(
            p, x, cfg, cache, position=pos, backend=backend, kv_block=32,
        )
        outs[backend.value] = np.asarray(y.astype(jnp.float32))
    np.testing.assert_allclose(outs["gather"], outs["blockwise"], atol=2e-2)
    np.testing.assert_allclose(outs["gather"], outs["kernel"], atol=2e-2)


@pytest.mark.parametrize("arch", ["whisper-large-v3", "zamba2-7b"])
def test_exotic_family_serving(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 32), 0,
                              cfg.vocab_size)
    if cfg.family == "audio":
        frames = jax.random.normal(jax.random.PRNGKey(3),
                                   (B, 32, cfg.d_model))
        cache = model.init_cache(B, 48, 32, key=jax.random.PRNGKey(1))
        logits, cache = model.prefill(params, frames, toks, cache)
    else:
        cache = model.init_cache(B, 48, key=jax.random.PRNGKey(1))
        logits, cache = model.prefill(params, toks, cache)
    for _ in range(3):
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        logits, cache = model.decode_step(params, tok, cache)
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert int(cache["pos"]) == 35


def test_serve_refuses_kernel_backend_on_mesh():
    """``serve.py --mesh N --backend kernel`` exits with an error naming
    the backend to use, before building anything."""
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--smoke", "--mesh", "2", "--backend", "kernel"])
    with pytest.raises(SystemExit, match="--backend blockwise"):
        serve.build_engine(args)
