"""The configuration as run: a ``ModelConfig`` built from a
``configs/<config>.json`` file, and the weights and rotation tables that
the benchmark makes from ``--seed`` and hands to the program.

The benchmark, not the program, makes the weights and the SRFT tables,
so that the plain reference can use them without taking anything the
program made.  Both are built on the device in one jitted call each,
in the dtypes the program serves them in.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# published config.json key -> the program's ModelConfig field
HF_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

# streams of the seed: each consumer of randomness draws from its own
STREAM_WEIGHTS, STREAM_ROTATIONS, STREAM_ENGINE = 1, 2, 3


def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw PRNG key from the whole of ``seed`` (``PRNGKey`` keeps only
    its low 32 bits, so seeds past 2**32 would repeat)."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(
        2, np.uint32)
    return jnp.asarray(state, jnp.uint32)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs import get_config

    prog = conf["program"]
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{conf['name']}: only SwiGLU (silu) MLPs are run")
    kw = {HF_FIELDS[k]: conf[k] for k in HF_FIELDS if k in conf}
    kv = conf["kv_cache"]
    kw.update(qk_norm=bool(prog.get("qk_norm", False)),
              qkv_bias=bool(conf.get("attention_bias", False)),
              ffn_activation="swiglu", kv_group=kv["group"],
              kv_window=kv["window"], rotation=kv["rotation"])
    base = get_config(prog["arch"])
    return dataclasses.replace(base, name=conf["name"], **kw).validated()


def _leaf_init(path, leaf, key, std: float):
    name = str(getattr(path[-1], "key", path[-1]))
    if name in ("w", "embedding"):
        return (jax.random.normal(key, leaf.shape, leaf.dtype)
                * jnp.asarray(std, leaf.dtype))
    if name == "scale":
        # RMSNorm weight is (1 + scale): a spread around the published
        # init of 1, so that a norm weight that is dropped shows
        return jax.random.normal(key, leaf.shape, leaf.dtype) * 0.1
    if name == "b":
        return jnp.zeros(leaf.shape, leaf.dtype)
    raise ValueError(f"no initializer for parameter {jax.tree_util.keystr(path)}")


def make_weights(model, seed: int, std: float):
    """Weights in the program's layout (``model.init``'s tree, shapes
    and dtypes), drawn from ``seed`` on the device in one jitted call:
    normal(0, ``std``) for every matrix (the published
    ``initializer_range``), norm weights around 1."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf_init(p, l, k, std)
                      for (p, l), k in zip(flat, keys)])

    return jax.jit(build)(seed_key(seed, STREAM_WEIGHTS))


def srft_matrix(signs: np.ndarray) -> np.ndarray:
    """(d, d) orthonormal B with B @ x = pack(rfft_ortho(signs * x)),
    the paper's Hermitian packing (Eq. 2): Re Y_0, sqrt2 Re Y_1..Y_{d/2-1},
    Re Y_{d/2}, sqrt2 Im Y_1..Y_{d/2-1}."""
    d = signs.shape[0]
    y = np.fft.rfft(np.diag(signs.astype(np.float64)), axis=-1, norm="ortho")
    s2 = np.sqrt(2.0)
    rows = np.concatenate([y.real[:, :1], s2 * y.real[:, 1:d // 2],
                           y.real[:, d // 2:d // 2 + 1],
                           s2 * y.imag[:, 1:d // 2]], axis=-1)
    return rows.T.astype(np.float32)  # column i is B @ e_i


def make_rotation_tables(cfg, seed: int) -> dict:
    """Per-layer SRFT tables for K and V: ``signs`` (2, L, d) of +-1 and
    ``matrix`` (2, L, d, d); lambda is 1 (the uncalibrated deployment)."""
    rng = seed_rng(seed, STREAM_ROTATIONS)
    d, L = cfg.head_dim, cfg.n_layers
    signs = rng.choice(np.array([-1.0, 1.0], np.float32), size=(2, L, d))
    mats = np.stack([np.stack([srft_matrix(signs[i, j]) for j in range(L)])
                     for i in range(2)])
    return {"signs": signs, "matrix": mats}


def program_rotations(tables: dict):
    """The tables as the program's ``Rotations`` (what ``BatchEngine``
    takes as ``rots``)."""
    from repro.core.transforms import Rotation
    from repro.models.lm import Rotations

    def rot(i):
        m = jnp.asarray(tables["matrix"][i])
        s = jnp.asarray(tables["signs"][i])
        return Rotation(matrix=m, lam=jnp.ones_like(s), signs=s, kind="srft")

    return Rotations(k=rot(0), v=rot(1))
