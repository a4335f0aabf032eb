"""Compile served paths for a TPU v5e that is described, not attached.

* Every cell of ``BENCHMARK.json``: its whole decode quantum at the
  cell's own sizes (slots, slot length from the traffic, pool), with the
  raw K/V side buffers of its longest prompt's admission live beside it,
  has to fit one chip's memory as the chip's compiler counts it.
* qwen3-14b, cut to 8 of its 40 layers: the Pallas int4 paged decode
  kernel at its shapes (8 KV heads, 5 query heads per KV head, d=128,
  16-token pages, 8 rows of 1296-token slots), and its whole 8-step
  decode quantum.  No cell runs this configuration yet; the check says
  that one can.

The topology is described inside a module-scoped fixture, so importing
this file never loads the TPU library; only the worker that runs these
tests does."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HKV, G, D, GROUP, W, PAGE = 8, 5, 128, 32, 16, 16
ROWS, S_MAX = 8, 1296
HBM_BYTES = 16e9
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: an
    entry compiled for it cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_paged_kernel_compiles_at_qwen3_shapes(one_chip):
    from repro.kernels.quant_attention.quant_attention import (
        quant_decode_attention_paged_fwd,
    )

    mp = S_MAX // PAGE
    n_pages = ROWS * mp + 1
    bh = ROWS * HKV
    kv = [((n_pages * HKV, PAGE, D // 2), jnp.uint8),
          ((n_pages * HKV, PAGE, D // GROUP), jnp.float32)] * 2
    shapes = ([((bh, G, D), jnp.float32)] + kv
              + [((bh, W, D), jnp.float32)] * 2
              + [((bh,), jnp.int32)] * 2 + [((ROWS, mp), jnp.int32)])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(lambda *a: quant_decode_attention_paged_fwd(
        *a, group=GROUP, page_size=PAGE, n_kv_heads=HKV, interpret=False)
    ).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _compile_quantum(one_chip, cfg, *, rows, s_max, page, policy, backend,
                     steps, beside=None):
    """Compile ``steps`` decode steps of ``rows`` slots of ``s_max``
    tokens over a full paged pool, as the engine's quantum scans them,
    for a described chip.  ``beside``: one more argument that stays live
    on the device through the quantum."""
    from repro.core.cache_api import AttendBackend
    from repro.models import build_model

    mdl = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(mdl.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: mdl.init_cache(
        rows, s_max, policy=policy, key=jax.random.PRNGKey(0),
        ragged=True, n_pages=rows * (s_max // page) + 1, page_size=page)))

    def quantum(params, tok, cache, active, beside):
        def body(carry, _):
            tok, cache = carry
            logits, cache = mdl.decode_step(
                params, tok, cache, backend=AttendBackend(backend),
                active=active)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            return (nxt, cache), nxt[:, 0]
        return jax.lax.scan(body, (tok, cache), None, length=steps), beside

    args = (params, jax.ShapeDtypeStruct((rows, 1), jnp.int32,
                                         sharding=one_chip),
            cache, jax.ShapeDtypeStruct((rows,), jnp.bool_,
                                        sharding=one_chip), beside)
    compiled = jax.jit(quantum, donate_argnums=(2, 4)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_qwen3_8_layer_decode_quantum_fits_one_chip(one_chip, monkeypatch):
    from repro.configs import get_config

    # trace as the chip would: bf16 dot operands, compiled kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("qwen3-14b"), n_layers=8,
                              kv_group=GROUP, kv_window=W)
    compiled = _compile_quantum(one_chip, cfg, rows=ROWS, s_max=S_MAX,
                                page=PAGE, policy="int4-srft",
                                backend="kernel", steps=8)
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert need < HBM_BYTES, need


@pytest.mark.parametrize("name", CELLS)
def test_cell_decode_quantum_fits_one_chip(one_chip, monkeypatch, name):
    """At the cell's own sizes: slots, the slot length its mix needs, a
    full pool, and the raw bf16 K/V buffers of its longest prompt's
    admission, which stay live on the device while quanta run.  The
    chip's compiler refuses a program that does not fit its memory
    (RESOURCE_EXHAUSTED), counting arguments and temporaries."""
    from bench import model as bmodel
    from bench import spec, system
    from bench.traffic import generator

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = spec.load_cell(name)
    conf, sv = cell.config, cell.config["serving"]
    cfg = bmodel.model_config(conf)
    plan = generator.generate(cell.traffic, 0, cfg.vocab_size)
    raw = jax.ShapeDtypeStruct(
        (2, cfg.n_layers, cfg.n_kv_heads, max(plan.prompt_lens),
         cfg.head_dim), jnp.bfloat16, sharding=one_chip)
    _compile_quantum(one_chip, cfg, rows=sv["slots"],
                     s_max=system.s_max_for(plan, cfg.kv_window),
                     page=sv["page_size"], policy=conf["kv_cache"]["policy"],
                     backend=sv["backend"], steps=sv["chunk"], beside=raw)
