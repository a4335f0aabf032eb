"""Compile the served path's Pallas kernels for a TPU v5e that is
described, not attached, at internlm2-1.8b widths (Hkv=8, G=2, d=128,
group=32, W=16, 4096-token prefix, 16-token pages, batch 8).  What the
chip's compiler refuses (a shape cast Mosaic cannot lower, a block not
aligned to the tiling, too much VMEM) fails here without the chip.

The topology is described inside a module-scoped fixture, so importing
this file (in every pytest-xdist worker) never loads the TPU library;
only the worker that runs these tests does."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.quant_attention.quant_attention import (
    quant_decode_attention_fwd,
    quant_decode_attention_paged_fwd,
)
from repro.kernels.srft_quant.srft_quant import (
    srft_dequant_fwd,
    srft_quant_fwd,
)

B, HKV, G, D, GROUP, W, PREFIX, PAGE = 8, 8, 2, 128, 32, 16, 4096, 16
BH = B * HKV


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


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kv(rows, blk):
    return [((rows, blk, D // 2), jnp.uint8),
            ((rows, blk, D // GROUP), jnp.float32)] * 2


def test_dense_decode_kernel_compiles(one_chip):
    shapes = ([((BH, G, D), jnp.float32)] + _kv(BH, PREFIX)
              + [((BH, W, D), jnp.float32)] * 2
              + [((BH,), jnp.int32)] * 2)
    text = _compiled_text(
        lambda *a: quant_decode_attention_fwd(*a, group=GROUP,
                                              interpret=False),
        shapes, one_chip)
    assert "tpu_custom_call" in text


# (batch, pages a row): the module's 4096-token batch of 8, and the
# long_docs benchmark cell's 5 slots of 6544 tokens (409 pages, which
# the kernel's tile of 16 pages does not divide)
@pytest.mark.parametrize("b,mp", [(B, PREFIX // PAGE), (5, 409)])
def test_paged_decode_kernel_compiles(one_chip, b, mp):
    bh = b * HKV
    n_pages = b * mp + 1  # every row's pages plus the null page
    shapes = ([((bh, G, D), jnp.float32)] + _kv(n_pages * HKV, PAGE)
              + [((bh, W, D), jnp.float32)] * 2
              + [((bh,), jnp.int32)] * 2 + [((b, mp), jnp.int32)])
    text = _compiled_text(
        lambda *a: quant_decode_attention_paged_fwd(
            *a, group=GROUP, page_size=PAGE, n_kv_heads=HKV,
            interpret=False),
        shapes, one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [4, 8])
def test_srft_quant_kernels_compile(one_chip, bits):
    n = BH * W  # one flush window of every row
    cols = D // 2 if bits == 4 else D
    code = jnp.uint8 if bits == 4 else jnp.int8
    quant = _compiled_text(
        lambda x, m: srft_quant_fwd(x, m, group=GROUP, bits=bits,
                                    interpret=False),
        [((n, D), jnp.float32), ((D, D), jnp.float32)], one_chip)
    dequant = _compiled_text(
        lambda p, s, m: srft_dequant_fwd(p, s, m, group=GROUP, bits=bits,
                                         interpret=False),
        [((n, cols), code), ((n, D // GROUP), jnp.float32),
         ((D, D), jnp.float32)], one_chip)
    assert "tpu_custom_call" in quant and "tpu_custom_call" in dequant


def test_engine_decode_quantum_keeps_its_names(one_chip, monkeypatch):
    """The engine's decode quantum, at internlm2-1.8b widths (one layer,
    paged int4-srft, the Pallas kernel), compiled for the chip: its
    module is ``jit_decode_quantum`` and the kernel's custom call is the
    instruction ``quant_decode_attention_paged_fwd.<n>``, the names the
    benchmark's device-trace readers match."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.launch.batch_engine import BatchEngine
    from repro.models import build_model

    cfg = dataclasses.replace(get_config("internlm2-1.8b"), n_layers=1,
                              kv_group=GROUP, kv_window=W).validated()
    mdl = build_model(cfg)
    params = jax.eval_shape(mdl.init, jax.random.PRNGKey(0))
    eng = BatchEngine(mdl, params, capacity=2, s_max=4 * PAGE,
                      policy="int4-srft", backend="kernel", chunk=2,
                      paged=True, page_size=PAGE,
                      key=jax.random.PRNGKey(0))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    # trace as the chip would: bf16 dot operands, compiled kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = on_chip((params, eng.tok, eng.cache,
                    jnp.zeros((2,), bool), jnp.zeros((2,), jnp.int32),
                    eng._sample_key))
    text = eng._chunk_fn(2).lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_decode_quantum,"), text[:200]
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert any(c.startswith("quant_decode_attention_paged_fwd.")
               for c in calls), calls
