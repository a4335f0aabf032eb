"""Every program the engine jits carries a fixed name, so its XLA module
is ``jit_<name>`` whatever the code around it, with or without a mesh
(the device trace is read by these names: ``jit_decode_quantum`` is the
decode quantum's module on the chip).

The engine runs the paths that reach every program (monolithic, packed
and chunked admission, a device prefix hit and a host-tier restore,
retirement, plain and speculative decode); each program's first call is
captured as shapes and shardings and lowered again here.  The mesh case
runs in a subprocess with four CPU devices (the device count is fixed
when JAX starts)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAMES = {
    "_prefill_fn": "prefill",
    "_insert_fn": "insert_row",
    "_insert_paged_fn": "insert_row_paged",
    "_reset_fn": "reset_rows",
    "_chunk_prefill_fn": "prefill_chunk",
    "_seed_fn": "seed_row",
    "_import_fn": "import_pages",
    "_raw_view_fn": "raw_view",
    "_slice_row_fn": "slice_row",
}
QUANTA = {"_chunk_fn": "decode_quantum", "_spec_chunk_fn": "spec_quantum"}
ALL = set(NAMES.values()) | set(QUANTA.values())


def _abstract(x):
    if isinstance(x, jax.Array):  # an uncommitted array goes anywhere
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    return x


def _capture(eng, seen: dict) -> None:
    """Record each program's first call (shapes, not arrays: donated
    buffers are gone after the call)."""
    def wrap(fn, name):
        def call(*args):
            seen.setdefault(name, (fn, jax.tree.map(_abstract, args)))
            return fn(*args)
        return call

    for attr, name in NAMES.items():
        setattr(eng, attr, wrap(getattr(eng, attr), name))
    for attr, name in QUANTA.items():
        make = getattr(eng, attr)
        setattr(eng, attr,
                lambda n, make=make, name=name: wrap(make(n), name))


def module_names(mesh=None) -> dict:
    """Program name -> the module name its lowering carries."""
    from repro.configs.paper_models import SMOL_D64
    from repro.launch.batch_engine import BatchEngine, Request
    from repro.models import build_model

    model = build_model(SMOL_D64)
    params = model.init(jax.random.PRNGKey(0))
    seen: dict = {}

    def engine(**kw):
        eng = BatchEngine(model, params, capacity=3, s_max=64, chunk=4,
                          kv_block=16, key=jax.random.PRNGKey(7), mesh=mesh,
                          **kw)
        _capture(eng, seen)
        return eng

    def prompt(n, seed=40):
        return np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed), (n,), 0, SMOL_D64.vocab_size))

    def run(eng, reqs):
        for _ in eng.run(list(reqs)):
            pass

    # monolithic and packed admission, retirement, decode
    dense = engine(policy="bf16")
    run(dense, [Request(rid=0, prompt=prompt(9), max_new_tokens=5)])
    dense.admit_packed([Request(rid=1, prompt=prompt(12, 1),
                                max_new_tokens=3),
                        Request(rid=2, prompt=prompt(12, 2),
                                max_new_tokens=3)])
    run(dense, [])
    # chunked admission: a device prefix hit, then a host-tier restore
    paged = engine(policy="int4-srft", paged=True, page_size=16,
                   prefill_chunk=16, offload_bytes=1 << 24)
    same = prompt(40)
    run(paged, [Request(rid=3, prompt=same, max_new_tokens=8),
                Request(rid=4, prompt=same, max_new_tokens=8)])
    run(paged, [Request(rid=5, prompt=same, max_new_tokens=8)])
    assert paged.n_reuse_hits_device >= 1 and paged.n_reuse_hits_host >= 1
    # speculative decode
    spec = engine(policy="bf16", spec_k=2)
    run(spec, [Request(rid=6, prompt=prompt(9), max_new_tokens=6)])

    out = {}
    for name, (fn, args) in seen.items():
        text = fn.lower(*args).as_text()
        out[name] = text.split("module @", 1)[1].split()[0]
    return out


def _check(got: dict) -> None:
    assert set(got) == ALL, sorted(ALL - set(got))
    for name, module in got.items():
        assert module == f"jit_{name}", (name, module)


def test_program_names_without_a_mesh():
    _check(module_names())


def test_program_names_on_a_four_device_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), HERE]))
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    code = textwrap.dedent("""
        import json
        import jax
        import numpy as np
        from jax.sharding import Mesh
        import test_program_names as t
        assert jax.device_count() == 4
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2),
                    ("data", "model"))
        print(json.dumps(t.module_names(mesh)))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    _check(json.loads(out.stdout.strip().splitlines()[-1]))
