"""Multi-device sharded serving: the PR-10 acceptance oracle.

A mesh-sharded ``BatchEngine`` (KV pools split by KV head over the
'model' axis, params and scheduler state replicated, DESIGN.md §16)
must stream BIT-IDENTICAL per-row tokens to the single-device engine --
for every cache policy, dense and paged layouts, and through every
scheduler event that rewrites cache bytes: COW prefix forks, recompute
preemption + resume, and speculative-decode rollback.

Bit-identity is by construction, not tolerance: the ``serve_exact``
activation policy pins projections and the merged attention output
replicated (full-width matmuls -- XLA:CPU reduction order depends on
operand widths, the §9 width-matched-oracle effect), so only the attend
against the head-sharded cache computes per shard, and a head split is
a batch-dim split (no cross-shard reduction).  Every assert here is
``assert_array_equal``.

This lane needs a simulated mesh: run it as its own pytest process with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -q tests/test_sharded_serving.py

(the CI ``mesh-smoke`` job does exactly this).  On a single-device host
every test skips cleanly via the ``needs_devices`` marker -- the flag
must be set before jax initializes, which a fixture cannot do.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import SMOL_D64, SMOL_D256
from repro.core.cache_api import AttendBackend
from repro.launch.batch_engine import BatchEngine, Request
from repro.launch.engine import Engine

pytestmark = pytest.mark.needs_devices(8)

S_MAX = 64
POLICIES = ("bf16", "int4-srft", "int8-per-token")


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh

    devs = jax.devices()
    # a TRUE 8-way mesh: 'model' (=2) divides SMOL_D64's Hkv=2, 'data'
    # carries the rest (batch/scheduler state is replicated, so the
    # data axis only proves the rules ignore it)
    return Mesh(np.array(devs).reshape(4, 2), ("data", "model"))


@pytest.fixture(scope="module")
def lm():
    from repro.models import build_model

    model = build_model(SMOL_D64)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lm_mqa():
    from repro.models import build_model

    model = build_model(SMOL_D256)  # MQA: Hkv=1, the replication rung
    return model, model.init(jax.random.PRNGKey(0))


def _prompts(lens, base=40):
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(base + i), (L,), 0, SMOL_D64.vocab_size))
        for i, L in enumerate(lens)]


def _run(model, params, reqs, *, mesh, **kw):
    kw.setdefault("capacity", 3)
    kw.setdefault("s_max", S_MAX)
    kw.setdefault("chunk", 4)
    kw.setdefault("kv_block", 16)
    eng = BatchEngine(model, params, key=jax.random.PRNGKey(7),
                      mesh=mesh, **kw)
    out = {c.rid: (tuple(map(int, c.tokens)), c.finish_reason)
           for c in eng.run(list(reqs))}
    return out, eng


def _assert_stream_parity(ref, got, tag):
    assert sorted(got) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(
            got[rid][0], ref[rid][0],
            err_msg=f"{tag}: row {rid} diverged from single-device",
        )
        assert got[rid][1] == ref[rid][1], f"{tag}: finish_reason {rid}"


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("policy", POLICIES)
def test_sharded_stream_parity(lm, mesh, policy, paged):
    """The acceptance oracle: every policy x dense/paged, mixed prompt
    lengths, bit-identical streams AND final cache bytes."""
    model, params = lm
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts((9, 17, 23)),
                                           (10, 8, 6)))]
    kw = dict(policy=policy, backend="gather", paged=paged, page_size=16)
    ref, ref_eng = _run(model, params, reqs, mesh=None, **kw)
    got, eng = _run(model, params, reqs, mesh=mesh, **kw)
    _assert_stream_parity(ref, got, f"{policy}/{'paged' if paged else 'dense'}")
    # the retired caches must hold the same bytes leaf for leaf: the
    # scheduler replayed the same admissions/retirements and every
    # device op was bit-exact (np.asarray gathers sharded leaves)
    for (pth, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(ref_eng.cache),
        jax.tree_util.tree_leaves_with_path(eng.cache),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"cache leaf {jax.tree_util.keystr(pth)}",
        )


def test_sharded_cow_fork_parity(lm, mesh):
    """COW prefix sharing on the sharded pool: sharers map the same
    physical pages (replicated page table / refcounts) and forked rows
    still decode bit-identically to the dense single-device engine."""
    model, params = lm
    prefix = np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (32,), 0, SMOL_D64.vocab_size))
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [prefix, np.asarray([100 + i])]).astype(np.int32),
                    max_new_tokens=8)
            for i in range(3)]
    ref, _ = _run(model, params, reqs, mesh=None, policy="int4-srft",
                  backend="gather", paged=False)
    eng = BatchEngine(model, params, capacity=3, s_max=S_MAX,
                      policy="int4-srft", backend="gather", kv_block=16,
                      chunk=4, key=jax.random.PRNGKey(7), paged=True,
                      page_size=16, mesh=mesh)
    for r in reqs:
        eng.submit(r)
    got = {}
    _, comp = eng.step()  # all admitted: sharing observable now
    rc = eng._refcount_host
    assert int((rc == 3).sum()) == 32 // 16, \
        "prefix pages must carry one reference per sharer (sharded pool)"
    for c in comp:
        got[c.rid] = (tuple(map(int, c.tokens)), c.finish_reason)
    while eng.pending or eng.n_active:
        _, comp = eng.step()
        for c in comp:
            got[c.rid] = (tuple(map(int, c.tokens)), c.finish_reason)
    _assert_stream_parity(ref, got, "cow-fork")
    assert eng.pool_stats()["pages_used"] == 0


def test_sharded_preemption_resume_parity(lm, mesh):
    """An undersized sharded pool preempts (pages freed, request
    requeued) and the recompute-resumed stream still matches the
    never-preempting single-device dense engine bit for bit."""
    model, params = lm
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts((9, 20)), (10, 8)))]
    ref, _ = _run(model, params, reqs, mesh=None, policy="int4-srft",
                  backend="gather", paged=False, capacity=2, s_max=48)
    got, eng = _run(model, params, reqs, mesh=mesh, policy="int4-srft",
                    backend="gather", paged=True, capacity=2, s_max=48,
                    page_size=16, n_pages=4)
    assert eng.n_preemptions > 0, "undersized pool must preempt"
    _assert_stream_parity(ref, got, "preempt-resume")
    assert eng.pool_stats()["pages_used"] == 0


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_sharded_spec_rollback_parity(lm, mesh, paged):
    """Self-speculative decoding on the sharded cache: k-wide verify
    appends + truncate_rows rollback of rejected drafts leave streams
    bit-identical to the plain (non-speculative) single-device run."""
    model, params = lm
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts((9, 17)), (12, 10)))]
    kw = dict(policy="int4-srft", capacity=2, paged=paged, page_size=16)
    ref, _ = _run(model, params, reqs, mesh=None, **kw)
    got, eng = _run(model, params, reqs, mesh=mesh, spec_k=4, **kw)
    _assert_stream_parity(ref, got, f"spec4/{'paged' if paged else 'dense'}")
    assert 0 <= eng.n_accepted <= eng.n_drafted


def test_sharded_single_stream_engine_parity(lm, mesh):
    """launch/engine.Engine under a mesh: generate() tokens AND every
    stored cache byte identical to the unsharded engine (the serve_exact
    trace-time hints make the projection matmuls full-width)."""
    model, params = lm
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0,
                              SMOL_D64.vocab_size)

    def run(mesh_):
        eng = Engine(model, backend="gather", mesh=mesh_)
        cache = model.init_cache(2, S_MAX, policy="int4-srft",
                                 key=jax.random.PRNGKey(1))
        p = params
        if mesh_ is not None:
            p = eng.shard_params(p)
            cache = eng.shard_cache(cache)
        out, cache = eng.generate(p, toks, cache, 12,
                                  key=jax.random.PRNGKey(5))
        return np.asarray(out), cache

    ref_out, ref_cache = run(None)
    got_out, got_cache = run(mesh)
    np.testing.assert_array_equal(got_out, ref_out)
    for (pth, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(ref_cache),
        jax.tree_util.tree_leaves_with_path(got_cache),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"cache leaf {jax.tree_util.keystr(pth)}",
        )


def test_mqa_degrades_to_replication_and_stays_exact(lm_mqa, mesh):
    """SMOL_D256 is MQA (Hkv=1): heads cannot divide the 'model' axis,
    so serve_cache_specs degrades every KV leaf to replication -- the
    engine must still compile and match single-device exactly."""
    from jax.sharding import PartitionSpec as P

    from repro.launch import partitioning as pt

    model, params = lm_mqa
    cache = model.init_cache(2, 32, policy="int4-srft",
                             key=jax.random.PRNGKey(1), ragged=True)
    specs = pt.serve_cache_specs(cache, mesh)
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))

    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(_prompts((7, 11)))]
    kw = dict(policy="int4-srft", backend="gather", capacity=2, s_max=48)
    ref, _ = _run(model, params, reqs, mesh=None, **kw)
    got, _ = _run(model, params, reqs, mesh=mesh, **kw)
    _assert_stream_parity(ref, got, "mqa-replicated")


def test_kernel_backend_falls_back_under_mesh(lm, mesh):
    """The Pallas kernel read path is single-device; asking for it on a
    mesh is an error that names the backend to use instead, never a
    silent swap."""
    model, params = lm
    with pytest.raises(ValueError, match="blockwise"):
        BatchEngine(model, params, capacity=2, s_max=32,
                    policy="int4-srft", backend="kernel",
                    key=jax.random.PRNGKey(7), mesh=mesh)
    eng = BatchEngine(model, params, capacity=2, s_max=32,
                      policy="int4-srft", backend="blockwise",
                      key=jax.random.PRNGKey(7), mesh=mesh)
    assert eng.backend is AttendBackend.BLOCKWISE


def test_nbytes_per_shard_vs_global(lm, mesh):
    """Regression for the per-shard vs global accounting split:
    ``nbytes()`` is global-logical (invariant under sharding);
    ``per_shard=True`` shrinks KV by the model-axis factor while
    replicated paging metadata still counts in full."""
    from repro.launch import partitioning as pt

    model, _ = lm
    msize = mesh.shape["model"]
    for paged in (False, True):
        cache = model.init_cache(
            2, S_MAX, policy="int4-srft", key=jax.random.PRNGKey(1),
            ragged=True, n_pages=9 if paged else None,
            page_size=16 if paged else None,
        )
        st = cache["attn"]
        sharded = jax.device_put(cache, pt.make_shardings(
            pt.serve_cache_specs(cache, mesh), mesh))["attn"]
        # global-logical: identical before/after sharding, and the
        # default (so existing reports/benchmarks cannot change)
        assert sharded.nbytes() == st.nbytes()
        assert sharded.nbytes(persistent_only=False) == \
            st.nbytes(persistent_only=False)
        # per-shard: persistent KV (head-sharded) divides exactly
        assert sharded.nbytes(per_shard=True) == st.nbytes() // msize
        # unsharded state: per_shard is a no-op, not an error
        assert st.nbytes(per_shard=True) == st.nbytes()
        ratio = st.policy.compression_ratio(st)
        assert sharded.policy.compression_ratio(sharded) == ratio
        if paged:
            # replicated metadata does NOT shrink: per-shard total is
            # strictly more than total/msize
            tot = sharded.nbytes(persistent_only=False)
            per = sharded.nbytes(persistent_only=False, per_shard=True)
            assert per > tot // msize
            from repro.core import paged as paged_mod

            pd = sharded.data.kv
            assert paged_mod.meta_nbytes(pd, per_shard=True) == \
                paged_mod.meta_nbytes(pd)
