"""Paged KV-cache pool (core/paged.py + the BatchEngine paged mode,
DESIGN.md §10).

Three layers of evidence, mirroring the module's invariants:

* **Allocator properties** (hypothesis when installed, fixed grids in
  the fast lane -- the tests/_hypothesis_stub.py pattern): alloc/free
  round-trips never double-free (refcounts are clamped at zero and hit
  zero exactly once under balanced use), allocated pages are unique,
  never the null page, and always previously free; COW forks preserve
  bit-identical prefix reads while the fork's own writes stay private.

* **Paged-parity oracle** (ISSUE-4 acceptance): batched decode through
  ``PagedCacheState`` is bit-identical PER ROW to the PR-3 dense
  ragged-slot path for every policy x supported backend -- including
  after a COW prefix fork (shared-prefix admissions) and after
  preemption + re-admission (recompute rebuilds the cache bit-exactly
  and the resumed stream continues from the same full-width decode
  dispatch).  The dense engine is itself validated against
  single-sequence runs (test_engine.py), so the oracle chain bottoms
  out at the scalar path.

* **Pool accounting**: a shared-prefix workload holds ONE physical copy
  of the prefix pages (refcounts == number of sharers, page counts
  below the no-sharing footprint), retirement returns every page, and
  ``nbytes(persistent_only=False)`` owns up to the page-table +
  free-list metadata.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - exercised by the fast CI lane
    from _hypothesis_stub import given, settings, st

from repro.configs.paper_models import SMOL_D64
from repro.core import paged as P
from repro.core.cache_api import available_policies, get_policy
from repro.kernels.quant_attention.quant_attention import paged_tile_pages
from repro.launch.batch_engine import BatchEngine, Request
from repro.models import build_model

MAX_EXAMPLES = 20


# ---------------------------------------------------------------------------
# Block allocator properties
# ---------------------------------------------------------------------------

def _check_alloc_free_roundtrip(n_pages, n_rounds, seed):
    """Random alloc/fork/free schedule against a host mirror: allocated
    pages are unique, non-null and previously free; refcounts track the
    mirror exactly; releasing everything restores a fully-free pool."""
    rng = np.random.default_rng(seed)
    pool = P.pool_init(n_pages)
    mirror = np.zeros(n_pages, np.int64)
    mirror[P.NULL_PAGE] = 1
    rows = []  # list of page-id lists (one per live "request")
    max_pages = max(2, (n_pages - 1) // 2)
    for _ in range(n_rounds):
        op = rng.integers(0, 3)
        free_now = int((mirror == 0).sum())
        if op == 0 and free_now:  # alloc
            n = int(rng.integers(1, min(free_now, max_pages) + 1))
            pool, pages = P.pool_alloc(pool, jnp.asarray(n), max_pages)
            pages = np.asarray(pages)
            got = pages[:n]
            assert (got != P.NULL_PAGE).all()
            assert len(set(got.tolist())) == n, "duplicate allocation"
            assert (mirror[got] == 0).all(), "allocated an in-use page"
            assert (pages[n:] == P.NULL_PAGE).all()
            mirror[got] += 1
            rows.append(got.tolist())
        elif op == 1 and rows:  # fork: share an existing row's pages
            src = rows[int(rng.integers(len(rows)))]
            pad = np.full(max_pages, P.NULL_PAGE, np.int64)
            pad[:len(src)] = src
            pool = P.pool_incref(pool, jnp.asarray(pad))
            mirror[src] += 1
            rows.append(list(src))
        elif op == 2 and rows:  # free one row
            row = rows.pop(int(rng.integers(len(rows))))
            pool = P.pool_free(pool, jnp.asarray(np.asarray(row)))
            mirror[row] -= 1
        np.testing.assert_array_equal(np.asarray(pool.refcount), mirror)
        assert int(P.pool_n_free(pool)) == int((mirror == 0).sum())
    for row in rows:  # drain
        pool = P.pool_free(pool, jnp.asarray(np.asarray(row)))
        mirror[row] -= 1
    np.testing.assert_array_equal(np.asarray(pool.refcount), mirror)
    assert int(P.pool_used(pool)) == 0
    assert int(P.pool_n_free(pool)) == n_pages - 1  # null stays pinned


def _check_refcount_zero_once_and_clamp(n_refs, n_pages, seed):
    """A page referenced ``n_refs`` times hits zero exactly once (on the
    final balanced free), and further frees are clamped at zero -- a
    double free can never wrap a counter negative or free the null
    page."""
    del seed
    pool = P.pool_init(n_pages)
    pool, pages = P.pool_alloc(pool, jnp.asarray(1), 2)
    page = int(np.asarray(pages)[0])
    one = jnp.asarray([page])
    for _ in range(n_refs - 1):
        pool = P.pool_incref(pool, one)
    zero_hits = 0
    for _ in range(n_refs + 2):  # two deliberate double frees at the end
        pool = P.pool_free(pool, one)
        rc = int(np.asarray(pool.refcount)[page])
        assert rc >= 0, "refcount went negative"
        zero_hits += rc == 0
    assert zero_hits == 3  # zero reached once, then CLAMPED twice
    assert int(np.asarray(pool.refcount)[P.NULL_PAGE]) == 1


def _check_cow_fork_prefix_bits(n_prefix_pages, ps, seed):
    """Fork a row's full prefix pages into a second row: both rows read
    BIT-IDENTICAL prefix bytes through their own page tables, and the
    fork's private tail writes never leak into the source (nor vice
    versa)."""
    H, d = 2, 8
    MP = n_prefix_pages + 2
    s_max = MP * ps
    rng = np.random.default_rng(seed)
    pd = P.init_paged(2, s_max, page_size=ps,
                      n_pages=2 * MP + 1,
                      leaf_specs=((H, d, jnp.float32),))
    plen = n_prefix_pages * ps + ps // 2  # partial tail page
    row = jnp.asarray(rng.standard_normal((1, H, s_max, d)), jnp.float32)
    need = -(-(plen + ps) // ps)
    nul = jnp.full((MP,), P.NULL_PAGE, jnp.int32)
    # row 0: all private
    pd = P.insert_row(pd, (row,), (), jnp.asarray([plen]), 0,
                      nul, jnp.asarray(0), jnp.asarray(need))
    # row 1: COW-forks row 0's full prefix pages, copies the tail
    shared = jnp.asarray(np.concatenate([
        np.asarray(pd.page_table)[0, :n_prefix_pages],
        np.full(MP - n_prefix_pages, P.NULL_PAGE, np.int32)]))
    pd = P.insert_row(pd, (row,), (), jnp.asarray([plen]), 1,
                      shared, jnp.asarray(n_prefix_pages),
                      jnp.asarray(need - n_prefix_pages))
    ptab = np.asarray(pd.page_table)
    rc = np.asarray(pd.pool.refcount)
    assert (rc[ptab[0, :n_prefix_pages]] == 2).all()
    np.testing.assert_array_equal(ptab[0, :n_prefix_pages],
                                  ptab[1, :n_prefix_pages])
    assert ptab[0, n_prefix_pages] != ptab[1, n_prefix_pages], \
        "the partial tail page must be a private copy"
    view0 = np.asarray(P.gather_view(pd)[0])
    np.testing.assert_array_equal(view0[0, :, :plen], view0[1, :, :plen])
    # divergent tail appends on each row stay private: the shared prefix
    # bytes are untouched, the tails differ
    for t in range(ps):
        val = jnp.asarray(rng.standard_normal((2, H, 1, d)), jnp.float32)
        pd = P.append_token(pd, (val,))
    view1 = np.asarray(P.gather_view(pd)[0])
    np.testing.assert_array_equal(view1[0, :, :plen], view0[0, :, :plen])
    np.testing.assert_array_equal(view1[1, :, :plen], view0[1, :, :plen])
    L = plen + ps
    assert not np.array_equal(view1[0, :, plen:L], view1[1, :, plen:L])


@settings(max_examples=MAX_EXAMPLES, deadline=None)
@given(n_pages=st.integers(3, 40), n_rounds=st.integers(1, 25),
       seed=st.integers(0, 2 ** 16))
def test_property_alloc_free_roundtrip(n_pages, n_rounds, seed):
    _check_alloc_free_roundtrip(n_pages, n_rounds, seed)


@settings(max_examples=MAX_EXAMPLES, deadline=None)
@given(n_refs=st.integers(1, 9), n_pages=st.integers(3, 20),
       seed=st.integers(0, 2 ** 16))
def test_property_refcount_zero_exactly_once(n_refs, n_pages, seed):
    _check_refcount_zero_once_and_clamp(n_refs, n_pages, seed)


@settings(max_examples=10, deadline=None)
@given(n_prefix_pages=st.integers(1, 4), ps=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 2 ** 16))
def test_property_cow_fork_prefix_bit_identical(n_prefix_pages, ps, seed):
    _check_cow_fork_prefix_bits(n_prefix_pages, ps, seed)


@pytest.mark.parametrize("n_pages,n_rounds,seed",
                         [(3, 6, 0), (9, 20, 1), (33, 25, 2)])
def test_grid_alloc_free_roundtrip(n_pages, n_rounds, seed):
    _check_alloc_free_roundtrip(n_pages, n_rounds, seed)


@pytest.mark.parametrize("n_refs", [1, 3, 8])
def test_grid_refcount_zero_exactly_once(n_refs):
    _check_refcount_zero_once_and_clamp(n_refs, n_pages=7, seed=0)


@pytest.mark.parametrize("n_prefix_pages,ps", [(1, 2), (3, 4), (2, 8)])
def test_grid_cow_fork_prefix_bit_identical(n_prefix_pages, ps):
    _check_cow_fork_prefix_bits(n_prefix_pages, ps, seed=11)


def test_pool_validation_and_null_page():
    with pytest.raises(ValueError, match="n_pages"):
        P.pool_init(1)
    pool = P.pool_init(4)
    # over-asking clamps to the free supply: never hands out a used page
    pool, pages = P.pool_alloc(pool, jnp.asarray(10), 6)
    pages = np.asarray(pages)
    assert (pages[:3] != P.NULL_PAGE).all() and (pages[3:] == 0).all()
    assert int(P.pool_n_free(pool)) == 0
    with pytest.raises(ValueError, match="multiple of page_size"):
        P.init_paged(1, 10, page_size=4, n_pages=4,
                     leaf_specs=((1, 2, jnp.float32),))
    with pytest.raises(ValueError, match="flush window"):
        get_policy("int4-srft", window=16).init_paged(
            1, 1, 64, 32, n_pages=4, page_size=8)


# ---------------------------------------------------------------------------
# Paged-parity oracle: BatchEngine paged vs dense ragged slots
# ---------------------------------------------------------------------------

S_MAX = 64
PAGE = 32
# the paged kernel's tile (P pages); dense engines run kv_block == TILE,
# so dense and paged kernels then tile identically
TILE = paged_tile_pages(PAGE, S_MAX // PAGE) * PAGE
RAGGED_PROMPTS = (9, 17, 23)
RAGGED_NEW = (12, 20, 7)


@pytest.fixture(scope="module")
def lm():
    model = build_model(SMOL_D64)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(lens, base=40):
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(base + i), (L,), 0, SMOL_D64.vocab_size))
        for i, L in enumerate(lens)]


def _run_engine(model, params, reqs, *, policy, backend, paged,
                capacity=3, s_max=S_MAX, **kw):
    eng = BatchEngine(model, params, capacity=capacity, s_max=s_max,
                      policy=policy, backend=backend, kv_block=TILE,
                      chunk=4, key=jax.random.PRNGKey(7), paged=paged, **kw)
    got = {c.rid: c for c in eng.run(list(reqs))}
    return eng, got


def _policy_backend_cases():
    cases = []
    for name in available_policies():
        pol = get_policy(name)
        for b in pol.supported_backends:
            cases.append((name, b))
    return cases


@pytest.mark.slow
@pytest.mark.parametrize("policy,backend", _policy_backend_cases())
def test_paged_engine_matches_dense_engine(lm, policy, backend):
    """ISSUE-4 acceptance oracle: paged decode == dense ragged decode,
    bit for bit per row, for every policy x supported backend.  The
    kernel case exercises the paged Pallas path (page-table scalar
    prefetch, P pages a tile) in interpret mode."""
    model, params = lm
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts(RAGGED_PROMPTS),
                                           RAGGED_NEW))]
    _, dense = _run_engine(model, params, reqs, policy=policy,
                           backend=backend, paged=False)
    eng, pag = _run_engine(model, params, reqs, policy=policy,
                           backend=backend, paged=True, page_size=PAGE)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(
            pag[i].tokens, dense[i].tokens,
            err_msg=f"{policy}/{backend.value} row {i} diverged from the "
                    f"dense ragged-slot path",
        )
    # retirement returned every page to the allocator
    assert eng.pool_stats()["pages_used"] == 0


def test_paged_engine_matches_dense_engine_fast(lm):
    """Fast-lane slice of the oracle: one policy/backend pair."""
    model, params = lm
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts((9, 17)), (8, 6)))]
    _, dense = _run_engine(model, params, reqs, policy="int4-srft",
                           backend="gather", paged=False, capacity=2)
    eng, pag = _run_engine(model, params, reqs, policy="int4-srft",
                           backend="gather", paged=True, capacity=2,
                           page_size=16)
    for i in range(2):
        np.testing.assert_array_equal(pag[i].tokens, dense[i].tokens)
    assert eng.pool_stats()["pages_used"] == 0


@pytest.mark.slow
def test_shared_prefix_holds_one_physical_copy(lm):
    """COW acceptance: requests sharing a page-aligned prompt prefix map
    the SAME physical pages (refcount == number of sharers, pool usage
    below the no-sharing footprint) and still decode bit-identically to
    the dense engine, which shares nothing."""
    model, params = lm
    n_req = 4
    prefix = np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (32,), 0, SMOL_D64.vocab_size))
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [prefix, np.asarray([100 + i])]).astype(np.int32),
                    max_new_tokens=8)
            for i in range(n_req)]
    _, dense = _run_engine(model, params, reqs, policy="int4-srft",
                           backend="gather", paged=False, capacity=n_req)

    eng = BatchEngine(model, params, capacity=n_req, s_max=S_MAX,
                      policy="int4-srft", backend="gather", kv_block=PAGE,
                      chunk=4, key=jax.random.PRNGKey(7), paged=True,
                      page_size=16)
    for r in reqs:
        eng.submit(r)
    got = {}
    ev, comp = eng.step()  # all admitted: sharing is observable now
    n_prefix_pages = 32 // 16
    rc = eng._refcount_host
    assert int((rc == n_req).sum()) == n_prefix_pages, \
        "prefix pages must carry one reference per sharer"
    stats = eng.pool_stats()
    no_share = n_req * eng._pages_needed(33, 8)
    assert stats["pages_used"] < no_share
    assert stats["shared_pages"] == n_prefix_pages
    for c in comp:
        got[c.rid] = c
    while eng.pending or eng.n_active:
        _, comp = eng.step()
        for c in comp:
            got[c.rid] = c
    for i in range(n_req):
        np.testing.assert_array_equal(got[i].tokens, dense[i].tokens)
    assert eng.pool_stats()["pages_used"] == 0


@pytest.mark.slow
def test_preemption_requeue_is_bit_exact(lm):
    """LRU preemption-to-queue: an undersized pool forces recompute
    preemption, and every request's stitched token stream still matches
    the dense (never-preempting) engine bit for bit -- re-admission
    rebuilds the cache bytes exactly and resumes the pending token in
    the tok buffer (no cross-width sample)."""
    model, params = lm
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts((9, 20)), (10, 8)))]
    _, dense = _run_engine(model, params, reqs, policy="int4-srft",
                           backend="gather", paged=False, capacity=2,
                           s_max=48)
    # pages needed: ceil(19/16)=2 and ceil(28/16)=2; 3 usable pages
    # cannot hold both rows -> the scheduler must preempt
    eng, pag = _run_engine(model, params, reqs, policy="int4-srft",
                           backend="gather", paged=True, capacity=2,
                           s_max=48, page_size=16, n_pages=4)
    assert eng.n_preemptions > 0, "undersized pool must preempt"
    for i in range(2):
        np.testing.assert_array_equal(
            pag[i].tokens, dense[i].tokens,
            err_msg=f"request {i} diverged across preemption",
        )
        assert pag[i].prompt_len == dense[i].prompt_len
        assert pag[i].finish_reason == dense[i].finish_reason
    assert eng.pool_stats()["pages_used"] == 0


def test_paged_decode_step_donates_cache(lm):
    """The paged decode step aliases pools, page tables and refcounts in
    place: paging must not reintroduce the per-step O(pool) copy."""
    model, params = lm
    cache = model.init_cache(2, S_MAX, policy="int4-srft",
                             key=jax.random.PRNGKey(7), ragged=True,
                             n_pages=9, page_size=16)
    tok = jnp.zeros((2, 1), jnp.int32)
    active = jnp.asarray([True, False])
    step = jax.jit(
        lambda p, t, c, a: model.decode_step(p, t, c, active=a),
        donate_argnums=(2,),
    )
    txt = step.lower(params, tok, cache, active).compile().as_text()
    assert "input_output_alias" in txt
    _, new_cache = step(params, tok, cache, active)
    jax.block_until_ready(new_cache)
    pd = cache["attn"].data.kv
    for i, leaf in enumerate(pd.pools):
        assert leaf.is_deleted(), f"pool leaf {i} was copied"
    assert pd.page_table.is_deleted(), "page table was copied"
    assert pd.pool.refcount.is_deleted(), "refcounts were copied"
    np.testing.assert_array_equal(
        np.asarray(new_cache["attn"].lengths[0]), [1, 0]
    )


def test_paged_nbytes_owns_up_to_metadata(lm):
    """Satellite: ``persistent_only=False`` adds exactly the page-table
    + free-list (+ int4 residual) bytes, so reported compression for
    paged states is honest about the paging bookkeeping."""
    for pname in available_policies():
        pol = get_policy(pname, group=8, window=16)
        st_ = pol.init_paged(2, 2, 64, 32, n_pages=9, page_size=16,
                             key=jax.random.PRNGKey(0))
        pd = st_.data if pname != "int4-srft" else st_.data.kv
        extra = st_.nbytes(persistent_only=False) - st_.nbytes()
        want = P.meta_nbytes(pd)
        if pname == "int4-srft":
            want += sum(x.size * x.dtype.itemsize for x in pd.residual)
        assert extra == want, pname
        assert pol.compression_ratio(st_) > 0


def test_paged_engine_validation(lm):
    """The constructor floor (pool holds >= one full row + the null
    page) is exactly what makes every s_max-bounded request admissible
    under some preemption schedule -- undersized pools are rejected up
    front, not discovered as a livelock mid-serve."""
    model, params = lm
    with pytest.raises(ValueError, match="cannot hold"):
        BatchEngine(model, params, capacity=1, s_max=32, policy="bf16",
                    paged=True, page_size=8, n_pages=3)
    eng = BatchEngine(model, params, capacity=1, s_max=32, policy="bf16",
                      paged=True, page_size=8, n_pages=5)
    with pytest.raises(ValueError, match="exceeds s_max"):
        eng.submit(Request(rid=0, prompt=np.zeros(30, np.int32),
                           max_new_tokens=8))


# ---------------------------------------------------------------------------
# Paged Pallas kernel unit test (page-table indirection)
# ---------------------------------------------------------------------------

# (G, flush window W, s_max, slot 0's first length, final lengths of
# slots 0 and 1).  16-token pages: a tile is P = min(MP, 16) pages.
SHUFFLE_CASES = {
    # one tile a row (MP = 4 = P), both rows ending in their last page
    "one_tile": (2, 16, 64, 22, (37, 37)),
    # MP = 36, not a multiple of P = 16; slot 1 holds residual tokens only
    # (packed_len 0); slot 0's packed prefix ends mid-tile at a page edge
    "residual_only_row": (2, 16, 576, 22, (300, 9)),
    # G = 5; slot 0's packed prefix ends exactly on the first tile's end,
    # slot 1 has 2 live pages, fewer than P
    "tile_boundary_g5": (5, 16, 576, 22, (261, 40)),
    # W = 8 < page: slot 0's packed prefix (520) ends mid-tile AND mid-page
    "mid_page": (2, 8, 576, 22, (527, 100)),
}


@pytest.mark.parametrize("case", list(SHUFFLE_CASES))
def test_paged_kernel_walks_shuffled_pages(case):
    """The paged kernel must follow the page table, not physical page
    order: decode attention over a row whose pages are deliberately
    NON-CONTIGUOUS (allocated across a free/realloc cycle) matches the
    gather oracle on the same state, wherever each row's packed prefix
    ends against the kernel's many-page tiles."""
    G, window, S, first, (len0, len1) = SHUFFLE_CASES[case]
    ps = 16
    pol = get_policy("int4-srft", group=8, window=window)
    B, H, D = 2, 2, 32
    key = jax.random.PRNGKey(3)
    MP = S // ps
    state = pol.init_paged(B, H, S, D, n_pages=2 * MP + 1, page_size=ps,
                           key=key)
    nul = jnp.full((MP,), P.NULL_PAGE, jnp.int32)

    def admit(state, slot, L, seed):
        row = pol.init_state(1, H, S, D, key=key, ragged=True)
        k = jax.random.normal(jax.random.fold_in(key, seed), (1, H, L, D))
        v = jax.random.normal(jax.random.fold_in(key, 9 + seed),
                              (1, H, L, D))
        row = pol.prefill(row, k, v)
        return pol.insert_row_paged(state, row, jnp.asarray(slot), nul,
                                    jnp.asarray(0),
                                    jnp.asarray(-(-L // ps)))

    # slot0 takes the first pages, slot1 the next ones; freeing slot0 and
    # re-admitting a LONGER row reuses slot0's pages and then jumps past
    # slot1's (e.g. [1, 2, 6])
    state = admit(state, 0, first, 0)
    state = admit(state, 1, len1, 1)
    state = pol.reset_rows(state, jnp.asarray([True, False]))
    state = admit(state, 0, len0, 2)
    ptab = np.asarray(state.data.kv.page_table)
    mapped = ptab[0][ptab[0] != P.NULL_PAGE]
    assert (np.diff(mapped) != 1).any(), \
        f"expected non-contiguous pages, got {ptab[0]}"
    np.testing.assert_array_equal(np.asarray(state.data.kv.length),
                                  [len0, len1])
    q = jax.random.normal(jax.random.fold_in(key, 77), (B, G * H, 1, D))
    out_k = pol.attend(q, state, backend="kernel")
    out_g = pol.attend(q, state, backend="gather")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_g),
                               atol=2e-5, rtol=2e-5)
