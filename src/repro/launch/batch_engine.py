"""Continuous batching: ragged multi-request serving over a slot cache.

The fused engine (launch/engine.py) decodes ONE request stream per
dispatch.  Serving "heavy traffic" means decoding many requests of
different lengths together -- and the paper's bandwidth argument only
survives batching if each row streams bytes proportional to ITS OWN
prefix, not the batch max (DESIGN.md §9).  This module is that layer:

``BatchEngine``
    A fixed-capacity slot cache (one ragged ``CacheState`` per layer:
    per-row ``lengths``) plus a host-side scheduler.

    * **admit**: a queued request is prefilled alone (batch-1 ragged
      cache sharing the slot cache's rotations), then copied into a free
      slot with ``policy.insert_row`` -- one donated-buffer scatter, no
      re-trace, the rest of the batch keeps decoding.
    * **decode**: the whole batch advances ``chunk`` tokens in ONE
      donated-buffer ``lax.scan`` dispatch.  Finished rows are masked by
      an in-carry ``active`` vector (their lengths stand still, their
      lane output is discarded); masks are data, so admissions and
      retirements never recompile.
    * **retire**: completed slots get ``policy.reset_rows`` (lengths to
      zero) and go back into the free list; the scheduler then admits
      from the queue.

    Per-request sampling keys are split off the engine key at admission,
    and each row's token stream is bit-identical to running that request
    alone through ``launch.engine.Engine`` with a greedy sampler (the
    ragged-parity oracle in tests/test_engine.py asserts this for every
    policy x backend).

Paged mode (``paged=True``; DESIGN.md §10) swaps the dense slot stripes
for a page pool (core/paged.py): each slot maps its tokens through a
page table, admission allocates only the pages a request actually
needs, and requests whose prompts share a page-aligned prefix map the
SAME physical pages copy-on-write (the engine keeps a host-side prefix
index keyed by page-aligned token prefixes; hits bump refcounts instead
of allocating).  Admission control is on free pages: when the pool
cannot fit the next request, the least-recently-admitted live slot is
*preempted to the queue* -- its pages are released and a continuation
request (prompt + generated-so-far, recompute-style) is requeued at the
front.  Because every cache write is deterministic, recompute rebuilds
bit-identical pages; ``Completion``s stitch carried tokens back
together so callers never see the preemption (greedy streams are
unchanged; temperature streams resample from re-admission).

Chunked prefill (``prefill_chunk=C``; DESIGN.md §11) removes the one
stall left in this design: a monolithic admission prefills the WHOLE
prompt in one dispatch, so a 4K-token arrival freezes every live decode
stream for the full prefill.  With chunking, admission becomes a
*pending* state machine: each scheduler quantum processes at most
``prefill_budget`` prompt tokens (in C-token chunk dispatches through
``model.prefill_chunk``) and then runs the decode chunk as usual -- so
live streams advance EVERY iteration while the admission makes
progress (Sarathi-style stall-free continuous batching).  Chunk
boundaries are page-aligned (paged mode) and flush-window-aligned, so
every policy's ``prefill_chunk`` write path produces byte-identical
cache state to a monolithic prefill; the chunk's queries attend a raw
bf16 K/V side buffer (not the quantized cache), which makes the whole
chunked admission bit-identical to the monolithic one -- tokens and
cache bytes (tests/test_chunked_prefill.py asserts it per policy x
backend x dense/paged).

Chunked + paged admissions also get token-level prefix reuse: the
engine keeps the token arrays of resident prompts next to the PR-4
page-aligned prefix index, finds the longest token-level shared prefix
(aligned down to the int4 flush window W), seeds the admission row
straight from the donor's resident pages (``policy.adopt_prefix``) and
starts chunking AFTER the shared tokens -- shared chunks are never
computed, and the first divergent page is forked copy-on-write at
insert exactly as before.  For quantized policies the suffix then
attends a dequantized view of the reused prefix (the same bytes every
decode step reads -- cache-consistent); bf16 reuse is bit-exact.

Typical use::

    eng = BatchEngine(model, params, capacity=8, s_max=2048,
                      policy="int4-srft", backend="kernel",
                      prefill_chunk=256)   # None = monolithic admission
    eng.submit(Request(rid=0, prompt=toks_a, max_new_tokens=128))
    eng.submit(Request(rid=1, prompt=toks_b, max_new_tokens=64))
    for completion in eng.run():
        ...  # Completion(rid, tokens, ...) as each request finishes

or drive ``step()`` directly for token-level streaming.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import deque
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache_api import AttendBackend
from repro.core.paged import NULL_PAGE, PagedData
from repro.kernels.quant_attention.quant_attention import paged_tile_pages
from repro.launch.engine import (
    GREEDY, Sampler, draft_tokens, resolve_mesh_backend, _serve_policy_ctx,
)
from repro.launch.prefix_store import PrefixStore

__all__ = ["Request", "Completion", "BatchEngine"]


@dataclasses.dataclass
class Request:
    """One generation request.  ``max_new_tokens`` counts every sampled
    token, including the one drawn from the prefill logits (the same
    convention as ``Engine.generate``'s ``n_tokens``).

    ``resume_tok`` is engine-internal (paged preemption): a preempted
    request is requeued with its generated-so-far tokens folded into
    the prompt EXCEPT the last sampled one, which resumes in the token
    buffer -- re-admission then recomputes the cache bit-identically
    and draws no admission token, so the continued stream is produced
    by the same full-width decode dispatch as an unpreempted run
    (bit-parity survives preemption)."""

    rid: int
    prompt: Any  # (S,) int array
    max_new_tokens: int
    resume_tok: Optional[int] = None


@dataclasses.dataclass
class Completion:
    rid: int
    prompt_len: int
    tokens: np.ndarray  # (n_generated,) int32
    finish_reason: str  # "length" | "eos" | "cancelled"


@dataclasses.dataclass
class _PendingAdmission:
    """Engine-internal: one in-flight chunked admission (DESIGN.md §11).

    ``row`` is the dense batch-1 ragged staging cache filling chunk by
    chunk; ``raw_k``/``raw_v`` are the per-layer raw bf16 K/V side
    buffers its chunks attend (shape ``(n_layers, 1, Hkv, n_total,
    hd)``); ``n_done`` counts prompt tokens already in the row --
    including ``reused_tokens`` seeded from a donor's resident pages,
    which were never computed.  ``logits`` holds the last processed
    chunk's final-token logits (the admission sample comes from them
    once ``n_done == n_total``)."""

    req: Request
    slot: int
    row: Any
    raw_k: Any
    raw_v: Any
    n_done: int
    n_total: int
    logits: Any = None
    reused_tokens: int = 0


class BatchEngine:
    """Continuous-batching engine for one (model, policy, backend,
    sampler) configuration.

    Compiled callables are cached per prompt length (prefill) and per
    chunk size (decode); slot churn is pure data.  ``eos_id`` is a
    static early-stop token (None = length-only).  The decode chunk is
    the scheduling quantum: smaller chunks admit waiting requests
    sooner, larger chunks amortize dispatch overhead.
    """

    def __init__(self, model, params, *, capacity: int, s_max: int,
                 policy=None, backend: "AttendBackend | str | None" = None,
                 sampler: Optional[Sampler] = None, kv_block: int = 512,
                 chunk: int = 8, eos_id: Optional[int] = None,
                 rots=None, key: Optional[jax.Array] = None,
                 donate: bool = True, paged: bool = False,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 prefix_reuse: bool = True,
                 offload_bytes: Optional[int] = None,
                 offload_dir: Optional[str] = None,
                 spec_k: Optional[int] = None,
                 trace=None, mesh=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.model = model
        self.params = params
        self.capacity = capacity
        self.policy = model.cache_policy(policy)
        # multi-device serving (DESIGN.md §16): KV pools sharded by head
        # over the mesh's 'model' axis, scheduler state and params
        # replicated.  All host-side bookkeeping below (mirrors, prefix
        # index, admission control) is sharding-oblivious: readbacks see
        # the same replicated metadata a single device would hold.
        self.mesh = mesh
        self.backend = resolve_mesh_backend(
            None if backend is None else AttendBackend.parse(backend), mesh
        )
        self.sampler = sampler if sampler is not None else GREEDY
        self.kv_block = kv_block
        self.chunk = chunk
        self.eos_id = eos_id
        self.donate = donate
        self._rots = rots
        self._init_key = key if key is not None else jax.random.PRNGKey(0)

        # self-speculative decoding (DESIGN.md §13): each scan step of
        # the decode chunk becomes a draft-verify-accept-rollback pass
        # that advances every live row by 1..spec_k tokens
        self.spec_k = spec_k
        if spec_k is not None:
            if self.sampler.temperature != 0.0:
                raise ValueError(
                    "spec_k requires greedy sampling (temperature == 0): "
                    "exact-match acceptance against the verify argmax is "
                    "what keeps per-row output bit-identical"
                )
            if spec_k < 2:
                raise ValueError(f"spec_k must be >= 2, got {spec_k}")
            W = getattr(self.policy, "window", None)
            if W is not None and spec_k > W:
                raise ValueError(
                    f"spec_k={spec_k} must be <= the policy flush window "
                    f"W={W}: a verify pass appends at most one "
                    f"residual-ring wrap (DESIGN.md §13)"
                )

        self.paged = paged
        if paged:
            # logical extent is whole pages; the pool defaults to the
            # dense slot footprint (capacity x max_pages) + null page --
            # pass a smaller n_pages to actually oversubscribe (LRU
            # preemption kicks in when it runs dry)
            s_max += (-s_max) % page_size
            self.page_size = page_size
            self.max_pages = s_max // page_size
            self.n_pages = (capacity * self.max_pages + 1
                            if n_pages is None else n_pages)
            if self.n_pages < self.max_pages + 1:
                raise ValueError(
                    f"n_pages={self.n_pages} cannot hold even one full "
                    f"row ({self.max_pages} pages + the null page)"
                )
        self.s_max = s_max

        # chunked prefill (DESIGN.md §11): chunk boundaries must be
        # flush-window-aligned (every non-final chunk ends at a W
        # boundary, so policy.prefill_chunk replays monolithic bytes)
        # and, in paged mode, page-aligned (an int4 flush slab then
        # never straddles a page -- the §10 invariant carries over).
        # page_size % W == 0 is already enforced by init_paged, so
        # page alignment implies W alignment.
        self._align = max(int(getattr(self.policy, "window", 1) or 1), 1)
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}"
                )
            if paged and prefill_chunk % page_size:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a multiple of "
                    f"page_size={page_size} (chunk boundaries are page "
                    f"boundaries, so flush slabs never straddle a page)"
                )
            if prefill_chunk % self._align:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a multiple of "
                    f"the policy flush window W={self._align} (chunked "
                    f"admission replays monolithic prefill bytes only at "
                    f"W-aligned chunk boundaries)"
                )
        if prefill_budget is not None and prefill_chunk is None:
            raise ValueError(
                "prefill_budget only bounds CHUNKED admission; pass "
                "prefill_chunk too (monolithic admission has no "
                "per-quantum token bound)"
            )
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1, got {prefill_budget}"
            )
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = (
            prefill_budget if prefill_budget is not None else prefill_chunk
        )
        self.prefix_reuse = prefix_reuse
        self._pending: Optional[_PendingAdmission] = None
        self.n_prefill_chunks = 0
        self.n_reused_tokens = 0

        # thread-safe step API (DESIGN.md §12): the serving pipeline
        # runs admission, decode and intake on different threads, all
        # serialized on this lock (one device; the overlap the pipeline
        # buys is host work against device work, never two dispatches).
        # ``step_listeners`` are called with every non-empty (events,
        # completions) pair -- the detokenize stage consumes the stream
        # without polling step() return values.
        self.lock = threading.RLock()
        self.step_listeners: list[
            Callable[[list[tuple[int, list[int]]], list[Completion]], None]
        ] = []

        # request-scoped tracing (DESIGN.md §15): spans/instants into a
        # lock-cheap ring buffer.  Lazy import: repro.launch.server
        # imports pipeline -> this module, so a top-level import here
        # would cycle.  The default recorder is disabled -- every trace
        # call is then one attribute check.
        if trace is None:
            from repro.launch.server.tracing import TraceRecorder
            trace = TraceRecorder(capacity=1, enabled=False)
        self._trace = trace
        # prefix-tier attribution per request outcome (ISSUE-9): which
        # tier first admitted each live rid (device COW / host restore /
        # miss; "none" for dense engines), folded into tier_outcomes at
        # retirement keyed by finish reason.
        self._admit_tier: dict[int, str] = {}
        self.tier_outcomes: dict[str, dict[str, int]] = {}

        # the slot cache: one ragged CacheState per layer, plus per-row
        # pos.  Row caches built at admission reuse _init_key/_rots so
        # their rotations are bit-identical to the slot cache's (an
        # insert_row requirement).  Rotations are embedded as COPIES:
        # every cache here is eventually donated, and donating a buffer
        # that aliases the caller's ``rots`` would delete it out from
        # under the next admission.
        self.cache = self._shard_cache_tree(model.init_cache(
            capacity, s_max, policy=self.policy, rots=self._rots_copy(),
            key=self._init_key, ragged=True,
            n_pages=self.n_pages if paged else None,
            page_size=page_size if paged else None,
        ))
        if mesh is not None:
            # replicate params + per-slot scheduler arrays: full-width
            # (bit-exact) projections, and any device can own any slot
            self.params = self._replicate_tree(params)
        self.tok = self._replicate_tree(
            jnp.zeros((capacity, 1), jnp.int32)  # last sampled
        )
        self.active = np.zeros((capacity,), bool)  # host mirror
        self.budget = np.zeros((capacity,), np.int32)  # decode steps left
        self._slot_req: list[Optional[Request]] = [None] * capacity
        self._slot_toks: list[list[int]] = [[] for _ in range(capacity)]
        self._queue: deque[Request] = deque()
        self._sample_key = jax.random.fold_in(self._init_key, 0x5A5A)

        if spec_k is not None:
            # per-slot drafter history: prompt + every sampled token.
            # Device-resident (the spec chunk carries it); admission
            # reseeds one row host-side.  Capacity: total tokens per row
            # is bounded by s_max - spec_k + 1 (_validate slack) and each
            # pass writes spec_k wide at hlen, so s_max + spec_k covers
            # the k-wide tail write with room to spare.
            self._hist_cap = s_max + spec_k
            self._hist = self._replicate_tree(
                jnp.zeros((capacity, self._hist_cap), jnp.int32))
            self._hlen = self._replicate_tree(
                jnp.zeros((capacity,), jnp.int32))
            self._spec_chunk_fns: dict[int, Any] = {}
            self.n_drafted = 0   # draft positions scored (excl. bonus)
            self.n_accepted = 0  # draft positions accepted (excl. bonus)

        # host-RAM offload tier (DESIGN.md §14): parks evicted prefix
        # pages' bytes behind the device index.  Only meaningful for a
        # paged pool -- dense engines have no prefix index to back.
        self.prefix_store: Optional[PrefixStore] = None
        if offload_bytes is not None and not paged:
            raise ValueError(
                "offload_bytes requires paged=True: the host tier stores "
                "evicted pool pages behind the prefix index (DESIGN.md §14)"
            )
        if offload_bytes is not None and prefill_chunk is None:
            raise ValueError(
                "offload_bytes requires chunked admission (prefill_chunk): "
                "a host-tier restore seeds the staging row and resumes "
                "prefill after the restored tokens -- monolithic admission "
                "has no resume path (DESIGN.md §14)"
            )

        if paged:
            # host-side pool bookkeeping: a refcount mirror drives
            # admission control, a prefix index maps page-aligned token
            # prefixes to resident physical pages (COW sharing), and
            # per-slot admission sequence numbers pick the LRU
            # preemption victim.  ``_carried``/``_orig`` stitch
            # preempted requests' token streams back together.
            self._refcount_host = np.zeros((self.n_pages,), np.int32)
            self._refcount_host[NULL_PAGE] = 1
            self._ptab_host = np.full((capacity, self.max_pages),
                                      NULL_PAGE, np.int32)
            self._prefix_pages: dict[bytes, int] = {}
            # token-level reuse (DESIGN.md §11): resident prompts' token
            # arrays + their physical pages, so chunked admissions can
            # skip a PARTIAL shared prefix (aligned down to W), not just
            # page-aligned ones.  Pruned with _prefix_pages.
            self._prefix_seqs: dict[bytes, tuple[np.ndarray, np.ndarray]] = {}
            self._slot_seq = [0] * capacity
            self._admit_seq = 0
            self._carried: dict[int, list[int]] = {}
            self._orig: dict[int, tuple[int, int]] = {}  # rid -> (plen, max_new)
            self.n_preemptions = 0
            self.peak_pages = 0
            if offload_bytes is not None:
                self.prefix_store = PrefixStore(offload_bytes, offload_dir)
                self.prefix_store.trace = self._trace
            # tier traffic: device COW hit / host restore / full prefill,
            # counted once per chunked admission (DESIGN.md §14)
            self.n_spilled_pages = 0
            self.n_restored_pages = 0
            self.n_restored_tokens = 0
            self.n_reuse_hits_device = 0
            self.n_reuse_hits_host = 0
            self.n_reuse_misses = 0

        # Every program is jitted under a fixed name, so its XLA module
        # is jit_<name> on the chip whatever the code around it, and the
        # device trace can be read by program (PERF.md, span table).
        # jit specializes per prompt-length shape on its own; one wrapper
        def prefill(p, t, c):
            return self.model.prefill(p, t, c)

        self._prefill_fn = jax.jit(
            self._traced(prefill, "prefill"),
            donate_argnums=(2,) if donate else (),
        )
        self._chunk_fns: dict[int, Any] = {}
        self._insert_fn = jax.jit(
            self._traced(self._insert_impl, "insert_row"),
            donate_argnums=(0,) if donate else ()
        )
        self._insert_paged_fn = jax.jit(
            self._traced(self._insert_paged_impl, "insert_row_paged"),
            donate_argnums=(0,) if donate else ()
        )
        self._reset_fn = jax.jit(
            self._traced(self._reset_impl, "reset_rows"),
            donate_argnums=(0,) if donate else ()
        )

        # chunked prefill: one jitted chunk dispatch (specializes per
        # (chunk_len, prompt_len) shape pair -- same compilation economy
        # as _prefill_fn), plus the paged-reuse seed/backfill helpers
        def prefill_chunk(p, t, row, rk, rv):
            return self.model.prefill_chunk(p, t, row, rk, rv)

        self._chunk_prefill_fn = jax.jit(
            self._traced(prefill_chunk, "prefill_chunk"),
            donate_argnums=(2, 3, 4) if donate else (),
        )
        self._seed_fn = jax.jit(
            self._traced(self._seed_impl, "seed_row"),
            donate_argnums=(0,) if donate else ()
        )
        self._import_fn = jax.jit(
            self._traced(self._import_impl, "import_pages"),
            donate_argnums=(0,) if donate else ()
        )
        self._raw_view_fn = jax.jit(
            self._traced(self._raw_view_impl, "raw_view"),
            static_argnums=(1, 2))
        # packed admission (DESIGN.md §12): slice one row out of a
        # batch-k staging cache (the staging cache is reused for every
        # row, so it is NOT donated here)
        self._slice_axes: Optional[tuple] = None
        self._slice_row_fn = jax.jit(
            self._traced(self._slice_row_impl, "slice_row"))

    @property
    def trace(self):
        return self._trace

    @trace.setter
    def trace(self, rec) -> None:
        # the serving front-end swaps in its (enabled) recorder after
        # construction; keep the offload tier pointed at the same one
        self._trace = rec
        if self.prefix_store is not None:
            self.prefix_store.trace = rec

    @property
    def n_rejected(self) -> int:
        """Spec-decode draft positions rolled back (drafted - accepted)."""
        if self.spec_k is None:
            return 0
        return int(self.n_drafted) - int(self.n_accepted)

    def _record_tier(self, rid: int, tier: str) -> None:
        """First admission wins: a preemption-resume keeps the tier the
        request was ORIGINALLY admitted from."""
        self._admit_tier.setdefault(rid, tier)

    def _count_outcome(self, rid: int, reason: str) -> None:
        tier = self._admit_tier.pop(rid, "none")
        byo = self.tier_outcomes.setdefault(tier, {})
        byo[reason] = byo.get(reason, 0) + 1

    def _rots_copy(self):
        return None if self._rots is None \
            else jax.tree.map(jnp.copy, self._rots)

    # ---------------------------------------------------------- mesh layout
    def _traced(self, fn, name: str):
        """Wrap a to-be-jitted callable under the fixed ``name`` (its
        XLA module is then ``jit_<name>``), tracing it under the
        serve_exact activation policy when the engine has a mesh
        (launch/act_sharding, DESIGN.md §16)."""
        mesh = self.mesh

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if mesh is None:
                return fn(*args, **kwargs)
            with _serve_policy_ctx(mesh):
                return fn(*args, **kwargs)

        inner.__name__ = inner.__qualname__ = name
        return inner

    def _shard_cache_tree(self, cache):
        """Lay a cache pytree (the slot cache or a staging row) out
        across the mesh: KV heads over 'model' where divisible, else
        replication (partitioning.serve_cache_specs).  Staging rows get
        the same layout as the slot cache, so ``insert_row``'s scatters
        stay shard-local.  Identity without a mesh."""
        if self.mesh is None:
            return cache
        from repro.launch import partitioning as pt

        specs = pt.serve_cache_specs(cache, self.mesh)
        return jax.device_put(cache, pt.make_shardings(specs, self.mesh))

    def _replicate_tree(self, tree):
        """Replicate every leaf across the mesh; identity without one."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.device_put(tree, jax.tree.map(lambda _: rep, tree))

    # ------------------------------------------------------------ jit bodies
    def _insert_impl(self, batched, row, slot, tok_buf, tok0):
        pol = self.policy
        attn = jax.vmap(pol.insert_row, in_axes=(0, 0, None))(
            batched["attn"], row["attn"], slot
        )
        pos = jax.lax.dynamic_update_slice(batched["pos"], row["pos"],
                                           (slot,))
        tok_buf = jax.lax.dynamic_update_slice(tok_buf, tok0, (slot, 0))
        return dict(batched, attn=attn, pos=pos), tok_buf

    def _insert_paged_impl(self, batched, row, slot, tok_buf, tok0,
                           shared_pages, n_shared, n_new):
        """Paged admission: COW-share ``n_shared`` prefix pages, allocate
        ``n_new`` fresh ones (pure pool ops inside the jit), scatter the
        dense row's tiles into them.  All page arguments are traced --
        admission never recompiles."""
        pol = self.policy
        attn = jax.vmap(
            pol.insert_row_paged, in_axes=(0, 0, None, None, None, None)
        )(batched["attn"], row["attn"], slot, shared_pages, n_shared, n_new)
        pos = jax.lax.dynamic_update_slice(batched["pos"], row["pos"],
                                           (slot,))
        tok_buf = jax.lax.dynamic_update_slice(tok_buf, tok0, (slot, 0))
        return dict(batched, attn=attn, pos=pos), tok_buf

    def _reset_impl(self, batched, mask):
        pol = self.policy
        attn = jax.vmap(pol.reset_rows, in_axes=(0, None))(
            batched["attn"], mask
        )
        pos = jnp.where(mask, 0, batched["pos"])
        return dict(batched, attn=attn, pos=pos)

    def _seed_impl(self, row, batched, pages, n_tok):
        """Token-level reuse seed: adopt the donor's resident page bytes
        into the staging row (vmapped over layers) and set its length to
        the shared token count -- chunked prefill then resumes AFTER the
        shared tokens."""
        pol = self.policy
        attn = jax.vmap(pol.adopt_prefix, in_axes=(0, 0, None, None))(
            row["attn"], batched["attn"], pages, n_tok
        )
        return dict(row, attn=attn, pos=jnp.full_like(row["pos"], n_tok))

    def _import_impl(self, row, payload, n_tok):
        """Host-tier restore seed (DESIGN.md §14): write exported page
        tiles into the staging row (vmapped over layers) and set its
        length -- chunked prefill then resumes AFTER the restored
        tokens, exactly like a device-tier adopt.  The unchanged COW
        insert plan later scatters these exact bytes into fresh pool
        pages, so the restored pages are bit-identical to the donor's."""
        pol = self.policy
        attn = jax.vmap(pol.import_pages, in_axes=(0, 0, None))(
            row["attn"], payload, n_tok
        )
        return dict(row, attn=attn, pos=jnp.full_like(row["pos"], n_tok))

    def _raw_view_impl(self, row, s_shared: int, s_prompt: int):
        """Backfill the raw K/V side buffers from a seeded staging row:
        bf16 rows read back bit-exactly; quantized rows dequantize (and
        inverse-rotate), so reused-prefix reads carry the same
        quantization error every decode read does (cache-consistent;
        DESIGN.md §11).  Only the ``[0, s_shared)`` extent is
        meaningful (the rest is zero-padded and overwritten by chunk
        writes before it is ever attended), and slicing there lets XLA
        narrow the dequant to the adopted tokens instead of the row's
        full capacity."""
        k, v = jax.vmap(self.policy.raw_kv_view)(row["attn"])
        pad = ((0, 0),) * 3 + ((0, s_prompt - s_shared), (0, 0))

        def clip(x):
            return jnp.pad(x[..., :s_shared, :].astype(jnp.bfloat16), pad)

        return clip(k), clip(v)

    def _row_slice_axes(self) -> tuple:
        """Per-leaf batch-axis map for slicing one row out of a batch-k
        staging cache: None where the leaf is batch-independent (shared
        rotation constants -- bit-identical across every staging cache
        built from ``_init_key``), else the axis whose extent is the
        staging batch.  Derived by diffing ABSTRACT shapes of batch-1 vs
        batch-2 staging caches (``jax.eval_shape``: no arrays are
        materialized), so the rule cannot be confused by head counts or
        capacities that happen to equal the group size."""
        if self._slice_axes is None:
            def shapes(b):
                return jax.eval_shape(lambda: self.model.init_cache(
                    b, self.s_max, policy=self.policy,
                    rots=self._rots_copy(), key=self._init_key, ragged=True,
                ))

            axes = []
            for t1, t2 in zip(jax.tree.leaves(shapes(1)),
                              jax.tree.leaves(shapes(2))):
                if t1.shape == t2.shape:
                    axes.append(None)
                    continue
                diff = [i for i, (a, b) in enumerate(zip(t1.shape, t2.shape))
                        if a != b]
                if len(diff) != 1 or t1.shape[diff[0]] != 1:
                    raise AssertionError(
                        f"cannot locate the batch axis of a staging-cache "
                        f"leaf: {t1.shape} vs {t2.shape}"
                    )
                axes.append(diff[0])
            self._slice_axes = tuple(axes)
        return self._slice_axes

    def _slice_row_impl(self, staged, j):
        """Batch-1 view of row ``j`` of a batch-k staging cache, shaped
        exactly like a monolithic admission's staging row -- feeds the
        shared ``_insert_row`` path.  ``j`` is traced: one compilation
        per staging shape, not per row."""
        axes = self._row_slice_axes()
        leaves = jax.tree.leaves(staged)
        out = [
            leaf if ax is None
            else jax.lax.dynamic_slice_in_dim(leaf, j, 1, axis=ax)
            for leaf, ax in zip(leaves, axes)
        ]
        return jax.tree.unflatten(jax.tree.structure(staged), out)

    # ------------------------------------------------------- paged pool state
    def _pd(self) -> PagedData:
        """Layer-stacked PagedData of the slot cache (leaves lead with
        the layer axis; layer 0 is the host bookkeeping view -- every
        layer's pool state is identical by construction)."""
        d = self.cache["attn"].data
        return d if isinstance(d, PagedData) else d.kv

    def _sync_pool(self) -> None:
        """Refresh the host mirrors (refcounts, page table) from layer 0
        of the device pool, track peak residency, and prune prefix-index
        entries whose page was freed (a freed page may be reallocated
        with different content; a stale hit would alias wrong bytes).

        This is a blocking readback, but only at admission/retire time
        (never per token), the arrays are tiny (one int32 per page +
        the table), and the caller already blocks on the device there
        anyway (``_admit`` pulls the sampled token to host).  The
        allocator's determinism would let the mirror be predicted
        host-side instead if admission rate ever makes this matter."""
        pd = self._pd()
        self._refcount_host = np.asarray(pd.pool.refcount)[0]
        self._ptab_host = np.asarray(pd.page_table)[0]
        used = int((self._refcount_host > 0).sum()) - 1  # null pinned
        self.peak_pages = max(self.peak_pages, used)
        dead = [k for k, p in self._prefix_pages.items()
                if self._refcount_host[p] == 0]
        for k in dead:
            del self._prefix_pages[k]
        dead_seq = [k for k, (_, pgs) in self._prefix_seqs.items()
                    if (self._refcount_host[pgs] == 0).any()]
        for k in dead_seq:
            del self._prefix_seqs[k]

    def _pages_needed(self, prompt_len: int, max_new: int) -> int:
        # spec_k - 1 slack: verify passes transiently append past the
        # last kept position, and the paged read path clamps page-table
        # lookups -- unmapped transient tokens would alias page 0
        slack = self.spec_k - 1 if self.spec_k is not None else 0
        return -(-(prompt_len + max_new + slack) // self.page_size)

    def _plan_pages(self, req: Request):
        """Host-side admission plan: walk the prefix index page by page
        (COW hits must be prefix-contiguous), then check the remainder
        against the free supply.  Returns (shared_page_ids, n_new) or
        None when the pool cannot fit the request right now."""
        prompt = np.asarray(req.prompt, np.int32)
        ps = self.page_size
        total = self._pages_needed(prompt.shape[-1], req.max_new_tokens)
        shared: list[int] = []
        for i in range(prompt.shape[-1] // ps):
            key = prompt[:(i + 1) * ps].tobytes()
            page = self._prefix_pages.get(key)
            if page is None or self._refcount_host[page] == 0 \
                    or not self._page_backed(page, i, key):
                break
            shared.append(page)
        n_new = total - len(shared)
        if n_new > int((self._refcount_host == 0).sum()):
            return None
        return shared, n_new

    def _page_backed(self, page: int, idx: int, key: bytes) -> bool:
        """True iff some LIVE slot's page table maps ``page`` at entry
        ``idx`` and that slot's prompt spells the key's tokens -- the
        ground truth a prefix-index hit must agree with.  Free-time
        pruning (:meth:`_release_slots`) keeps stale entries out of the
        index; this guard makes a stale COW hit *structurally*
        impossible even if a page is freed and reallocated to different
        content between a free and the next index prune (the
        free->realloc->plan window, DESIGN.md §14)."""
        end = (idx + 1) * self.page_size
        for s in range(self.capacity):
            req = self._slot_req[s]
            if req is None or int(self._ptab_host[s, idx]) != page:
                continue
            p = np.asarray(req.prompt, np.int32)
            if p.shape[-1] >= end and p[:end].tobytes() == key:
                return True
        return False

    def _donor_live(self, toks: np.ndarray, pages: np.ndarray,
                    n_tokens: int) -> bool:
        """Token-level analogue of :meth:`_page_backed`: a donor entry
        is only usable while some live slot still maps exactly these
        pages for exactly these tokens."""
        npg = -(-n_tokens // self.page_size)
        want = pages[:npg]
        for s in range(self.capacity):
            req = self._slot_req[s]
            if req is None:
                continue
            if not np.array_equal(self._ptab_host[s, :npg], want):
                continue
            p = np.asarray(req.prompt, np.int32)
            if p.shape[-1] >= n_tokens \
                    and np.array_equal(p[:n_tokens], toks[:n_tokens]):
                return True
        return False

    def _register_prefix(self, req: Request, slot: int) -> None:
        """Index this row's full prompt pages for future COW admissions.
        Only *full* prompt pages are registered: they are immutable
        (decode appends and int4 flushes target positions at or past
        the admission-time packed length, which live in later pages)."""
        prompt = np.asarray(req.prompt, np.int32)
        ps = self.page_size
        row = self._ptab_host[slot]
        for i in range(prompt.shape[-1] // ps):
            self._prefix_pages[prompt[:(i + 1) * ps].tobytes()] = int(row[i])
        # token-level index entry (DESIGN.md §11): the prompt's tokens +
        # every page its prompt touches (incl. a partial tail page --
        # its packed slots below the prompt's flush boundary are
        # immutable deterministic bytes, which is all reuse ever adopts)
        n_pp = -(-prompt.shape[-1] // ps)
        self._prefix_seqs[prompt.tobytes()] = (
            prompt.copy(), row[:n_pp].copy()
        )

    def _release_slots(self, slots) -> None:
        """Free-time hook, called BEFORE the reset that drops these
        slots' page references, while the page bytes are still resident.

        Two jobs (DESIGN.md §14): (1) spill registered prefix pages
        about to hit refcount zero into the host store -- their exported
        bytes restore bit-identically later; (2) prune every prefix
        index entry those dying pages back.  Free-time pruning closes
        the stale-index window: a freed page can be reallocated with
        different content before the next ``_sync_pool``, whose
        refcount==0 sweep cannot see a page that died and was reborn in
        between.  Page tables are fixed at admission (pages cover
        prompt + max_new up front), so the host mirrors are current here
        even though the last device sync predates recent decode steps."""
        if not self.paged:
            return
        slots = list(np.atleast_1d(np.asarray(slots, np.int64)))
        if not slots:
            return
        drops = np.zeros((self.n_pages,), np.int32)
        for s in slots:
            pages = self._ptab_host[int(s)]
            np.add.at(drops, pages[pages != NULL_PAGE], 1)
        rc = self._refcount_host
        dying = (rc > 0) & (rc - drops <= 0)
        dying[NULL_PAGE] = False
        if not dying.any():
            return
        if self.prefix_store is not None:
            spill = [(k, p) for k, p in self._prefix_pages.items()
                     if dying[p]]
            fresh = [(k, p) for k, p in spill
                     if k not in self.prefix_store]
            if fresh:
                leaves = self.policy.export_pages(
                    self.cache["attn"], [p for _, p in fresh]
                )
                for j, (k, _) in enumerate(fresh):
                    self.prefix_store.put(
                        k, tuple(leaf[:, j] for leaf in leaves)
                    )
                self.n_spilled_pages += len(fresh)
                self._trace.instant("offload.spill", cat="offload",
                                    tier="host", pages=len(fresh))
            for k, _ in spill:
                # content is deterministic in the key's tokens (§10), so
                # a re-spill of a present key is just a recency touch
                self.prefix_store.touch(k)
        for k in [k for k, p in self._prefix_pages.items() if dying[p]]:
            del self._prefix_pages[k]
        for k in [k for k, (_, pgs) in self._prefix_seqs.items()
                  if dying[pgs].any()]:
            del self._prefix_seqs[k]

    def _preempt_one(self, protect_from_seq: int) -> bool:
        """Preempt the least-recently-admitted live slot to the FRONT of
        the queue as a recompute continuation (prompt + generated so
        far, remaining budget).  Frees its pages immediately.  Slots
        admitted during the CURRENT admission round (seq >=
        ``protect_from_seq``) are never victims -- preempting work that
        has not decoded since admission makes no progress and would
        livelock the admission loop.  A slot reserved by an in-flight
        chunked admission is never a victim either (it holds no cache
        row yet).  Returns False when nothing is eligible."""
        pend_slot = self._pending.slot if self._pending is not None else None
        live = [s for s in range(self.capacity)
                if self._slot_req[s] is not None
                and self._slot_seq[s] < protect_from_seq
                and s != pend_slot]
        if not live:
            return False
        slot = min(live, key=lambda s: self._slot_seq[s])
        req = self._slot_req[slot]
        toks = self._slot_toks[slot]
        self._carried[req.rid] = self._carried.get(req.rid, []) + list(toks)
        # prompt absorbs every token the cache has appended: the original
        # prompt, a still-pending resume token from an earlier
        # preemption, and all but the last newly sampled token -- which
        # is sampled-but-not-yet-appended (exactly the dense engine's
        # state) and resumes in the token buffer at re-admission
        gen = ([] if req.resume_tok is None else [req.resume_tok]) \
            + list(toks)
        cont = Request(
            rid=req.rid,
            prompt=np.concatenate([np.asarray(req.prompt, np.int32),
                                   np.asarray(gen[:-1], np.int32)]),
            max_new_tokens=req.max_new_tokens - len(toks),
            resume_tok=int(gen[-1]),
        )
        self._queue.appendleft(cont)
        self._slot_req[slot] = None
        self._slot_toks[slot] = []
        self.active[slot] = False
        self.budget[slot] = 0
        ptab = self._ptab_host[slot]
        self._trace.instant(
            "engine.preempt", cat="sched", rid=req.rid, slot=int(slot),
            pages=int((ptab != NULL_PAGE).sum()),
            carried=len(self._carried[req.rid]),
        )
        self._release_slots([slot])
        mask = np.zeros((self.capacity,), bool)
        mask[slot] = True
        self.cache = self._reset_fn(self.cache, jnp.asarray(mask))
        self._sync_pool()
        self.n_preemptions += 1
        return True

    def pool_stats(self) -> Optional[dict]:
        """Pool utilization snapshot (None for dense engines): page
        counts, live per-request page spans and COW sharing, plus byte
        accounting (pool bytes from the policy's own nbytes, so serving
        and benchmarks cannot drift)."""
        if not self.paged:
            return None
        with self.lock:
            return self._pool_stats_locked()

    def _pool_stats_locked(self) -> dict:
        rc = self._refcount_host
        used = int((rc > 0).sum()) - 1
        usable = self.n_pages - 1
        live = [s for s in range(self.capacity)
                if self._slot_req[s] is not None]
        mapped = int((self._ptab_host[live] != NULL_PAGE).sum()) if live \
            else 0
        pool_bytes = self.policy.nbytes(self.cache["attn"])
        page_bytes = pool_bytes / self.n_pages
        # host-side footprint (DESIGN.md §14): the device accounting
        # above is blind to the mirrors, the prefix-index keys, and the
        # offload tier -- all host RAM the pool spends to run
        key_bytes = sum(len(k) for k in self._prefix_pages)
        seq_bytes = sum(len(k) + t.nbytes + pg.nbytes
                        for k, (t, pg) in self._prefix_seqs.items())
        host_bytes = {
            "refcount_mirror": int(rc.nbytes),
            "page_table_mirror": int(self._ptab_host.nbytes),
            "prefix_index": int(key_bytes + seq_bytes),
            "offload_store": int(self.prefix_store.nbytes)
            if self.prefix_store is not None else 0,
        }
        host_bytes["total"] = sum(host_bytes.values())
        offload = {
            "enabled": self.prefix_store is not None,
            "spilled_pages": self.n_spilled_pages,
            "restored_pages": self.n_restored_pages,
            "restored_tokens": self.n_restored_tokens,
            "hits_device": self.n_reuse_hits_device,
            "hits_host": self.n_reuse_hits_host,
            "misses": self.n_reuse_misses,
        }
        if self.prefix_store is not None:
            offload["store"] = self.prefix_store.stats()
        return {
            "host_bytes": host_bytes,
            "offload": offload,
            "n_pages": usable,
            "page_size": self.page_size,
            "pages_used": used,
            "pages_free": usable - used,
            "utilization": used / max(usable, 1),
            "peak_pages": self.peak_pages,
            "live_requests": len(live),
            "pages_per_request": mapped / max(len(live), 1),
            "shared_pages": int((rc > 1).sum()),
            "preemptions": self.n_preemptions,
            "pool_bytes": int(pool_bytes),
            "used_page_bytes": int(used * page_bytes),
            "dense_equiv_bytes": int(
                page_bytes * self.max_pages * self.capacity
            ),
        }

    def _chunk_fn(self, n_steps: int):
        fn = self._chunk_fns.get(n_steps)
        if fn is None:
            def run(params, tok, cache, active, budget, key):
                def body(carry, _):
                    tok, cache, active, budget, key = carry
                    logits, cache = self.model.decode_step(
                        params, tok, cache, kv_block=self.kv_block,
                        backend=self.backend, active=active,
                    )
                    key, sub = jax.random.split(key)
                    nxt = self.sampler.sample(logits[:, -1], sub)[:, None]
                    valid = active  # rows live when this token was drawn
                    budget = budget - active.astype(budget.dtype)
                    alive = active & (budget > 0)
                    if self.eos_id is not None:
                        alive = alive & (nxt[:, 0] != self.eos_id)
                    return ((nxt, cache, alive, budget, key),
                            (nxt[:, 0], valid))

                carry, (toks, valid) = jax.lax.scan(
                    body, (tok, cache, active, budget, key), None,
                    length=n_steps,
                )
                tok, cache, active, budget, key = carry
                return (tok, cache, active, budget,
                        jnp.moveaxis(toks, 0, 1),  # (capacity, n_steps)
                        jnp.moveaxis(valid, 0, 1))

            fn = jax.jit(self._traced(run, "decode_quantum"),
                         donate_argnums=(2,) if self.donate else ())
            self._chunk_fns[n_steps] = fn
        return fn

    def _spec_chunk_fn(self, n_steps: int):
        """Speculative decode chunk (DESIGN.md §13): ``n_steps`` scan
        iterations, each a draft-verify-accept-rollback pass advancing
        every live row 1..spec_k tokens.  Emits ``(capacity, n_steps *
        spec_k)`` token/valid grids -- the host extraction loop reads
        them exactly like the plain chunk's (valid rows are the accepted
        prefix of each pass's k-block).  Per-row acceptance widths are
        the ragged advance: ``truncate_cache`` rolls every row back to
        its own accepted length inside the dispatch."""
        fn = self._spec_chunk_fns.get(n_steps)
        if fn is None:
            k = self.spec_k

            def run(params, tok, cache, active, budget, hist, hlen, key):
                def body(carry, _):
                    tok, cache, active, budget, hist, hlen, key, nd, na \
                        = carry
                    L0 = cache["pos"]  # (capacity,) entry lengths
                    drafts = draft_tokens(hist, hlen, k)  # (B, k-1)
                    block = jnp.concatenate([tok, drafts], axis=1)
                    logits, cache, snaps = self.model.decode_verify(
                        params, block, cache, kv_block=self.kv_block,
                        backend=self.backend, active=active,
                    )
                    key, _ = jax.random.split(key)  # greedy: drawn, unused
                    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    # exact-match acceptance per row: longest prefix of
                    # drafts equal to the verified greedy tokens, +1 for
                    # the always-emitted bonus token
                    match = (block[:, 1:] == g[:, :-1]).astype(jnp.int32)
                    a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # (B,)
                    m = jnp.minimum(a + 1, budget)  # per-row budget clamp
                    if self.eos_id is not None:
                        # an eos inside the accepted prefix ends the row
                        # there: tokens past it were never sampled in the
                        # sequential run
                        is_eos = g == self.eos_id
                        m = jnp.where(is_eos.any(axis=1),
                                      jnp.minimum(m, jnp.argmax(is_eos,
                                                                axis=1) + 1),
                                      m)
                    m = jnp.where(active, m, 0)
                    valid = jnp.arange(k)[None, :] < m[:, None]  # (B, k)
                    nxt = jnp.take_along_axis(
                        g, jnp.clip(m - 1, 0, k - 1)[:, None], axis=1
                    )
                    nxt = jnp.where(active[:, None], nxt, tok)
                    budget = budget - m.astype(budget.dtype)
                    alive = active & (budget > 0)
                    if self.eos_id is not None:
                        alive = alive & (nxt[:, 0] != self.eos_id)
                    # ragged rollback: every row to its own accepted
                    # length (inactive rows appended nothing; L0 + 0
                    # restores them to their snapshot, a no-op)
                    cache = self.model.truncate_cache(cache, L0 + m, snaps)
                    hist2 = jax.vmap(
                        lambda h, row, s: jax.lax.dynamic_update_slice(
                            h, row, (s,))
                    )(hist, g, hlen)
                    hist = jnp.where(active[:, None], hist2, hist)
                    hlen = hlen + m
                    nd = nd + jnp.sum(jnp.where(active, k - 1, 0))
                    na = na + jnp.sum(jnp.where(active, m - 1, 0))
                    return ((nxt, cache, alive, budget, hist, hlen, key,
                             nd, na), (g, valid))

                carry0 = (tok, cache, active, budget, hist, hlen, key,
                          jnp.int32(0), jnp.int32(0))
                carry, (toks, valid) = jax.lax.scan(
                    body, carry0, None, length=n_steps
                )
                tok, cache, active, budget, hist, hlen, _, nd, na = carry
                toks = jnp.moveaxis(toks, 0, 1).reshape(
                    self.capacity, n_steps * k)
                valid = jnp.moveaxis(valid, 0, 1).reshape(
                    self.capacity, n_steps * k)
                return (tok, cache, active, budget, hist, hlen, toks,
                        valid, nd, na)

            fn = jax.jit(
                self._traced(run, "spec_quantum"),
                donate_argnums=(2, 5, 6) if self.donate else ()
            )
            self._spec_chunk_fns[n_steps] = fn
        return fn

    # -------------------------------------------------------------- schedule
    def _validate(self, req: Request) -> int:
        """Shared request validation (submit + packed admission).
        Returns the prompt length."""
        n = int(np.asarray(req.prompt).shape[-1])
        if n < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1"
            )
        # speculative rows need spec_k - 1 tokens of slack past the last
        # decoded position: a verify pass appends k tokens BEFORE the
        # rollback, and a clamped out-of-bounds append would corrupt
        # resident bytes instead of failing loudly
        slack = self.spec_k - 1 if self.spec_k is not None else 0
        if n + req.max_new_tokens + slack > self.s_max:
            extra = f" + spec_k-1 ({slack})" if slack else ""
            raise ValueError(
                f"request {req.rid}: prompt ({n}) + max_new_tokens "
                f"({req.max_new_tokens}){extra} exceeds s_max={self.s_max}"
            )
        return n

    def submit(self, req: Request) -> None:
        with self.lock:
            self._validate(req)
            self._trace.req_mark(req.rid, "submit")
            # paged admissibility needs no extra check here: the s_max
            # bound above caps any request at max_pages pages, and the
            # constructor floor (n_pages >= max_pages + 1) guarantees
            # the pool can hold that once everything else is preempted
            self._queue.append(req)

    @property
    def pending(self) -> int:
        """Requests not yet decoding: queued plus any in-flight chunked
        admission."""
        return len(self._queue) + (1 if self._pending is not None else 0)

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def n_free_slots(self) -> int:
        """Slots holding no request (neither live nor reserved by an
        in-flight chunked admission)."""
        return sum(1 for r in self._slot_req if r is None)

    @property
    def has_work(self) -> bool:
        return self.pending > 0 or bool(self.active.any())

    def _notify(self, events, completions) -> None:
        """Fan (events, completions) out to ``step_listeners``.  Called
        with the engine lock held, so listeners observe engine state
        consistent with the batch they are handed; they must be quick
        (enqueue-and-return) and must not call back into the engine."""
        if not events and not completions:
            return
        for fn in list(self.step_listeners):
            fn(events, completions)

    def _admit(self, req: Request, slot: int, plan=None
               ) -> Optional[Completion]:
        """Prefill alone, copy into ``slot``, draw the first token.
        ``plan`` is the paged (shared_pages, n_new) admission plan."""
        tr = self._trace
        tr.req_mark(req.rid, "submit")  # direct-admission callers
        tr.req_mark(req.rid, "admit")
        plen = int(np.asarray(req.prompt).shape[-1])
        t0p = time.perf_counter()
        prompt = jnp.asarray(np.asarray(req.prompt)[None, :], jnp.int32)
        row = self._shard_cache_tree(self.model.init_cache(
            1, self.s_max, policy=self.policy, rots=self._rots_copy(),
            key=self._init_key, ragged=True,
        ))
        logits, row = self._prefill_fn(self.params, prompt, row)
        tok0 = self._draw_tok0(req, logits)
        self._insert_row(req, slot, row, tok0, plen, plan)
        tr.span_at("engine.prefill", t0p, cat="prefill", rid=req.rid,
                   tokens=plen)
        tr.req_add(req.rid, "prefill_s", time.perf_counter() - t0p)
        return self._post_insert(req, slot, tok0)

    def _draw_tok0(self, req: Request, logits) -> jax.Array:
        """The admission token.  Preemption resumes re-enter their
        pending token and draw NO sample (the next token must come from
        the same full-width decode dispatch an unpreempted run would
        have used -- bit-parity); fresh admissions split the engine key
        exactly ONCE, so callers must not invoke this until the insert
        is certain (a retried draw would desynchronize the PRNG stream
        from the monolithic engine's)."""
        if req.resume_tok is not None:
            return jnp.full((1, 1), req.resume_tok, jnp.int32)
        self._sample_key, sub = jax.random.split(self._sample_key)
        return self.sampler.sample(logits[:, -1], sub)[:, None]

    def _insert_row(self, req: Request, slot: int, row, tok0,
                    prompt_len: int, plan) -> None:
        """Copy a fully prefilled batch-1 row into ``slot`` -- dense
        scatter or paged COW insert plus its host bookkeeping -- the one
        insert path both admission flavors (monolithic and chunked)
        share."""
        if self.paged:
            shared, n_new = plan
            if req.rid not in self._admit_tier:
                # monolithic/packed admissions attribute their tier
                # here; chunked ones already did in _start_pending
                if len(shared):
                    self._record_tier(req.rid, "device")
                    self._trace.instant("prefix.adopt", cat="prefix",
                                        rid=req.rid, tier="device",
                                        pages=int(len(shared)))
                else:
                    self._record_tier(req.rid, "miss")
            sp = np.full((self.max_pages,), NULL_PAGE, np.int32)
            sp[:len(shared)] = shared
            self.cache, self.tok = self._insert_paged_fn(
                self.cache, row, jnp.asarray(slot), self.tok, tok0,
                jnp.asarray(sp), jnp.asarray(len(shared), jnp.int32),
                jnp.asarray(n_new, jnp.int32),
            )
            self._slot_seq[slot] = self._admit_seq
            self._admit_seq += 1
            self._orig.setdefault(req.rid, (prompt_len,
                                            req.max_new_tokens))
            self._sync_pool()
            self._register_prefix(req, slot)
        else:
            self._record_tier(req.rid, "none")
            self.cache, self.tok = self._insert_fn(
                self.cache, row, jnp.asarray(slot), self.tok, tok0
            )

    def _reset_slot_now(self, slot: int) -> None:
        """Reset one slot's cache row immediately (admission-time
        retire): the admission loop may re-admit this very slot within
        the same quantum, and a deferred reset would wipe the new
        tenant's row (and, paged, free its pages)."""
        self._release_slots([slot])
        mask = np.zeros((self.capacity,), bool)
        mask[slot] = True
        self.cache = self._reset_fn(self.cache, jnp.asarray(mask))
        if self.paged:
            self._sync_pool()

    def _post_insert(self, req: Request, slot: int, tok0
                     ) -> Optional[Completion]:
        """Shared admission bookkeeping (monolithic and chunked paths)
        once the row is in the slot cache and ``tok0`` is drawn."""
        t0 = int(tok0[0, 0])
        self._slot_req[slot] = req
        self._trace.req_mark(req.rid, "first_token")
        if self.spec_k is not None:
            self._seed_hist(slot, req, t0)
        if req.resume_tok is not None:
            # t0 was already counted/streamed before the preemption
            self._slot_toks[slot] = []
            self.budget[slot] = req.max_new_tokens
            self.active[slot] = True
            return None
        self._slot_toks[slot] = [t0]
        self.budget[slot] = req.max_new_tokens - 1
        done = self.budget[slot] <= 0 or (
            self.eos_id is not None and t0 == self.eos_id
        )
        self.active[slot] = not done
        if done:
            return self._retire(slot)
        return None

    def _seed_hist(self, slot: int, req: Request, t0: int) -> None:
        """(Re)seed one slot's drafter history: prompt followed by the
        admission token (a preemption resume's ``prompt`` already
        absorbed everything generated before, so the same layout covers
        both admission flavors).  Admission-rate host work -- the decode
        chunks carry the history on device."""
        prompt = np.asarray(req.prompt, np.int32).ravel()
        row = np.zeros((self._hist_cap,), np.int32)
        row[:prompt.shape[0]] = prompt
        row[prompt.shape[0]] = t0
        self._hist = self._hist.at[slot].set(jnp.asarray(row))
        self._hlen = self._hlen.at[slot].set(prompt.shape[0] + 1)

    # ------------------------------------------------- chunked admission
    def _find_donor(self, prompt: np.ndarray) -> tuple[int, Optional[np.ndarray]]:
        """Longest token-level shared prefix between ``prompt`` and any
        resident registered prompt, aligned DOWN to the policy flush
        window W and capped at ``len(prompt) - 1`` (the final prompt
        token is always computed: its logits draw the admission
        sample).  Returns ``(n_shared_tokens, donor_page_ids)`` --
        ``(0, None)`` when nothing matches.  W alignment is what makes
        the adopted bytes safe: every shared token then lies below the
        donor's prefill flush boundary, so its packed bytes are resident
        and immutable (DESIGN.md §11)."""
        best_t, best_pages = 0, None
        cap = int(prompt.shape[-1]) - 1
        for toks, pages in self._prefix_seqs.values():
            n = min(int(toks.shape[-1]), cap)
            if n <= best_t:
                continue
            neq = np.nonzero(toks[:n] != prompt[:n])[0]
            t = int(neq[0]) if neq.size else n
            t = (t // self._align) * self._align
            if t > best_t and t >= self.page_size \
                    and self._donor_live(toks, pages, t):
                best_t, best_pages = t, pages
        if best_t < self.page_size:
            # below one page nothing can be COW-shared and the compute
            # skip is noise; incidental 1-2 token matches between
            # unrelated prompts would also make quantized-policy
            # admissions needlessly read dequantized prefixes
            return 0, None
        return best_t, best_pages

    def _find_host_prefix(self, prompt: np.ndarray
                          ) -> tuple[int, Optional[list]]:
        """Deepest contiguous page-aligned prefix of ``prompt`` present
        in the host store (DESIGN.md §14).  Returns ``(n_tokens,
        page_payloads)`` in page order, ``(0, None)`` on a miss.  The
        final prompt token is always computed (its logits draw the
        admission sample), so at most ``(len - 1) // page_size`` pages
        are consulted -- the same cap the device-tier plan obeys."""
        if self.prefix_store is None:
            return 0, None
        ps = self.page_size
        payloads: list[tuple] = []
        for i in range((int(prompt.shape[-1]) - 1) // ps):
            pl = self.prefix_store.get(prompt[:(i + 1) * ps].tobytes())
            if pl is None:
                break
            payloads.append(pl)
        if not payloads:
            return 0, None
        return len(payloads) * ps, payloads

    def _start_pending(self, req: Request, slot: int) -> None:
        """Open a chunked admission: build the batch-1 staging row and
        the raw bf16 K/V side buffers, reserve ``slot``, and -- paged +
        reuse -- seed the row from a donor's resident pages so chunking
        skips the shared tokens entirely."""
        tr = self._trace
        tr.req_mark(req.rid, "admit")
        prompt = np.asarray(req.prompt, np.int32)
        n_total = int(prompt.shape[-1])
        row = self._shard_cache_tree(self.model.init_cache(
            1, self.s_max, policy=self.policy, rots=self._rots_copy(),
            key=self._init_key, ragged=True,
        ))
        # Preemption-resume continuations NEVER reuse (resume_tok
        # guard): recompute must rebuild the cache bytes the original
        # admission produced, and a quantized-policy reuse hit would
        # swap raw-prefix attention for dequantized reads -- breaking
        # the §10 bit-for-bit preemption-survival guarantee.
        shared_t = 0
        if self.paged and self.prefix_reuse and req.resume_tok is None:
            shared_t, donor_pages = self._find_donor(prompt)
            host_t, host_payloads = self._find_host_prefix(prompt)
            if host_t > shared_t:
                # host-tier restore (DESIGN.md §14): device_put the
                # exported page tiles and seed the staging row -- a
                # memcpy, not a recompute.  The deeper tier wins; a
                # device COW hit at equal depth is preferred (no copy).
                payload = tuple(
                    jnp.asarray(np.stack([pl[j] for pl in host_payloads],
                                         axis=1))
                    for j in range(len(host_payloads[0]))
                )
                row = self._import_fn(row, payload,
                                      jnp.asarray(host_t, jnp.int32))
                shared_t = host_t
                self.n_restored_pages += len(host_payloads)
                self.n_restored_tokens += host_t
                self.n_reuse_hits_host += 1
                self._record_tier(req.rid, "host")
                tr.instant("prefix.restore", cat="prefix", rid=req.rid,
                           tier="host", pages=len(host_payloads),
                           tokens=host_t)
            elif shared_t:
                pages = np.full((self.max_pages,), NULL_PAGE, np.int32)
                npg = -(-shared_t // self.page_size)
                pages[:npg] = donor_pages[:npg]
                row = self._seed_fn(row, self.cache, jnp.asarray(pages),
                                    jnp.asarray(shared_t, jnp.int32))
                self.n_reuse_hits_device += 1
                self._record_tier(req.rid, "device")
                tr.instant("prefix.adopt", cat="prefix", rid=req.rid,
                           tier="device", pages=int(npg), tokens=shared_t)
            else:
                self.n_reuse_misses += 1
                self._record_tier(req.rid, "miss")
                tr.instant("prefix.miss", cat="prefix", rid=req.rid)
        cfg = self.model.cfg
        if shared_t:
            raw_k, raw_v = self._raw_view_fn(row, shared_t, n_total)
        else:
            raw_k = jnp.zeros(
                (self.model.n_attn_layers, 1, cfg.n_kv_heads, n_total,
                 cfg.head_dim), jnp.bfloat16,
            )
            raw_v = jnp.zeros_like(raw_k)
        self._slot_req[slot] = req  # reserve (inactive until insert)
        self._pending = _PendingAdmission(
            req=req, slot=slot, row=row, raw_k=raw_k, raw_v=raw_v,
            n_done=shared_t, n_total=n_total, reused_tokens=shared_t,
        )
        self.n_reused_tokens += shared_t

    def _finalize_pending(self, round_start: int
                          ) -> tuple[bool, list, list]:
        """Insert a fully prefilled pending admission into its slot.
        Returns ``(inserted, events, completions)``; ``inserted`` is
        False when the paged pool cannot fit the row yet (no eligible
        preemption victim) -- the admission stays pending and is retried
        next step, after end-of-step retirements return pages."""
        pend = self._pending
        req, slot = pend.req, pend.slot
        events: list[tuple[int, list[int]]] = []
        completions: list[Completion] = []
        plan = None
        if self.paged:
            while True:
                plan = self._plan_pages(req)
                if plan is not None:
                    break
                if not self._preempt_one(round_start):
                    return False, events, completions
        # drawn only AFTER the plan loop: the insert is now certain, so
        # a pool-dry retry next step cannot burn a PRNG split
        tok0 = self._draw_tok0(req, pend.logits)
        self._insert_row(req, slot, pend.row, tok0,
                         pend.n_total, plan)
        self._pending = None  # staging row buffers are dropped here
        done = self._post_insert(req, slot, tok0)
        if done is not None:  # finished at admission (eos / n=1)
            events.append((req.rid, [int(done.tokens[-1])]))
            completions.append(done)
            self._reset_slot_now(slot)
        elif req.resume_tok is None:
            events.append((req.rid, [self._slot_toks[slot][0]]))
        return True, events, completions

    def _retire(self, slot: int, reason: Optional[str] = None
                ) -> Completion:
        req = self._slot_req[slot]
        toks = self._slot_toks[slot]
        max_new = req.max_new_tokens
        plen = int(np.asarray(req.prompt).shape[-1])
        if self.paged:
            # stitch tokens carried across preemptions back on, and
            # report against the ORIGINAL prompt/budget
            carried = self._carried.pop(req.rid, [])
            toks = carried + toks
            plen, max_new = self._orig.pop(req.rid, (plen, max_new))
        toks = np.asarray(toks, np.int32)
        if reason is None:
            reason = (
                "eos" if self.eos_id is not None and len(toks)
                and toks[-1] == self.eos_id
                and len(toks) < max_new else "length"
            )
        self._slot_req[slot] = None
        self._slot_toks[slot] = []
        self.active[slot] = False
        self.budget[slot] = 0
        self._count_outcome(req.rid, reason)
        self._trace.req_done(req.rid)
        self._trace.instant("req.retire", cat="request", rid=req.rid,
                            reason=reason, tokens=int(len(toks)))
        return Completion(
            rid=req.rid, prompt_len=plen,
            tokens=toks, finish_reason=reason,
        )

    def _cancelled(self, req: Request, toks: list[int]) -> Completion:
        """Completion for a cancelled request: everything streamed so
        far, reported against the ORIGINAL prompt/budget.  A preempted
        continuation's streamed tokens live entirely in ``_carried``
        (``_preempt_one`` carries the whole slot stream, resume token
        included), so queued continuations pass ``toks=[]``."""
        plen = int(np.asarray(req.prompt).shape[-1])
        max_new = req.max_new_tokens
        if self.paged:
            toks = self._carried.pop(req.rid, []) + toks
            plen, max_new = self._orig.pop(req.rid, (plen, max_new))
        self._count_outcome(req.rid, "cancelled")
        self._trace.req_done(req.rid)
        return Completion(
            rid=req.rid, prompt_len=plen,
            tokens=np.asarray(toks, np.int32), finish_reason="cancelled",
        )

    def cancel_all(self) -> list[Completion]:
        """Drain-on-shutdown (DESIGN.md §12): cancel every live, pending
        and queued request, returning partial ``Completion``s
        (``finish_reason="cancelled"``, tokens = everything streamed so
        far).  Afterwards the engine is empty -- all slots free, every
        row length zero and, paged, every refcount back to zero except
        the pinned null page -- so a drained server leaks nothing.
        Listeners see the cancellations as one final batch."""
        with self.lock:
            completions: list[Completion] = []
            if self._pending is not None:
                pend = self._pending
                self._pending = None  # drop staging buffers
                self._slot_req[pend.slot] = None  # release reservation
                completions.append(self._cancelled(pend.req, []))
            for slot in range(self.capacity):
                if self._slot_req[slot] is not None:
                    completions.append(
                        self._retire(slot, reason="cancelled")
                    )
            while self._queue:
                completions.append(
                    self._cancelled(self._queue.popleft(), [])
                )
            self.active[:] = False
            self.budget[:] = 0
            # drain spills every registered resident prefix to the host
            # tier (if configured) before the pool-wide free, so a
            # post-drain engine sharing the store restores warm
            self._release_slots(list(range(self.capacity)))
            self.cache = self._reset_fn(
                self.cache, jnp.asarray(np.ones((self.capacity,), bool))
            )
            if self.paged:
                self._sync_pool()
            self._notify([], completions)
            return completions

    def _admit_monolithic(self, round_start: int, events: list,
                          completions: list) -> None:
        """Admit from the queue into free slots, one whole-prompt
        prefill per admission.  Paged mode peeks the head, plans its
        pages (COW prefix hits + fresh allocations) and, when the pool
        is dry, preempts the LRU live slot to the queue and replans --
        the preempted continuation lands at the head, so it is also the
        next admission candidate.  Victims are only slots from BEFORE
        this admission round, so the loop always terminates (each
        iteration admits, or consumes one pre-round victim, or
        breaks)."""
        while self._queue:
            free = [s for s in range(self.capacity)
                    if self._slot_req[s] is None]
            if not free:
                break
            slot = free[0]
            plan = None
            if self.paged:
                plan = self._plan_pages(self._queue[0])
                if plan is None:
                    if not self._preempt_one(round_start):
                        break  # pages return at the end-of-step reset
                    continue
            req = self._queue.popleft()
            done = self._admit(req, slot, plan)
            if done is not None:  # finished at admission (eos / n=1)
                events.append((req.rid, [int(done.tokens[-1])]))
                completions.append(done)
                self._reset_slot_now(slot)
            elif req.resume_tok is None:  # resumes already streamed theirs
                events.append((req.rid, [self._slot_toks[slot][0]]))

    # ------------------------------------------------- packed admission
    def admit_packed(self, reqs: list[Request]) -> None:
        """Admit ``reqs`` through ONE batched prefill dispatch
        (DESIGN.md §12).  All prompts must share one exact length L --
        the batch is stacked, not padded: right-padding would change the
        flash-prefill reduction order AND leave junk bytes in the cache,
        so same-length stacking is the only packing that keeps cache
        bytes exactly what a same-width grouped replay produces.

        Determinism contract: on CPU XLA, matmul rounding is only
        row-deterministic at fixed batch width (DESIGN.md §9), so a
        packed admission's rows are bit-identical to any other batch-k
        prefill of the same prompts IN ANY ROW ORDER -- but not to k
        batch-1 prefills.  Stream parity therefore holds between two
        runs that use the same admission *grouping*; the serving
        pipeline's reference replay reuses this method for exactly that
        reason.

        Needs ``len(reqs)`` free slots up front (raises otherwise --
        the caller buckets against ``n_free_slots``) and monolithic
        admission mode (chunked prefill has its own stall-free path).
        Paged mode plans pages per row in admission order, preempting
        pre-round LRU victims exactly like ``_admit_monolithic``; rows
        the pool cannot fit are requeued at the FRONT in order (their
        prefill work is repeated on retry -- rare, and correctness
        needs the requeue to preserve FIFO order)."""
        with self.lock:
            if not reqs:
                return
            if self.prefill_chunk is not None:
                raise ValueError(
                    "admit_packed requires monolithic admission "
                    "(prefill_chunk=None); chunked admission already "
                    "interleaves prefill with decode"
                )
            lens = {self._validate(r) for r in reqs}
            if len(lens) != 1:
                raise ValueError(
                    f"admit_packed needs one exact prompt length, got "
                    f"{sorted(lens)} (stacked, never padded: padding "
                    f"would poison cache bytes)"
                )
            free = [s for s in range(self.capacity)
                    if self._slot_req[s] is None]
            if len(reqs) < 1 or len(reqs) > len(free):
                raise ValueError(
                    f"admit_packed: {len(reqs)} requests but only "
                    f"{len(free)} free slots (callers pack against "
                    f"n_free_slots)"
                )
            self._admit_packed_locked(reqs, free[:len(reqs)])

    def _admit_packed_locked(self, reqs: list[Request],
                             slots: list[int]) -> None:
        k = len(reqs)
        tr = self._trace
        for req in reqs:
            tr.req_mark(req.rid, "submit")  # direct callers (no submit())
            tr.req_mark(req.rid, "admit")
        prompts = jnp.asarray(
            np.stack([np.asarray(r.prompt, np.int32) for r in reqs])
        )
        L = int(prompts.shape[-1])
        t0p = time.perf_counter()
        staged = self._shard_cache_tree(self.model.init_cache(
            k, self.s_max, policy=self.policy, rots=self._rots_copy(),
            key=self._init_key, ragged=True,
        ))
        logits, staged = self._prefill_fn(self.params, prompts, staged)
        tr.span_at("prefill.packed", t0p, cat="prefill", rows=k, tokens=L,
                   rids=[r.rid for r in reqs])
        dt = time.perf_counter() - t0p
        for req in reqs:
            # the group shares one dispatch; each request is attributed
            # the full group duration (it waited on all of it)
            tr.req_add(req.rid, "prefill_s", dt)
        events: list[tuple[int, list[int]]] = []
        completions: list[Completion] = []
        round_start = self._admit_seq if self.paged else 0
        for j, (req, slot) in enumerate(zip(reqs, slots)):
            plan = None
            if self.paged:
                while True:
                    plan = self._plan_pages(req)
                    if plan is not None:
                        break
                    if not self._preempt_one(round_start):
                        # pool dry mid-group: requeue the unplaced tail
                        # in order at the front (their staged rows are
                        # dropped; re-admission recomputes them)
                        self._queue.extendleft(reversed(reqs[j:]))
                        self._notify(events, completions)
                        return
            row = self._slice_row_fn(staged, jnp.asarray(j))
            tok0 = self._draw_tok0(req, logits[j:j + 1])
            self._insert_row(req, slot, row, tok0, L, plan)
            done = self._post_insert(req, slot, tok0)
            if done is not None:  # finished at admission (eos / n=1)
                events.append((req.rid, [int(done.tokens[-1])]))
                completions.append(done)
                self._reset_slot_now(slot)
            elif req.resume_tok is None:
                events.append((req.rid, [self._slot_toks[slot][0]]))
        self._notify(events, completions)

    def _admit_chunked(self, round_start: int, events: list,
                       completions: list) -> None:
        """Chunked admission phase (DESIGN.md §11): spend at most
        ``prefill_budget`` prompt tokens on the in-flight admission
        (starting one from the queue head when none is open), then hand
        control back so the decode chunk runs -- live streams advance
        every quantum regardless of how long the arriving prompt is.
        One admission is in flight at a time (FIFO); a completed one is
        inserted and, budget permitting, the next begins within the same
        quantum.  Token-level prefix reuse means seeded tokens cost no
        budget -- a fully-shared prompt admits almost for free."""
        spent = 0
        while True:
            if self._pending is None:
                if not self._queue:
                    return
                free = [s for s in range(self.capacity)
                        if self._slot_req[s] is None]
                if not free:
                    return
                t0a = time.perf_counter()
                req = self._queue.popleft()
                self._start_pending(req, free[0])
                self._trace.span_at("admit.start", t0a, cat="prefill",
                                    rid=req.rid)
            pend = self._pending
            prompt = np.asarray(pend.req.prompt, np.int32)
            # at least one chunk per quantum even if budget < chunk;
            # otherwise stop at the budget
            while pend.n_done < pend.n_total and (
                    spent == 0 or spent < self.prefill_budget):
                C = min(self.prefill_chunk, pend.n_total - pend.n_done)
                t0c = time.perf_counter()
                toks = jnp.asarray(
                    prompt[None, pend.n_done:pend.n_done + C]
                )
                (pend.logits, pend.row, pend.raw_k,
                 pend.raw_v) = self._chunk_prefill_fn(
                    self.params, toks, pend.row, pend.raw_k, pend.raw_v
                )
                pend.n_done += C
                spent += C
                self.n_prefill_chunks += 1
                # ends at dispatch: the chunk's device work runs under
                # whatever the host does next (the decode.wait after it)
                self._trace.span_at("prefill.chunk", t0c, cat="prefill",
                                    rid=pend.req.rid, tokens=C,
                                    done=pend.n_done, total=pend.n_total)
                self._trace.req_add(pend.req.rid, "prefill_s",
                                    time.perf_counter() - t0c)
            if pend.n_done < pend.n_total:
                return  # budget exhausted; decode now
            t0i = time.perf_counter()
            ok, ev, comps = self._finalize_pending(round_start)
            self._trace.span_at("admit.insert", t0i, cat="prefill",
                                rid=pend.req.rid, inserted=ok)
            events.extend(ev)
            completions.extend(comps)
            if not ok:
                return  # pool dry: retried after end-of-step retirements
            if spent >= self.prefill_budget:
                return

    def _kv_tiles(self) -> dict:
        """Live and total grid tiles of one paged-kernel call at the
        quantum's first step, from the host's own lengths (no device
        read): ``kv_tiles_live / kv_tiles_grid`` is the share of the
        kernel's grid steps that do work.  Empty off the kernel path."""
        if not (self.paged and self.spec_k is None
                and self.backend is AttendBackend.KERNEL):
            return {}
        pages = paged_tile_pages(self.page_size, self.max_pages)
        tile = pages * self.page_size
        live = 0
        for slot in np.nonzero(self.active)[0]:
            req = self._slot_req[slot]
            # the step appends the slot's last token, then attends
            n = (len(req.prompt) + (req.resume_tok is not None)
                 + len(self._slot_toks[slot]))
            live += -(-(n - n % self._align) // tile)
        return {"kv_tiles_live": live,
                "kv_tiles_grid": self.capacity * -(-self.max_pages // pages)}

    def step(self) -> tuple[list[tuple[int, list[int]]], list[Completion]]:
        """One scheduler quantum: admit into free slots (monolithic
        prefill, or up to ``prefill_budget`` tokens of chunked prefill),
        decode one chunk.  Returns (events, completions) -- ``events``
        is the token stream, one ``(rid, new_tokens)`` per live
        request.  ``step_listeners`` receive the same pair before it is
        returned (still under the engine lock)."""
        with self.lock:
            t0 = time.perf_counter()
            events, completions = self._step_locked()
            self._notify(events, completions)
            self._trace.span_at("engine.step", t0, cat="engine",
                                streams=len(events),
                                retired=len(completions))
            return events, completions

    def _step_locked(self
                     ) -> tuple[list[tuple[int, list[int]]],
                                list[Completion]]:
        events: list[tuple[int, list[int]]] = []
        completions: list[Completion] = []
        newly_retired = np.zeros((self.capacity,), bool)
        round_start = self._admit_seq if self.paged else 0

        if self.prefill_chunk is not None:
            self._admit_chunked(round_start, events, completions)
        else:
            self._admit_monolithic(round_start, events, completions)

        if not self.active.any():  # admission retires were reset in-loop
            return events, completions

        # one fused dispatch: the whole batch advances up to `chunk`
        # tokens (clipped to the longest remaining budget -- no masked
        # tail steps when every live request is nearly done)
        n_steps = int(min(self.chunk, self.budget[self.active].max()))
        # decode.chunk runs from here to the tokens' readback, split into
        # decode.dispatch (host enqueue, jit cache lookup included) and
        # decode.wait (the host blocked on the device); decode.post is
        # the host work after it
        t0d = time.perf_counter()
        n_live = int(self.active.sum())
        kv_tiles = self._kv_tiles()
        self._sample_key, sub = jax.random.split(self._sample_key)
        if self.spec_k is not None:
            # each scan step is one verify pass emitting 1..spec_k
            # tokens per live row; the flattened (capacity, n_steps *
            # spec_k) grids feed the same extraction loop below
            fn = self._spec_chunk_fn(n_steps)
            (self.tok, self.cache, active_dev, budget_dev, self._hist,
             self._hlen, toks, valid, nd, na) = fn(
                self.params, self.tok, self.cache,
                jnp.asarray(self.active), jnp.asarray(self.budget),
                self._hist, self._hlen, sub)
            t_disp = time.perf_counter()
            nd, na = int(nd), int(na)
            self.n_drafted += nd
            self.n_accepted += na
        else:
            fn = self._chunk_fn(n_steps)
            (self.tok, self.cache, active_dev, budget_dev, toks,
             valid) = fn(self.params, self.tok, self.cache,
                         jnp.asarray(self.active), jnp.asarray(self.budget),
                         sub)
            t_disp = time.perf_counter()
        toks = np.asarray(toks)
        valid = np.asarray(valid)
        self.budget = np.asarray(budget_dev).copy()
        still_active = np.asarray(active_dev)
        t_read = time.perf_counter()
        tr = self._trace
        tr.span_at("decode.dispatch", t0d, cat="decode", t1=t_disp)
        tr.span_at("decode.wait", t_disp, cat="decode", t1=t_read)
        tr.span_at("decode.chunk", t0d, cat="decode", t1=t_read,
                   steps=n_steps, rows=n_live, capacity=self.capacity,
                   spec=self.spec_k is not None, **kv_tiles)
        if self.spec_k is not None:
            tr.instant("spec.verify", cat="spec", drafted=nd, accepted=na,
                       rejected=nd - na)

        for slot in range(self.capacity):
            req = self._slot_req[slot]
            if req is None or not self.active[slot]:
                continue
            new = [int(t) for t, ok in zip(toks[slot], valid[slot]) if ok]
            self._slot_toks[slot].extend(new)
            events.append((req.rid, new))
            if not still_active[slot]:
                completions.append(self._retire(slot))
                newly_retired[slot] = True
        self.active = still_active.copy()
        if newly_retired.any():  # free the rows: lengths back to zero
            # (paged: one page-table reference dropped per mapped page;
            # COW prefix pages survive while other rows hold them)
            self._release_slots(np.nonzero(newly_retired)[0])
            self.cache = self._reset_fn(self.cache,
                                        jnp.asarray(newly_retired))
            if self.paged:
                self._sync_pool()
        tr.span_at("decode.post", t_read, cat="decode")
        return events, completions

    def run(self, requests: Optional[list[Request]] = None
            ) -> Iterator[Completion]:
        """Drain the queue (plus ``requests``), yielding completions as
        they finish -- the streaming-response loop serve.py sits on."""
        for r in requests or ():
            self.submit(r)
        while self._queue or self._pending is not None or self.active.any():
            _, completions = self.step()
            yield from completions
