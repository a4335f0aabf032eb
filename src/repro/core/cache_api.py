"""Unified ``KVCachePolicy`` API: registry-driven cache backends.

The paper ships its int4 SRFT cache as a single polymorphic HuggingFace
``Cache`` subclass.  This module is the functional-JAX analogue of that
surface: one protocol, one registry, one state wrapper -- so the model
code (``models/attention.py`` / ``models/lm.py``) never branches on the
concrete cache type and serving configs select a scheme by name.

Pieces (DESIGN.md §6):

``KVCachePolicy``
    Protocol every cache scheme implements.  A policy is a *frozen
    dataclass of static hyperparameters* (group size, window, rotation
    kind ...); all array state lives in the :class:`CacheState` pytree it
    creates.  Lifecycle::

        pol   = get_policy("int4-srft", group=32, window=16)
        state = pol.init_state(B, Hkv, S_max, d, key=key)   # owns pytree
        state = pol.prefill(state, k, v)                    # bulk insert
        state = pol.update(state, k, v)                     # decode append
        out   = pol.attend(q, state, backend=AttendBackend.GATHER)
        bytes_, ratio = pol.nbytes(state), pol.compression_ratio(state)

    Ragged continuous batching (DESIGN.md §9) adds a second lifecycle on
    the SAME state type: ``init_state(..., ragged=True)`` makes
    ``length`` a per-row (B,) vector; ``update(state, k, v, active=m)``
    appends row i at its own L_i and only advances lengths where the
    mask is True; ``attend`` masks per row; ``insert_row`` /
    ``reset_rows`` admit and retire requests in a fixed-capacity slot
    cache.  Raggedness is a shape property (``length.ndim``), so the
    two lifecycles share one pytree structure and one dispatch.

``CacheState``
    Pytree wrapper pairing a policy (static aux data, hashable) with its
    array state.  Because the policy rides in the treedef, a cache pytree
    is self-describing: ``state.policy.attend(q, state)`` dispatches with
    no ``isinstance`` and no stringly-typed flags, and the wrapper threads
    through ``jit`` / ``vmap`` (layer stacking) / ``scan`` unchanged.

``AttendBackend``
    Typed enum selecting the decode read path -- ``GATHER`` (one-shot
    dequant, GSPMD-friendly), ``BLOCKWISE`` (flash-decode jnp mirror),
    ``KERNEL`` (Pallas) -- replacing the old magic-string ``impl=``.

``register_policy`` / ``get_policy``
    String-keyed registry so configs and CLIs name schemes ("bf16",
    "int4-srft", "int8-per-token", future fp8/...) without importing
    their classes.

Built-in policies:

    bf16            uncompressed DynamicCache analogue (baseline)
    int4-srft       the paper's deployment recipe: SRFT rotation +
                    per-channel lambda + int4 per-group + fp32 residual
                    window.  Rotation state (``rot_k``/``rot_v``) lives
                    INSIDE the cache state, so callers no longer thread
                    rotations by hand.
    int8-per-token  one fp32 scale per K/V vector at 8 bits (near-
                    lossless, ~1.9x); proves the protocol carries a third
                    scheme with zero model-code changes.
"""
from __future__ import annotations

import dataclasses
import enum
import math
import warnings
from typing import Any, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kvcache, paged, quant
from repro.core.kvcache import BF16KVCache, QuantKVCache
from repro.core.paged import PagedData
from repro.core.quant_attention_ref import (
    decode_attention_bf16,
    decode_attention_bf16_blockwise,
    decode_attention_quant,
    decode_attention_quant_blockwise,
    verify_attention_bf16,
    verify_attention_quant,
)
from repro.core.transforms import Rotation, make_rotation

__all__ = [
    "AttendBackend",
    "CacheState",
    "KVCachePolicy",
    "BF16Policy",
    "Int4SRFTPolicy",
    "Int8PerTokenPolicy",
    "register_policy",
    "get_policy",
    "available_policies",
    "policy_from_config",
]


class AttendBackend(enum.Enum):
    """Decode read path.  Policies may support a subset (``attend`` raises
    for unsupported combinations rather than silently degrading)."""

    GATHER = "gather"      # one-shot dequant of the local shard (GSPMD)
    BLOCKWISE = "blockwise"  # flash-decode tiles, jnp mirror of the kernel
    KERNEL = "kernel"      # Pallas kernel (single device / shard_map inner)

    @classmethod
    def parse(cls, value: "AttendBackend | str | None") -> "AttendBackend":
        if value is None:
            return cls.GATHER
        if isinstance(value, AttendBackend):
            return value
        try:
            return cls(str(value).lower())
        except ValueError:
            names = ", ".join(b.value for b in cls)
            raise ValueError(
                f"unknown attend backend {value!r} (have: {names})"
            ) from None


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class CacheState:
    """A cache pytree that knows its own policy.

    ``policy`` is static treedef aux data (frozen dataclass => hashable),
    ``data`` is the policy-specific array pytree.  Layer stacking is just
    ``vmap`` over ``init_state``; scan-over-layers slices ``data`` leaves
    and preserves the policy.
    """

    policy: "KVCachePolicy"
    data: Any

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("data"), self.data),), (self.policy,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(policy=aux[0], data=children[0])

    # -- conveniences (delegate; every policy's data exposes .length) -------
    @property
    def length(self) -> jax.Array:
        return self.data.length

    @property
    def lengths(self) -> jax.Array:
        """Alias for ragged callers: per-row (B,) lengths (or scalar)."""
        return self.data.length

    @property
    def is_ragged(self) -> bool:
        """True when ``length`` carries one entry per batch row (shape
        (B,)); static under tracing, so code may branch on it."""
        return self.data.length.ndim == 1

    @property
    def is_paged(self) -> bool:
        """True when K/V live in a page pool behind a per-row page
        table (core/paged.py; DESIGN.md §10).  A type property --
        static under tracing.  Paged states are always ragged."""
        return isinstance(self.data, PagedData) \
            or isinstance(getattr(self.data, "kv", None), PagedData)

    def nbytes(self, *, persistent_only: bool = True,
               per_shard: bool = False) -> int:
        return self.policy.nbytes(self, persistent_only=persistent_only,
                                  per_shard=per_shard)


@runtime_checkable
class KVCachePolicy(Protocol):
    """Protocol for KV-cache schemes (see module docstring for lifecycle).

    ``supported_backends`` lets serve/benchmark sweeps enumerate the read
    paths a scheme implements instead of catching NotImplementedError.

    Ragged slot semantics (DESIGN.md §9): with ``init_state(...,
    ragged=True)`` the state's ``length`` is a per-row (B,) vector and
    every row is an independent request slot.  ``update`` takes an
    optional ``active`` mask (rows where it is False keep their length;
    any bytes they write land at positions ≥ their length and are
    masked by ``attend``).  ``insert_row`` copies a freshly prefilled
    batch-1 ragged state into slot ``slot`` of a capacity-B state
    (leaving shared non-per-row leaves -- e.g. rotations -- untouched:
    both states MUST have been built with the same rotations).
    ``reset_rows`` zeroes the lengths of retired slots for reuse.

    Donation invariant (DESIGN.md §8): ``prefill`` and ``update`` must
    return a state with the SAME pytree structure, shapes and dtypes,
    and must not read any input buffer except as an operand of the op
    producing its replacement -- so a jitted step with
    ``donate_argnums`` on the cache lowers every append to an in-place
    ``dynamic_update_slice`` (no per-token O(S_max) copy).  The fused
    generation engine (launch/engine.py) relies on this.
    """

    name: str
    supported_backends: tuple[AttendBackend, ...]

    def init_state(self, batch: int, n_kv_heads: int, s_max: int,
                   head_dim: int, *, key: Optional[jax.Array] = None,
                   ragged: bool = False) -> CacheState:
        """Build a zeroed dense cache for ``batch`` rows of capacity
        ``s_max`` tokens.  ``key`` seeds any rotation state (policies
        without rotations ignore it).  ``ragged=True`` makes ``length``
        a per-row ``(B,)`` vector (continuous-batching slot cache,
        DESIGN.md §9); otherwise it is a scalar shared by every row."""
        ...

    def init_paged(self, batch: int, n_kv_heads: int, s_max: int,
                   head_dim: int, *, n_pages: int, page_size: int,
                   key: Optional[jax.Array] = None) -> CacheState:
        """Build a zeroed PAGED cache (DESIGN.md §10): seq-major leaves
        become ``(n_pages, H, page_size, c)`` pools behind a per-row
        ``(B, max_pages)`` page table.  Paged states are always ragged.
        Policies with alignment constraints (int4: ``page_size %
        window == 0``) must validate them here and raise ``ValueError``
        up front rather than corrupting pages later."""
        ...

    def prefill(self, state: CacheState, k: jax.Array, v: jax.Array
                ) -> CacheState:
        """Bulk-insert a whole prompt.  ``k``/``v`` are ``(B, Hkv, S,
        d)`` post-RoPE projections; every row's length becomes S (ragged
        states set all rows).  Must be donation-safe: same pytree
        structure/shapes/dtypes out, old buffers read only as operands
        of the ops producing their replacements (DESIGN.md §8).  Paged
        states raise -- they are filled per row via
        :meth:`insert_row_paged` or :meth:`prefill_chunk`."""
        ...

    def update(self, state: CacheState, k: jax.Array, v: jax.Array,
               *, active: Optional[jax.Array] = None) -> CacheState:
        """Append ONE decode token per row.  ``k``/``v`` are ``(B, Hkv,
        1, d)``; row ``i`` writes at its own length ``L_i`` (scalar
        states: the shared length).  ``active`` is a ``(B,)`` bool mask
        for ragged/paged states only (passing it to a scalar state
        raises): rows where it is False still write -- at a position ≥
        their unchanged length, masked by every read path -- but their
        length does not advance (DESIGN.md §9 invariant 2; the int4
        re-flush there is idempotent).  O(1)/O(W) HBM traffic per step,
        never O(S_max); donation-safe like :meth:`prefill`."""
        ...

    def prefill_chunk(self, state: CacheState, k: jax.Array, v: jax.Array
                      ) -> CacheState:
        """Append a C-token PROMPT CHUNK at each row's own length
        (chunked prefill, DESIGN.md §11).  ``k``/``v`` are ``(B, Hkv, C,
        d)`` post-RoPE projections; every row's length advances by C.
        Works on ragged (per-row scatter of the chunk) and paged states
        (page-table-routed writes; the int4 W-slabs stay inside one page
        because ``page_size % W == 0``); scalar states raise.

        Alignment contract (the batch engine enforces it): every row's
        current length is a multiple of the policy's flush window W
        (policies without a window: W = 1), and only the final chunk of
        an admission may have ``C % W != 0`` (its tail lands in the
        residual ring).  Under that contract a sequence of chunks
        produces byte-identical state to one monolithic
        :meth:`prefill` of the concatenated prompt.  Donation-safe like
        :meth:`prefill`."""
        ...

    def attend(self, q: jax.Array, state: CacheState, *,
               scale: Optional[float] = None,
               backend: "AttendBackend | str | None" = None,
               kv_block: int = 512,
               sliding_window: Optional[int] = None) -> jax.Array:
        """One-token attention read: ``q`` is ``(B, Hq, 1, d)``, the
        result ``(B, Hq, 1, d)``.  ``backend`` picks the read path
        (unsupported combinations raise rather than silently degrade);
        ragged/paged states mask per row against their own lengths and
        must return finite output even for fully-masked rows (§10
        degenerate-lane hygiene)."""
        ...

    def snapshot_rows(self, state: CacheState) -> Any:
        """Capture the minimal pytree needed to rewind a speculative
        verify pass (DESIGN.md §13).  Taken BEFORE the pass's k
        :meth:`update` calls; passed back to :meth:`verify_attend`
        (which reconstructs per-query historical cache views from it)
        and :meth:`truncate_rows` (which restores rejected state).
        Schemes whose appends are position-addressed (bf16, int8) need
        only the entry lengths; the int4 mod-W residual ring is an
        overwrite structure, so its snapshot also carries the O(W) ring
        buffers.  O(B·W) at most -- never O(S_max)."""
        ...

    def verify_attend(self, q: jax.Array, state: CacheState, snap: Any, *,
                      scale: Optional[float] = None,
                      backend: "AttendBackend | str | None" = None,
                      kv_block: int = 512,
                      sliding_window: Optional[int] = None) -> jax.Array:
        """Score k verify queries in ONE dispatch: ``q`` is ``(B, Hq, k,
        d)`` (k <= the policy's flush window), ``state`` is the cache
        AFTER all k tokens were appended, ``snap`` the matching
        :meth:`snapshot_rows` capture.  Query i attends exactly the
        length-(L0+i+1) prefix a sequential decode would have seen --
        per-token bit-identical to k :meth:`attend` calls interleaved
        with the appends (DESIGN.md §13).  Runs on the GATHER reference
        path for every backend (the int4 KERNEL backend warns once and
        falls back; multi-query verify tiles are future kernel work)."""
        ...

    def truncate_rows(self, state: CacheState, new_length: jax.Array,
                      snap: Any) -> CacheState:
        """Roll rows back to ``new_length`` (per-row ``(B,)`` for
        ragged/paged states, scalar otherwise; ``base_len <= new_length
        <= length``) after a verify pass rejected a draft tail:  length
        decrement plus -- for the int4 scheme -- the residual-ring
        rewind from ``snap`` (``kvcache.rewind_residual``).  Packed/
        paged storage is NOT rewound: a rolled-back flush slab sits
        whole at a W-aligned offset past the rewound packed length,
        masked by every read until the next flush rewrites it whole
        (the W-alignment invariant, DESIGN.md §13); paged rewinds keep
        their page mappings (position-deterministic; reclaimed at
        retirement or by ``paged.truncate_pages``).  Donation-safe."""
        ...

    def with_rotations(self, state: CacheState, rot_k: Rotation,
                       rot_v: Rotation) -> CacheState:
        """Embed (calibrated) rotations into the state; a no-op for
        rotation-free schemes.  The returned state must be usable
        interchangeably with states built from the same rotations --
        ``insert_row`` requires it."""
        ...

    def insert_row(self, state: CacheState, row: CacheState, slot
                   ) -> CacheState:
        """Admit a freshly prefilled batch-1 ragged ``row`` into slot
        ``slot`` of a capacity-B dense ragged ``state`` (one
        ``dynamic_update_slice`` per per-row leaf; ``slot`` may be
        traced, so admission never recompiles).  Shared non-per-row
        leaves (rotations) stay the batched state's -- both states MUST
        have been built from the same rotations.  Donation-safe on
        ``state``; ``row`` is read-only."""
        ...

    def insert_row_paged(self, state: CacheState, row: CacheState, slot,
                         shared_pages: jax.Array, n_shared: jax.Array,
                         n_new: jax.Array) -> CacheState:
        """Paged admission (DESIGN.md §10): COW-share the first
        ``n_shared`` pages named by ``shared_pages`` (a ``(max_pages,)``
        id vector, refcounts bumped, bytes untouched), allocate
        ``n_new`` fresh pages inside the jit, and scatter the dense
        ``row``'s tiles into the fresh pages only.  All page arguments
        may be traced.  The engine supplies the plan from its host
        refcount mirror and guarantees ``n_new`` free pages exist."""
        ...

    def adopt_prefix(self, row: CacheState, paged: CacheState,
                     pages: jax.Array, n_tokens: jax.Array) -> CacheState:
        """Seed a dense batch-1 ragged ``row`` from resident pages of
        ``paged`` (token-level prefix reuse, DESIGN.md §11): gather the
        ``(max_pages,)`` page ids into the row's seq-major leaves
        (positions past the shared prefix read garbage that chunked
        prefill overwrites before any read) and set the row length to
        ``n_tokens``.  For windowed policies ``n_tokens`` must be
        W-aligned, so every adopted byte comes from packed storage and
        the residual ring stays in its initial (zero) state -- exactly
        the state a monolithic prefill of those ``n_tokens`` would leave
        behind at a flush boundary."""
        ...

    def export_pages(self, state: CacheState, pages) -> tuple:
        """Snapshot the named physical pages of a paged state to HOST
        memory (the spill side of the offload tier, DESIGN.md §14).
        ``pages`` is a host sequence of page ids; the result is one
        numpy array per pool leaf, shaped ``(..., NP, H, page_size, c)``
        with any leading layer axes preserved -- the exact resident
        bytes (packed int4 codes + scales, int8 codes, or bf16 K/V),
        no dequantization, no recompute.  A later
        :meth:`import_pages` of these arrays must reproduce the bytes
        bit-identically."""
        ...

    def import_pages(self, row: CacheState, payload: tuple, n_tokens
                     ) -> CacheState:
        """Seed a dense batch-1 ragged ``row`` from page bytes exported
        by :meth:`export_pages` (the restore side of the offload tier,
        DESIGN.md §14): the host-tier analogue of :meth:`adopt_prefix`,
        with the pages' bytes supplied as ``(NP, H, page_size, c)``
        device arrays instead of gathered from a resident pool.  Writes
        positions ``[0, NP*page_size)`` of the row's seq-major leaves
        and sets its length to ``n_tokens``; a subsequent
        ``insert_row_paged`` then scatters those tiles into freshly
        allocated pages byte-identically to the donor's.  Same
        alignment contract as ``adopt_prefix`` (windowed policies:
        ``n_tokens`` W-aligned, residual ring stays zero)."""
        ...

    def raw_kv_view(self, state: CacheState) -> tuple[jax.Array, jax.Array]:
        """Best-available RAW-space (pre-rotation, post-RoPE) dense
        ``(B, Hkv, S_max, d)`` K/V views of a dense ragged state, valid
        on ``[0, packed-aligned length)``.  bf16 returns its buffers
        bit-exactly; quantized schemes dequantize (and inverse-rotate),
        so the view carries quantization error -- the chunked-prefill
        raw side buffer backfill documents this as cache-consistent
        reads (DESIGN.md §11)."""
        ...

    def reset_rows(self, state: CacheState, mask: jax.Array
                   ) -> CacheState:
        """Retire masked rows: lengths back to 0 so slots can be reused
        (paged states additionally decref every mapped page and null
        the page-table rows).  Retired rows keep riding in the decode
        dispatch -- their writes land past their zero length (or in the
        null scratch page) and every read path masks them."""
        ...

    def nbytes(self, state: CacheState, *, persistent_only: bool = True,
               per_shard: bool = False) -> int:
        """Cache bytes.  ``persistent_only=True`` counts the O(S)
        persistent storage (paged states: the whole pool -- that is the
        allocation); False adds transient state (int4 residual window)
        and, for paged states, page-table + allocator metadata.

        GLOBAL-LOGICAL by default: on a mesh-sharded state the figure is
        the whole cache, identical on every process, the same number a
        single-device run reports.  ``per_shard=True`` instead counts
        one device's resident bytes -- KV leaves shrink by the 'model'
        factor while replicated metadata (page table, refcounts,
        rotations) counts in full (DESIGN.md §16)."""
        ...

    def compression_ratio(self, state: CacheState, *,
                          per_shard: bool = False) -> float:
        """bf16-equivalent bytes / persistent bytes (paper §4.5).

        Global-logical by default (sharding-invariant).  With
        ``per_shard=True`` both sides of the ratio are one device's
        bytes -- for paged states this is slightly LOWER than the
        global ratio because replicated paging metadata does not shrink
        with the pool."""
        ...


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type] = {}


def register_policy(name: str):
    """Class decorator: ``@register_policy("int4-srft")``."""

    def deco(cls):
        if name in _REGISTRY:
            raise ValueError(f"policy {name!r} already registered")
        _REGISTRY[name] = cls
        cls.name = name
        return cls

    return deco


def get_policy(name: str, **hyperparams) -> "KVCachePolicy":
    """Instantiate a registered policy by name.

    Extra hyperparameters not accepted by the scheme (e.g. ``window`` for
    bf16) are dropped, so callers can pass a superset from a shared
    config.
    """
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown cache policy {name!r} "
            f"(registered: {', '.join(sorted(_REGISTRY))})"
        ) from None
    fields = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in hyperparams.items() if k in fields})


def available_policies() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def policy_from_config(cfg, policy: "KVCachePolicy | str | None" = None
                       ) -> "KVCachePolicy":
    """Resolve a policy for a ModelConfig-like object.

    ``policy`` may be an instance (returned as-is), a registry name, or
    None -- in which case the config's quantization settings pick
    "int4-srft" (kv_quant) or "bf16".
    """
    if policy is None:
        policy = "int4-srft" if getattr(cfg, "kv_quant", False) else "bf16"
    if isinstance(policy, str):
        return get_policy(
            policy,
            group=getattr(cfg, "kv_group", 32),
            window=getattr(cfg, "kv_window", 16),
            rotation=getattr(cfg, "rotation", "srft"),
        )
    return policy


def _leaf_elems(x, *, per_shard: bool = False) -> int:
    """Element count of one cache leaf.

    Global-logical by default: ``x.size`` on a mesh-sharded jax array is
    the full logical array, so every ``nbytes`` figure means "the
    cache", independent of how many devices hold it.  With
    ``per_shard=True`` the count is one device's addressable shard
    (``sharding.shard_shape``); replicated leaves -- page tables,
    refcounts, rotations -- count in FULL on every device, which is
    exactly their footprint there."""
    if per_shard:
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            return int(math.prod(sharding.shard_shape(x.shape)))
    return int(x.size)


def _leaf_bytes(*leaves, per_shard: bool = False) -> int:
    return sum(
        _leaf_elems(x, per_shard=per_shard) * jnp.dtype(x.dtype).itemsize
        for x in leaves
    )


def _export_pool_pages(pd, pages) -> tuple:
    """Host snapshot of the named pages from every pool leaf (spill side
    of the offload tier, DESIGN.md §14): gather along the page axis
    (axis -4 -- leaves are ``(..., n_pages, H, ps, c)`` with any layer
    axes leading) and pull to numpy.  A host-side call, never jitted:
    it runs at retire/preempt time, where the engine already blocks on
    the device."""
    idx = jnp.asarray(np.asarray(list(pages), np.int32))
    return tuple(np.asarray(jnp.take(p, idx, axis=-4)) for p in pd.pools)


def _seed_dense_leaf(buf: jax.Array, tiles: jax.Array) -> jax.Array:
    """Write ``(NP, H, ps, c)`` page tiles at positions [0, NP*ps) of a
    dense batch-1 seq-major leaf (restore side of the offload tier)."""
    dense = paged.pages_to_dense(tiles).astype(buf.dtype)
    return jax.lax.dynamic_update_slice(buf, dense, (0, 0, 0, 0))


def _insert_row_leaf(batched: jax.Array, row: jax.Array, slot) -> jax.Array:
    """Write a batch-1 leaf into row ``slot`` of a capacity-B leaf.

    Both leaves must lead with the batch axis (lengths included: ragged
    states carry (B,) lengths).  ``slot`` may be traced -- admission
    does not recompile per slot."""
    idx = (slot,) + (0,) * (batched.ndim - 1)
    return jax.lax.dynamic_update_slice(batched, row.astype(batched.dtype),
                                        idx)


# ---------------------------------------------------------------------------
# bf16 baseline
# ---------------------------------------------------------------------------

@register_policy("bf16")
@dataclasses.dataclass(frozen=True)
class BF16Policy:
    """Uncompressed bf16 cache (the paper's fp16 DynamicCache analogue).

    GATHER reads the dense cache in one shot; BLOCKWISE runs the same
    flash-decode tiling as the int4 mirror (minus dequant) so backend
    sweeps compare policies under identical tiling.  KERNEL is int4-only
    (there are no packed codes to stream) and raises.

    Donation-safe (DESIGN.md §8): ``prefill``/``update`` produce the new
    k/v buffers via ``dynamic_update_slice`` over the old ones -- same
    shape/dtype, no read after the write -- so under ``donate_argnums``
    XLA updates the cache in place.
    """

    supported_backends = (AttendBackend.GATHER, AttendBackend.BLOCKWISE)

    def init_state(self, batch, n_kv_heads, s_max, head_dim, *, key=None,
                   ragged=False):
        return CacheState(
            self, kvcache.init_bf16_cache(batch, n_kv_heads, s_max, head_dim,
                                          ragged=ragged)
        )

    def init_paged(self, batch, n_kv_heads, s_max, head_dim, *, n_pages,
                   page_size, key=None):
        return CacheState(self, paged.init_paged(
            batch, s_max, page_size=page_size, n_pages=n_pages,
            leaf_specs=((n_kv_heads, head_dim, jnp.bfloat16),) * 2,
        ))

    def prefill(self, state, k, v):
        if state.is_paged:
            raise NotImplementedError(
                "paged states are filled per row: prefill a dense batch-1 "
                "ragged state and admit it with insert_row_paged"
            )
        return CacheState(self, kvcache.bf16_prefill(state.data, k, v))

    def update(self, state, k, v, *, active=None):
        if state.is_paged:
            return CacheState(self, paged.append_token(
                state.data,
                (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)), active,
            ))
        if state.is_ragged:
            return CacheState(self, kvcache.bf16_decode_update_ragged(
                state.data, k, v, active
            ))
        if active is not None:
            raise ValueError("active masks need a ragged cache "
                             "(init_state(..., ragged=True))")
        return CacheState(self, kvcache.bf16_decode_update(state.data, k, v))

    def prefill_chunk(self, state, k, v):
        if state.is_paged:
            return CacheState(self, paged.append_chunk(
                state.data,
                (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)),
            ))
        if not state.is_ragged:
            raise ValueError("chunked prefill is a ragged/paged lifecycle "
                             "(init_state(..., ragged=True))")
        return CacheState(self, kvcache.bf16_prefill_chunk_ragged(
            state.data, k, v
        ))

    def adopt_prefix(self, row, paged_state, pages, n_tokens):
        kview, vview = paged.read_pages(paged_state.data, pages)
        d = row.data
        return CacheState(self, BF16KVCache(
            k=kview.astype(d.k.dtype), v=vview.astype(d.v.dtype),
            length=jnp.full_like(d.length, n_tokens),
        ))

    def export_pages(self, state, pages):
        return _export_pool_pages(state.data, pages)

    def import_pages(self, row, payload, n_tokens):
        d = row.data
        return CacheState(self, BF16KVCache(
            k=_seed_dense_leaf(d.k, payload[0]),
            v=_seed_dense_leaf(d.v, payload[1]),
            length=jnp.full_like(d.length, n_tokens),
        ))

    def raw_kv_view(self, state):
        return state.data.k, state.data.v

    def insert_row(self, state, row, slot):
        if state.is_paged:
            raise NotImplementedError(
                "paged admission goes through insert_row_paged (the engine "
                "supplies the COW page plan)"
            )
        return CacheState(self, jax.tree.map(
            lambda b, r: _insert_row_leaf(b, r, slot), state.data, row.data
        ))

    def insert_row_paged(self, state, row, slot, shared_pages, n_shared,
                         n_new):
        rd = row.data  # dense batch-1 ragged BF16KVCache
        return CacheState(self, paged.insert_row(
            state.data, (rd.k, rd.v), (), rd.length, slot,
            shared_pages, n_shared, n_new,
        ))

    def reset_rows(self, state, mask):
        if state.is_paged:
            return CacheState(self, paged.reset_rows(state.data, mask))
        return CacheState(self, state.data._replace(
            length=jnp.where(mask, 0, state.data.length)
        ))

    def attend(self, q, state, *, scale=None, backend=None, kv_block=512,
               sliding_window=None):
        backend = AttendBackend.parse(backend)
        data = state.data
        if state.is_paged:
            kview, vview = paged.gather_view(data)
            data = BF16KVCache(k=kview, v=vview, length=data.length)
        if backend is AttendBackend.BLOCKWISE:
            return decode_attention_bf16_blockwise(
                q, data, scale=scale, sliding_window=sliding_window,
                kv_block=kv_block,
            )
        if backend is not AttendBackend.GATHER:
            raise NotImplementedError(
                f"bf16 implements GATHER and BLOCKWISE read paths "
                f"(got {backend.value}); the Pallas kernel is int4-only"
            )
        return decode_attention_bf16(
            q, data, scale=scale, sliding_window=sliding_window
        )

    def snapshot_rows(self, state):
        # position-addressed appends: entry lengths are the whole rewind
        return state.data.length

    def verify_attend(self, q, state, snap, *, scale=None, backend=None,
                      kv_block=512, sliding_window=None):
        AttendBackend.parse(backend)  # validate; reference serves all
        data = state.data
        if state.is_paged:
            kview, vview = paged.gather_view(data)
            data = BF16KVCache(k=kview, v=vview, length=data.length)
        return verify_attention_bf16(
            q, data, base_len=snap, scale=scale,
            sliding_window=sliding_window,
        )

    def truncate_rows(self, state, new_length, snap):
        del snap  # length-only scheme
        d = state.data
        return CacheState(self, d._replace(
            length=jnp.broadcast_to(new_length, d.length.shape).astype(
                d.length.dtype)
        ))

    def with_rotations(self, state, rot_k, rot_v):
        return state  # no rotation state

    def nbytes(self, state, *, persistent_only=True, per_shard=False):
        if state.is_paged:
            n = _leaf_bytes(*state.data.pools, per_shard=per_shard)
            if not persistent_only:
                n += paged.meta_nbytes(state.data, per_shard=per_shard)
            return n
        return _leaf_bytes(state.data.k, state.data.v,
                           per_shard=per_shard)

    def compression_ratio(self, state, *, per_shard=False) -> float:
        return 1.0


# ---------------------------------------------------------------------------
# int4 SRFT (the paper's deployment recipe)
# ---------------------------------------------------------------------------

class Int4State(NamedTuple):
    """int4 policy state: packed KV + the per-layer rotations that produced
    it.  Keeping the rotations next to the codes they rotated makes the
    cache self-contained (calibrated lambdas travel with the state through
    scan/checkpointing) and frees callers from rot_k/rot_v plumbing."""

    kv: QuantKVCache
    rot_k: Rotation
    rot_v: Rotation

    @property
    def length(self) -> jax.Array:
        return self.kv.length


_KERNEL_SLIDING_WINDOW_WARNED = False
_KERNEL_VERIFY_WARNED = False


@register_policy("int4-srft")
@dataclasses.dataclass(frozen=True)
class Int4SRFTPolicy:
    """SRFT rotation + per-channel lambda + int4 per-group codes + fp32
    residual window (paper §7.1-7.2).  Supports all three attend backends;
    their parity is asserted by tests/test_cache_api.py.

    Donation-safe (DESIGN.md §8): ``kvcache.prefill`` writes packed
    storage and residual window via ``dynamic_update_slice``;
    ``kvcache.decode_update`` writes one residual slot the same way and,
    on a flush step, rebuilds packed storage with a masked select over
    the old buffers (reads only as operands of the producing op).  All
    buffers keep shape/dtype, so the whole state aliases in place under
    ``donate_argnums``.
    """

    supported_backends = (AttendBackend.GATHER, AttendBackend.BLOCKWISE,
                          AttendBackend.KERNEL)

    group: int = 32
    window: int = 16
    rotation: str = "srft"  # srft | srht | identity

    def init_state(self, batch, n_kv_heads, s_max, head_dim, *, key=None,
                   ragged=False):
        if key is None:
            key = jax.random.PRNGKey(0)
        kk, kv_ = jax.random.split(key)
        return CacheState(self, Int4State(
            kv=kvcache.init_cache(
                batch, n_kv_heads, s_max, head_dim,
                group=self.group, window=self.window, ragged=ragged,
            ),
            rot_k=make_rotation(self.rotation, kk, head_dim),
            rot_v=make_rotation(self.rotation, kv_, head_dim),
        ))

    def init_paged(self, batch, n_kv_heads, s_max, head_dim, *, n_pages,
                   page_size, key=None):
        if head_dim % 2 or head_dim % self.group:
            raise ValueError(
                f"head_dim={head_dim} must divide 2 and group={self.group}"
            )
        if page_size % self.window:
            raise ValueError(
                f"page_size={page_size} must be a multiple of the int4 "
                f"flush window W={self.window}: a residual flush writes a "
                f"W-token slab at a W-aligned offset, and the multiple "
                f"guarantees the slab lands inside one (tail) page "
                f"(DESIGN.md §10)"
            )
        if key is None:
            key = jax.random.PRNGKey(0)
        kk, kv_ = jax.random.split(key)
        return CacheState(self, Int4State(
            kv=paged.init_paged(
                batch, s_max, page_size=page_size, n_pages=n_pages,
                leaf_specs=(
                    (n_kv_heads, head_dim // 2, jnp.uint8),
                    (n_kv_heads, head_dim // self.group, jnp.float32),
                    (n_kv_heads, head_dim // 2, jnp.uint8),
                    (n_kv_heads, head_dim // self.group, jnp.float32),
                ),
                residual_specs=(
                    (n_kv_heads, self.window, head_dim, jnp.float32),
                ) * 2,
            ),
            rot_k=make_rotation(self.rotation, kk, head_dim),
            rot_v=make_rotation(self.rotation, kv_, head_dim),
        ))

    def with_rotations(self, state, rot_k, rot_v):
        return CacheState(
            self, state.data._replace(rot_k=rot_k, rot_v=rot_v)
        )

    def prefill(self, state, k, v):
        d = state.data
        if state.is_paged:
            raise NotImplementedError(
                "paged states are filled per row: prefill a dense batch-1 "
                "ragged state and admit it with insert_row_paged"
            )
        return CacheState(self, d._replace(
            kv=kvcache.prefill(d.kv, d.rot_k, d.rot_v, k, v)
        ))

    def update(self, state, k, v, *, active=None):
        d = state.data
        if state.is_paged:
            return CacheState(self, d._replace(
                kv=paged.int4_update_paged(d.kv, d.rot_k, d.rot_v, k, v,
                                           active)
            ))
        if state.is_ragged:
            return CacheState(self, d._replace(
                kv=kvcache.decode_update_ragged(d.kv, d.rot_k, d.rot_v, k, v,
                                                active)
            ))
        if active is not None:
            raise ValueError("active masks need a ragged cache "
                             "(init_state(..., ragged=True))")
        return CacheState(self, d._replace(
            kv=kvcache.decode_update(d.kv, d.rot_k, d.rot_v, k, v)
        ))

    def prefill_chunk(self, state, k, v):
        d = state.data
        if state.is_paged:
            return CacheState(self, d._replace(
                kv=paged.int4_prefill_chunk_paged(d.kv, d.rot_k, d.rot_v,
                                                  k, v)
            ))
        if not state.is_ragged:
            raise ValueError("chunked prefill is a ragged/paged lifecycle "
                             "(init_state(..., ragged=True))")
        return CacheState(self, d._replace(
            kv=kvcache.prefill_chunk_ragged(d.kv, d.rot_k, d.rot_v, k, v)
        ))

    def adopt_prefix(self, row, paged_state, pages, n_tokens):
        # n_tokens must be W-aligned (engine contract): every adopted
        # byte then comes from packed pages and the residual ring stays
        # zero -- the exact state monolithic prefill leaves at a flush
        # boundary.
        d = row.data
        kp, ks, vp, vs = paged.read_pages(paged_state.data.kv, pages)
        kv = d.kv._replace(
            k_packed=kp.astype(d.kv.k_packed.dtype),
            k_scales=ks.astype(d.kv.k_scales.dtype),
            v_packed=vp.astype(d.kv.v_packed.dtype),
            v_scales=vs.astype(d.kv.v_scales.dtype),
            length=jnp.full_like(d.kv.length, n_tokens),
        )
        return CacheState(self, d._replace(kv=kv))

    def export_pages(self, state, pages):
        return _export_pool_pages(state.data.kv, pages)

    def import_pages(self, row, payload, n_tokens):
        # page-aligned n_tokens (engine contract, and page_size % W == 0)
        # keeps the residual ring in its zero init state -- the same
        # flush-boundary argument as adopt_prefix
        d = row.data
        kp, ks, vp, vs = payload
        kv = d.kv._replace(
            k_packed=_seed_dense_leaf(d.kv.k_packed, kp),
            k_scales=_seed_dense_leaf(d.kv.k_scales, ks),
            v_packed=_seed_dense_leaf(d.kv.v_packed, vp),
            v_scales=_seed_dense_leaf(d.kv.v_scales, vs),
            length=jnp.full_like(d.kv.length, n_tokens),
        )
        return CacheState(self, d._replace(kv=kv))

    def raw_kv_view(self, state):
        d = state.data
        yk, yv, _ = kvcache.gather_rotated(d.kv)
        return d.rot_k.inverse(yk), d.rot_v.inverse(yv)

    def insert_row(self, state, row, slot):
        # per-row KV storage is copied; the rotations are shared model
        # constants and stay the batched state's (the row cache MUST
        # have been built with the same rotations -- BatchEngine
        # guarantees this by reusing one init key / calibrated rots).
        d = state.data
        if state.is_paged:
            raise NotImplementedError(
                "paged admission goes through insert_row_paged (the engine "
                "supplies the COW page plan)"
            )
        return CacheState(self, d._replace(kv=jax.tree.map(
            lambda b, r: _insert_row_leaf(b, r, slot), d.kv, row.data.kv
        )))

    def insert_row_paged(self, state, row, slot, shared_pages, n_shared,
                         n_new):
        d = state.data
        rkv = row.data.kv  # dense batch-1 ragged QuantKVCache
        return CacheState(self, d._replace(kv=paged.insert_row(
            d.kv,
            (rkv.k_packed, rkv.k_scales, rkv.v_packed, rkv.v_scales),
            (rkv.k_residual, rkv.v_residual),
            rkv.length, slot, shared_pages, n_shared, n_new,
        )))

    def reset_rows(self, state, mask):
        d = state.data
        if state.is_paged:
            return CacheState(self, d._replace(
                kv=paged.reset_rows(d.kv, mask)
            ))
        return CacheState(self, d._replace(kv=d.kv._replace(
            length=jnp.where(mask, 0, d.kv.length)
        )))

    def _dense_kv_view(self, d) -> QuantKVCache:
        """Per-row dense view of a paged int4 state (jnp read paths
        gather through the page table; the kernel walks pages)."""
        kp, ks, vp, vs = paged.gather_view(d.kv)
        k_res, v_res = d.kv.residual
        return QuantKVCache(kp, ks, vp, vs, k_res, v_res, d.kv.length)

    def attend(self, q, state, *, scale=None, backend=None, kv_block=512,
               sliding_window=None):
        backend = AttendBackend.parse(backend)
        d = state.data
        is_paged = state.is_paged
        if backend is AttendBackend.KERNEL and sliding_window is not None:
            # Mid-request backend/feature mismatch must not kill the
            # request: serve the step through the blockwise mirror
            # (same tiling, same numerics) and say so once.
            global _KERNEL_SLIDING_WINDOW_WARNED
            if not _KERNEL_SLIDING_WINDOW_WARNED:
                _KERNEL_SLIDING_WINDOW_WARNED = True
                warnings.warn(
                    "int4-srft: the Pallas kernel path does not "
                    "implement sliding_window; falling back to the "
                    "BLOCKWISE read path for this and subsequent "
                    "windowed reads",
                    RuntimeWarning,
                    stacklevel=2,
                )
            backend = AttendBackend.BLOCKWISE
        if backend is AttendBackend.KERNEL and is_paged:
            # paged kernel: the page table rides the scalar prefetch and
            # each grid step gathers several physical pages of one row
            # -- the dense view is never materialized.
            from repro.kernels.quant_attention import (
                decode_attention_kernel_paged,
            )

            return decode_attention_kernel_paged(
                q, d.kv, d.rot_k, d.rot_v, scale=scale
            )
        kv = self._dense_kv_view(d) if is_paged else d.kv
        if backend is AttendBackend.BLOCKWISE:
            return decode_attention_quant_blockwise(
                q, kv, d.rot_k, d.rot_v, scale=scale,
                sliding_window=sliding_window, kv_block=kv_block,
            )
        if backend is AttendBackend.KERNEL:
            from repro.kernels.quant_attention import decode_attention_kernel

            return decode_attention_kernel(
                q, kv, d.rot_k, d.rot_v, scale=scale, blk=kv_block
            )
        return decode_attention_quant(
            q, kv, d.rot_k, d.rot_v, scale=scale,
            sliding_window=sliding_window,
        )

    def snapshot_rows(self, state):
        # the mod-W ring is an overwrite structure: carry the O(B·W)
        # buffers alongside the entry lengths (DESIGN.md §13)
        d = state.data
        if state.is_paged:
            k_res, v_res = d.kv.residual
        else:
            k_res, v_res = d.kv.k_residual, d.kv.v_residual
        return (k_res, v_res, d.kv.length)

    def verify_attend(self, q, state, snap, *, scale=None, backend=None,
                      kv_block=512, sliding_window=None):
        backend = AttendBackend.parse(backend)
        if backend is AttendBackend.KERNEL:
            # verify reads are multi-query; the Pallas decode kernel is
            # single-query.  Serve the pass through the reference path
            # (same numerics as GATHER) and say so once.
            global _KERNEL_VERIFY_WARNED
            if not _KERNEL_VERIFY_WARNED:
                _KERNEL_VERIFY_WARNED = True
                warnings.warn(
                    "int4-srft: the Pallas kernel path does not implement "
                    "multi-query speculative verify; falling back to the "
                    "GATHER reference read path for this and subsequent "
                    "verify passes",
                    RuntimeWarning,
                    stacklevel=2,
                )
        d = state.data
        snap_k, snap_v, base_len = snap
        kv = self._dense_kv_view(d) if state.is_paged else d.kv
        return verify_attention_quant(
            q, kv, d.rot_k, d.rot_v,
            snap_k_res=snap_k, snap_v_res=snap_v, base_len=base_len,
            scale=scale, sliding_window=sliding_window,
        )

    def truncate_rows(self, state, new_length, snap):
        d = state.data
        snap_k, snap_v, base_len = snap
        if state.is_paged:
            pdd = d.kv
            k_res = kvcache.rewind_residual(
                pdd.residual[0], snap_k, base_len, new_length)
            v_res = kvcache.rewind_residual(
                pdd.residual[1], snap_v, base_len, new_length)
            return CacheState(self, d._replace(kv=pdd._replace(
                residual=(k_res, v_res),
                length=jnp.broadcast_to(new_length, pdd.length.shape).astype(
                    pdd.length.dtype),
            )))
        return CacheState(self, d._replace(kv=kvcache.truncate_rows(
            d.kv, new_length, snap_k, snap_v, base_len
        )))

    def nbytes(self, state, *, persistent_only=True, per_shard=False):
        """Cache bytes.  ``persistent_only`` counts the O(S) packed codes +
        scales (for paged states: the whole page pool -- that is the
        allocation, mirroring how dense states count their full
        capacity); otherwise the O(W) fp32 residual window and, for
        paged states, the page-table + allocator metadata are included.
        The rotation matrices are excluded either way: they are O(d^2)
        model constants (parameters), not per-token cache.
        ``per_shard``: one device's resident bytes instead of the
        global-logical figure (protocol docstring)."""
        if state.is_paged:
            pd = state.data.kv
            n = _leaf_bytes(*pd.pools, per_shard=per_shard)
            if not persistent_only:
                n += _leaf_bytes(*pd.residual, per_shard=per_shard) \
                    + paged.meta_nbytes(pd, per_shard=per_shard)
            return n
        kv = state.data.kv
        n = _leaf_bytes(kv.k_packed, kv.k_scales, kv.v_packed,
                        kv.v_scales, per_shard=per_shard)
        if not persistent_only:
            n += _leaf_bytes(kv.k_residual, kv.v_residual,
                             per_shard=per_shard)
        return n

    def compression_ratio(self, state, *, per_shard=False) -> float:
        """bf16-equivalent bytes / persistent bytes (paper §4.5)."""
        kv = state.data.kv
        k_packed = kv.pools[0] if state.is_paged else kv.k_packed
        d = k_packed.shape[-1] * 2
        # K vectors incl. layer axis (per-shard: this device's slice)
        n_vectors = _leaf_elems(k_packed, per_shard=per_shard) // (d // 2)
        bf16 = 2 * 2 * n_vectors * d  # K and V at 2 B/coord
        return bf16 / self.nbytes(state, per_shard=per_shard)


# ---------------------------------------------------------------------------
# int8 per-token (third scheme: proves the registry carries new policies)
# ---------------------------------------------------------------------------

class Int8State(NamedTuple):
    k_codes: jax.Array   # (B, Hkv, S_max, d) int8
    k_scales: jax.Array  # (B, Hkv, S_max, 1) f32, one scale per vector
    v_codes: jax.Array   # (B, Hkv, S_max, d) int8
    v_scales: jax.Array  # (B, Hkv, S_max, 1) f32
    length: jax.Array    # () int32


@register_policy("int8-per-token")
@dataclasses.dataclass(frozen=True)
class Int8PerTokenPolicy:
    """Symmetric int8 with one fp32 scale per K/V vector (paper Table 5's
    per_token row at 8 bits: near-lossless, no rotation needed).

    Realized directly on ``quant.quantize_per_token``, so the whole
    scheme is ~40 lines on top of the existing quantizers.  ~1.9x
    compression at d=128 vs bf16.  Read path: dense dequant-gather (the
    BLOCKWISE/KERNEL tiled paths are int4-only; requesting them raises).

    Donation-safe: ``_write`` is four ``dynamic_update_slice`` ops over
    the old buffers, shape/dtype preserved -- aliases in place under
    ``donate_argnums`` (DESIGN.md §8).
    """

    supported_backends = (AttendBackend.GATHER,)

    def _quant(self, x):
        q = quant.quantize_per_token(x, 8)
        return q.codes, q.scales  # codes (...,d) int8, scales (...,1) f32

    def init_state(self, batch, n_kv_heads, s_max, head_dim, *, key=None,
                   ragged=False):
        shape_c = (batch, n_kv_heads, s_max, head_dim)
        shape_s = (batch, n_kv_heads, s_max, 1)
        return CacheState(self, Int8State(
            k_codes=jnp.zeros(shape_c, jnp.int8),
            k_scales=jnp.zeros(shape_s, jnp.float32),
            v_codes=jnp.zeros(shape_c, jnp.int8),
            v_scales=jnp.zeros(shape_s, jnp.float32),
            length=jnp.zeros((batch,) if ragged else (), jnp.int32),
        ))

    def init_paged(self, batch, n_kv_heads, s_max, head_dim, *, n_pages,
                   page_size, key=None):
        return CacheState(self, paged.init_paged(
            batch, s_max, page_size=page_size, n_pages=n_pages,
            leaf_specs=(
                (n_kv_heads, head_dim, jnp.int8),
                (n_kv_heads, 1, jnp.float32),
                (n_kv_heads, head_dim, jnp.int8),
                (n_kv_heads, 1, jnp.float32),
            ),
        ))

    def with_rotations(self, state, rot_k, rot_v):
        return state  # rotation-free scheme

    def _write(self, state, k, v, offset):
        d = state.data
        kc, ks = self._quant(k)
        vc, vs = self._quant(v)
        at = (0, 0, offset, 0)
        return Int8State(
            k_codes=jax.lax.dynamic_update_slice(d.k_codes, kc, at),
            k_scales=jax.lax.dynamic_update_slice(d.k_scales, ks, at),
            v_codes=jax.lax.dynamic_update_slice(d.v_codes, vc, at),
            v_scales=jax.lax.dynamic_update_slice(d.v_scales, vs, at),
            length=d.length,
        )

    def _write_ragged(self, state, k, v, offsets):
        """Per-row writes at per-row offsets (vmapped DUS = scatter)."""
        d = state.data
        kc, ks = self._quant(k)
        vc, vs = self._quant(v)

        def put(buf, val, off):  # (H,S,·), (H,1,·), ()
            return jax.lax.dynamic_update_slice(buf, val, (0, off, 0))

        return Int8State(
            k_codes=jax.vmap(put)(d.k_codes, kc, offsets),
            k_scales=jax.vmap(put)(d.k_scales, ks, offsets),
            v_codes=jax.vmap(put)(d.v_codes, vc, offsets),
            v_scales=jax.vmap(put)(d.v_scales, vs, offsets),
            length=d.length,
        )

    def prefill(self, state, k, v):
        if state.is_paged:
            raise NotImplementedError(
                "paged states are filled per row: prefill a dense batch-1 "
                "ragged state and admit it with insert_row_paged"
            )
        S = k.shape[-2]
        new = self._write(state, k, v, 0)
        return CacheState(self, new._replace(
            length=jnp.full_like(state.data.length, S)
        ))

    def update(self, state, k, v, *, active=None):
        if state.is_paged:
            kc, ks = self._quant(k)
            vc, vs = self._quant(v)
            return CacheState(self, paged.append_token(
                state.data, (kc, ks, vc, vs), active
            ))
        lengths = state.data.length
        if state.is_ragged:
            new = self._write_ragged(state, k, v, lengths)
            new_len = lengths + 1 if active is None \
                else jnp.where(active, lengths + 1, lengths)
            return CacheState(self, new._replace(length=new_len))
        if active is not None:
            raise ValueError("active masks need a ragged cache "
                             "(init_state(..., ragged=True))")
        new = self._write(state, k, v, lengths)
        return CacheState(self, new._replace(length=lengths + 1))

    def prefill_chunk(self, state, k, v):
        if state.is_paged:
            kc, ks = self._quant(k)
            vc, vs = self._quant(v)
            return CacheState(self, paged.append_chunk(
                state.data, (kc, ks, vc, vs)
            ))
        if not state.is_ragged:
            raise ValueError("chunked prefill is a ragged/paged lifecycle "
                             "(init_state(..., ragged=True))")
        lengths = state.data.length
        new = self._write_ragged(state, k, v, lengths)
        return CacheState(self, new._replace(length=lengths + k.shape[-2]))

    def adopt_prefix(self, row, paged_state, pages, n_tokens):
        d = row.data
        kc, ks, vc, vs = paged.read_pages(paged_state.data, pages)
        return CacheState(self, Int8State(
            k_codes=kc.astype(d.k_codes.dtype),
            k_scales=ks.astype(d.k_scales.dtype),
            v_codes=vc.astype(d.v_codes.dtype),
            v_scales=vs.astype(d.v_scales.dtype),
            length=jnp.full_like(d.length, n_tokens),
        ))

    def export_pages(self, state, pages):
        return _export_pool_pages(state.data, pages)

    def import_pages(self, row, payload, n_tokens):
        d = row.data
        kc, ks, vc, vs = payload
        return CacheState(self, Int8State(
            k_codes=_seed_dense_leaf(d.k_codes, kc),
            k_scales=_seed_dense_leaf(d.k_scales, ks),
            v_codes=_seed_dense_leaf(d.v_codes, vc),
            v_scales=_seed_dense_leaf(d.v_scales, vs),
            length=jnp.full_like(d.length, n_tokens),
        ))

    def raw_kv_view(self, state):
        d = state.data
        k = quant.dequantize_per_token(
            quant.Quantized(d.k_codes, d.k_scales, 8)
        )
        v = quant.dequantize_per_token(
            quant.Quantized(d.v_codes, d.v_scales, 8)
        )
        return k, v

    def insert_row(self, state, row, slot):
        if state.is_paged:
            raise NotImplementedError(
                "paged admission goes through insert_row_paged (the engine "
                "supplies the COW page plan)"
            )
        return CacheState(self, jax.tree.map(
            lambda b, r: _insert_row_leaf(b, r, slot), state.data, row.data
        ))

    def insert_row_paged(self, state, row, slot, shared_pages, n_shared,
                         n_new):
        rd = row.data  # dense batch-1 ragged Int8State
        return CacheState(self, paged.insert_row(
            state.data, (rd.k_codes, rd.k_scales, rd.v_codes, rd.v_scales),
            (), rd.length, slot, shared_pages, n_shared, n_new,
        ))

    def reset_rows(self, state, mask):
        if state.is_paged:
            return CacheState(self, paged.reset_rows(state.data, mask))
        return CacheState(self, state.data._replace(
            length=jnp.where(mask, 0, state.data.length)
        ))

    def attend(self, q, state, *, scale=None, backend=None, kv_block=512,
               sliding_window=None):
        backend = AttendBackend.parse(backend)
        if backend is not AttendBackend.GATHER:
            raise NotImplementedError(
                f"int8-per-token implements only the GATHER read path "
                f"(got {backend.value}); tiled dequant is int4-only"
            )
        d = state.data
        if state.is_paged:
            kc, ks, vc, vs = paged.gather_view(d)
            d = Int8State(k_codes=kc, k_scales=ks, v_codes=vc, v_scales=vs,
                          length=d.length)
        k = quant.dequantize_per_token(
            quant.Quantized(d.k_codes, d.k_scales, 8)
        )
        v = quant.dequantize_per_token(
            quant.Quantized(d.v_codes, d.v_scales, 8)
        )
        # dequantized K/V in the original basis: reuse the dense oracle
        return decode_attention_bf16(
            q, BF16KVCache(k=k, v=v, length=d.length),
            scale=scale, sliding_window=sliding_window,
        )

    def snapshot_rows(self, state):
        # per-token quantization is position-addressed: appends at
        # position t overwrite (codes, scale) for t wholesale, so the
        # entry lengths are the whole rewind
        return state.data.length

    def verify_attend(self, q, state, snap, *, scale=None, backend=None,
                      kv_block=512, sliding_window=None):
        AttendBackend.parse(backend)  # validate; reference serves all
        d = state.data
        if state.is_paged:
            kc, ks, vc, vs = paged.gather_view(d)
            d = Int8State(k_codes=kc, k_scales=ks, v_codes=vc, v_scales=vs,
                          length=d.length)
        k = quant.dequantize_per_token(
            quant.Quantized(d.k_codes, d.k_scales, 8)
        )
        v = quant.dequantize_per_token(
            quant.Quantized(d.v_codes, d.v_scales, 8)
        )
        return verify_attention_bf16(
            q, BF16KVCache(k=k, v=v, length=d.length),
            base_len=snap, scale=scale, sliding_window=sliding_window,
        )

    def truncate_rows(self, state, new_length, snap):
        del snap  # length-only scheme
        d = state.data
        return CacheState(self, d._replace(
            length=jnp.broadcast_to(new_length, d.length.shape).astype(
                d.length.dtype)
        ))

    def nbytes(self, state, *, persistent_only=True, per_shard=False):
        d = state.data
        if state.is_paged:
            n = _leaf_bytes(*d.pools, per_shard=per_shard)
            if not persistent_only:
                n += paged.meta_nbytes(d, per_shard=per_shard)
            return n
        return _leaf_bytes(d.k_codes, d.k_scales, d.v_codes, d.v_scales,
                           per_shard=per_shard)

    def compression_ratio(self, state, *, per_shard=False) -> float:
        d = state.data
        k_codes = d.pools[0] if state.is_paged else d.k_codes
        bf16 = 2 * 2 * _leaf_elems(k_codes, per_shard=per_shard)
        return bf16 / self.nbytes(state, per_shard=per_shard)
