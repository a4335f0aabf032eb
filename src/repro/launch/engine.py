"""Fused generation engine: the whole decode loop in one device dispatch.

The paper's mechanism is bandwidth (DESIGN.md §1): int4 wins because the
decode hot loop streams ~3x fewer bytes than fp16.  A Python-driven
``jit(decode_step)``-per-token loop throws that win away -- every step
pays host round-trip latency and, without buffer donation, a full
O(S_max) copy of the cache pytree.  This module is the serving analogue
of the paper's ``model.generate``: prefill plus the *entire* decode loop
run inside a single ``jax.jit`` via ``lax.scan``, with the cache pytree
donated (``donate_argnums``) so each policy's ``update`` lowers to an
in-place ``dynamic_update_slice`` instead of a per-token copy.

Scan carry layout (DESIGN.md §8)::

    carry = (token (B, 1) int32, cache pytree, prng key (2,) uint32)

``cache`` is whatever ``model.init_cache`` built -- a dict whose "attn"
entry is a layer-stacked :class:`~repro.core.cache_api.CacheState` (the
policy rides in the treedef, so the carry is self-describing), plus any
recurrent state (ssm/hybrid/xlstm) and the scalar "pos".  The carry
treedef must be invariant under ``decode_step``; every model family
guarantees that (tested by tests/test_engine.py).

Donation invariants each policy's ``update`` must satisfy (audited in
core/cache_api.py + core/kvcache.py; see DESIGN.md §8):

  * same pytree structure, shapes and dtypes in and out (XLA can only
    alias matching buffers);
  * no read of a cache buffer *after* the write that replaces it -- all
    reads happen as operands of the op producing the new buffer
    (``dynamic_update_slice`` / ``select``), which XLA updates in place.

Entry points:

``generate(params, prompt, cache, n_tokens, *, model, backend, sampler)``
    One dispatch for prefill + decode.  Greedy by default; pass a
    :class:`Sampler` for temperature / top-k sampling (PRNG state is a
    scan carry).  ``prompt`` may be a tuple (e.g. ``(frames, tokens)``
    for the audio encoder-decoder).

``Engine``
    The reusable object behind :func:`generate`: jitted ``prefill`` /
    ``decode`` / ``generate`` with per-``n_tokens`` compilation caching.
    ``prefill`` + ``decode`` let serving report prefill latency and
    decode-only throughput separately while keeping the decode loop a
    single dispatch.

CAUTION: donated caches are consumed -- after ``generate``/``decode``
returns, the *input* cache buffers are invalid (that is the point: no
per-token copy).  Pass ``donate=False`` to keep the functional
semantics for debugging.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.cache_api import AttendBackend

__all__ = ["Sampler", "GREEDY", "Engine", "generate", "draft_tokens"]


def resolve_mesh_backend(backend, mesh):
    """Refuse the Pallas KERNEL backend under a mesh.

    The decode kernel addresses one device's buffers and has no
    shard_map wrapper yet, so a mesh-sharded engine must be asked for
    BLOCKWISE (the same masked-read semantics in jnp) explicitly.
    """
    if mesh is not None and backend == AttendBackend.KERNEL:
        raise ValueError(
            "the 'kernel' backend is single-device (Pallas) and cannot "
            "serve a mesh-sharded engine; use backend 'blockwise'"
        )
    return backend


def _serve_policy_ctx(mesh):
    """Trace-time activation-sharding context: serve_exact under a mesh
    (DESIGN.md §16), identity otherwise."""
    if mesh is None:
        return contextlib.nullcontext()
    from repro.launch.act_sharding import use_policy

    return use_policy(mesh, "serve_exact")


@dataclasses.dataclass(frozen=True)
class Sampler:
    """Token-selection rule (static: hashable, part of the jit key).

    temperature == 0 is greedy argmax (the PRNG key is split but unused,
    keeping the scan carry layout identical across samplers).  top_k > 0
    restricts sampling to the k highest logits.
    """

    temperature: float = 0.0
    top_k: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")

    def sample(self, logits: jax.Array, key: jax.Array) -> jax.Array:
        """logits (B, V) -> tokens (B,) int32."""
        if self.temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) / self.temperature
        if self.top_k:
            kth = jax.lax.top_k(scaled, self.top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


GREEDY = Sampler()


def draft_tokens(hist: jax.Array, hlen: jax.Array, k: int) -> jax.Array:
    """n-gram / prompt-lookup drafter (DESIGN.md §13): propose ``k - 1``
    continuation tokens from the request's own history.

    ``hist`` is ``(B, H)`` int32 -- prompt followed by every token
    sampled so far, with ``hist[:, hlen - 1]`` the current token; ``hlen``
    is a () int32 when rows advance in lockstep (one fused engine) or a
    per-row ``(B,)`` int32 (the ragged batch engine: each slot's history
    has its own length).  Finds the most recent earlier position whose
    (previous, current) bigram matches the tail (unigram fallback) and
    proposes the tokens that followed it; with no match it proposes the
    current token repeated.  Entirely in-trace (one pass over ``hist``,
    no host sync) and allowed to be WRONG: drafts only ever gate how many
    verified tokens are accepted, never what they are -- greedy verify
    output is bit-identical to plain decode for any drafts whatsoever.
    Returns ``(B, k - 1)`` int32.
    """
    B, H = hist.shape
    pos = jnp.arange(H)[None, :]  # (1, H)
    if jnp.ndim(hlen):
        # ragged: per-row tails via clipped gathers (rows with hlen == 0
        # -- empty slots -- read garbage that never matters: their drafts
        # are masked out by the caller's ``active`` vector)
        hl = hlen[:, None]  # (B, 1)
        t = jnp.take_along_axis(hist, jnp.clip(hl - 1, 0, H - 1), axis=1)
        prev = jnp.take_along_axis(hist, jnp.clip(hl - 2, 0, H - 1), axis=1)
        can = pos < hl - 1
    else:
        t = jax.lax.dynamic_slice_in_dim(hist, hlen - 1, 1, axis=1)  # (B,1)
        prev = jax.lax.dynamic_slice_in_dim(
            hist, jnp.maximum(hlen - 2, 0), 1, axis=1
        )
        # candidate p must have a successor inside the realized history
        can = pos < hlen - 1
    m1 = can & (hist == t)
    m2 = m1 & (pos >= 1) \
        & (jnp.concatenate([hist[:, :1], hist[:, :-1]], axis=1) == prev)
    p1 = jnp.max(jnp.where(m1, pos, -1), axis=1)  # (B,) most recent match
    p2 = jnp.max(jnp.where(m2, pos, -1), axis=1)
    pstar = jnp.where(p2 >= 0, p2, p1)  # bigram preferred
    j = jnp.arange(1, k)[None, :]
    gidx = jnp.clip(pstar[:, None] + j, 0, H - 1)
    drafts = jnp.take_along_axis(hist, gidx, axis=1)
    return jnp.where(pstar[:, None] >= 0, drafts, t).astype(jnp.int32)


class Engine:
    """Fused generation for one (model, backend, sampler) configuration.

    Compiled callables are cached per ``n_tokens`` (the scan length is
    static); everything else -- params, prompt, cache, key -- is traced.
    """

    def __init__(self, model, *, backend: "AttendBackend | str | None" = None,
                 sampler: Optional[Sampler] = None, kv_block: int = 512,
                 donate: bool = True, mesh=None):
        self.model = model
        self.backend = resolve_mesh_backend(
            None if backend is None else AttendBackend.parse(backend), mesh
        )
        self.sampler = sampler if sampler is not None else GREEDY
        self.kv_block = kv_block
        self.donate = donate
        self.mesh = mesh
        self._prefill = jax.jit(
            self._traced(self._prefill_impl),
            donate_argnums=(2,) if donate else (),
        )
        self._decode_fns: dict[int, Any] = {}
        self._generate_fns: dict[int, Any] = {}
        self._spec_fns: dict[tuple, Any] = {}

    # ------------------------------------------------------------- internals
    def _traced(self, fn):
        """Wrap a to-be-jitted callable so tracing runs under the
        serve_exact activation policy when the engine has a mesh
        (identity otherwise; compiled calls are unaffected)."""
        if self.mesh is None:
            return fn

        def inner(*args):
            with _serve_policy_ctx(self.mesh):
                return fn(*args)

        return inner

    def shard_params(self, params):
        """Replicate params across the mesh (DESIGN.md §16: decode is
        KV-bandwidth-bound; replicated weights keep every projection a
        full-width, bit-exact matmul).  Identity without a mesh."""
        if self.mesh is None:
            return params
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(self.mesh, PartitionSpec())
        return jax.device_put(params, jax.tree.map(lambda _: rep, params))

    def shard_cache(self, cache, *, allow_split_k: bool = False):
        """Lay a cache pytree out across the mesh: KV heads over
        'model' where divisible, replication otherwise (the serving
        ladder -- partitioning.serve_cache_specs).  Donation preserves
        the layout through every subsequent dispatch.  Identity without
        a mesh."""
        if self.mesh is None:
            return cache
        from repro.launch import partitioning as pt

        specs = pt.serve_cache_specs(
            cache, self.mesh, allow_split_k=allow_split_k
        )
        return jax.device_put(cache, pt.make_shardings(specs, self.mesh))

    def _prefill_impl(self, params, prompt, cache):
        if isinstance(prompt, tuple):
            return self.model.prefill(params, *prompt, cache)
        return self.model.prefill(params, prompt, cache)

    def _decode_body(self, params):
        """lax.scan body: one decode_step + one sample draw."""
        step = self.model.decode_body(
            params, kv_block=self.kv_block, backend=self.backend
        )

        def body(carry, _):
            tok, cache, key = carry
            cache, logits = step(cache, tok)
            key, sub = jax.random.split(key)
            nxt = self.sampler.sample(logits[:, -1], sub)[:, None]
            return (nxt, cache, key), nxt[:, 0]

        return body

    def _decode_loop(self, n_steps, params, tok, cache, key):
        (tok, cache, key), toks = jax.lax.scan(
            self._decode_body(params), (tok, cache, key), None,
            length=n_steps,
        )
        return jnp.moveaxis(toks, 0, 1), (tok, cache, key)  # (B, n_steps)

    # ----------------------------------------------------------- public API
    def prefill(self, params, prompt, cache):
        """Jitted prefill.  Returns (last-token logits, cache).  The input
        cache is donated when the engine donates (it is blank anyway)."""
        return self._prefill(params, prompt, cache)

    def decode(self, params, tok, cache, n_tokens: int, *,
               key: Optional[jax.Array] = None):
        """Fused decode loop: ONE dispatch for ``n_tokens`` steps.

        ``tok`` (B, 1) is the last sampled token (cache does not yet
        contain it).  Returns (tokens (B, n_tokens), cache).  The input
        cache is donated -- invalid after the call.
        """
        fn = self._decode_fns.get(n_tokens)
        if fn is None:
            def run(params, tok, cache, key):
                toks, (_, cache, _) = self._decode_loop(
                    n_tokens, params, tok, cache, key
                )
                return toks, cache

            fn = jax.jit(self._traced(run),
                         donate_argnums=(2,) if self.donate else ())
            self._decode_fns[n_tokens] = fn
        if key is None:
            key = jax.random.PRNGKey(0)
        return fn(params, tok, cache, key)

    def generate(self, params, prompt, cache, n_tokens: int, *,
                 key: Optional[jax.Array] = None):
        """Prefill + sample + (n_tokens - 1) decode steps, one dispatch.

        Returns (tokens (B, n_tokens), cache).  Matches the conventional
        per-step loop exactly: the first token is sampled from the
        prefill logits; the final sampled token is returned but not
        appended to the cache.  The input cache is donated.
        """
        fn = self._generate_fns.get(n_tokens)
        if fn is None:
            def run(params, prompt, cache, key):
                logits, cache = self._prefill_impl(params, prompt, cache)
                key, sub = jax.random.split(key)
                tok0 = self.sampler.sample(logits[:, -1], sub)[:, None]
                toks, (_, cache, _) = self._decode_loop(
                    n_tokens - 1, params, tok0, cache, key
                )
                return jnp.concatenate([tok0, toks], axis=1), cache

            fn = jax.jit(self._traced(run),
                         donate_argnums=(2,) if self.donate else ())
            self._generate_fns[n_tokens] = fn
        if key is None:
            key = jax.random.PRNGKey(0)
        return fn(params, prompt, cache, key)

    # ----------------------------------------------- speculative decoding
    def _check_spec(self, cache, spec_k: int, batch: int):
        if self.sampler.temperature != 0.0:
            raise ValueError(
                "speculative decoding requires greedy sampling "
                "(temperature == 0): exact-match acceptance against the "
                "verify argmax is what keeps output bit-identical"
            )
        if spec_k < 2:
            raise ValueError(f"spec_k must be >= 2, got {spec_k}")
        if batch != 1:
            raise ValueError(
                "Engine.decode_spec serves a single stream (batch 1): a "
                "non-ragged cache has one shared length, so per-row "
                "acceptance widths are impossible -- use BatchEngine "
                "with spec_k for batched speculative decoding"
            )
        pol = cache["attn"].policy
        W = getattr(pol, "window", None)
        if W is not None and spec_k > W:
            raise ValueError(
                f"spec_k={spec_k} must be <= the policy flush window "
                f"W={W}: a verify pass appends at most one residual-ring "
                f"wrap (DESIGN.md §13)"
            )

    def _spec_body(self, params, n_tokens: int, spec_k: int):
        """lax.scan body: one draft-verify-accept-rollback pass.

        Emits 1..spec_k tokens per firing into the carried output buffer;
        firings after the budget is spent are skipped via ``lax.cond``
        (no append past ``n_tokens``, so cache state stays exactly what a
        sequential run leaves behind)."""
        k = spec_k

        def do_pass(op):
            out_buf, tok, cache, key, hist, hlen, count, nd, na = op
            L0 = cache["pos"]  # () int32: entry length
            drafts = draft_tokens(hist, hlen, k)  # (B, k-1)
            block = jnp.concatenate([tok, drafts], axis=1)  # (B, k)
            logits, cache, snaps = self.model.decode_verify(
                params, block, cache, kv_block=self.kv_block,
                backend=self.backend,
            )
            key, _ = jax.random.split(key)  # greedy: drawn, unused
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k)
            # exact-match acceptance: longest prefix of drafts that equals
            # the verified greedy tokens, +1 for the bonus token
            match = (block[:, 1:] == g[:, :-1]).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)[0]  # ()
            m = jnp.minimum(a + 1, n_tokens - count)  # budget clamp
            out_buf = jax.lax.dynamic_update_slice(out_buf, g, (0, count))
            # rejected garbage past position count + m is overwritten by
            # the next pass's k-wide write before the final [:n_tokens]
            # slice can see it
            cache = self.model.truncate_cache(cache, L0 + m, snaps)
            tok = jax.lax.dynamic_slice(g, (0, m - 1), (g.shape[0], 1))
            hist = jax.lax.dynamic_update_slice(hist, g, (0, hlen))
            return (out_buf, tok, cache, key, hist, hlen + m, count + m,
                    nd + k - 1, na + m - 1)

        def body(carry, _):
            count = carry[6]
            carry = jax.lax.cond(
                count < n_tokens, do_pass, lambda op: op, carry
            )
            return carry, None

        return body

    def decode_spec(self, params, tok, cache, n_tokens: int, *,
                    prompt: jax.Array, spec_k: int,
                    key: Optional[jax.Array] = None):
        """Self-speculative fused decode (DESIGN.md §13): ONE dispatch
        scanning draft-verify passes until ``n_tokens`` tokens are out.

        ``tok`` (1, 1) is the last sampled token (not yet in the cache);
        ``prompt`` (1, S) seeds the prompt-lookup drafter.  Greedy only;
        returns ``(tokens (1, n_tokens), cache, stats)`` with ``tokens``
        bit-identical to :meth:`decode` and ``stats`` the device counters
        ``{"drafted": (), "accepted": ()}`` (accepted/drafted = the
        acceptance rate; both count draft positions, excluding the
        always-emitted bonus token).  The cache must have
        ``spec_k - 1`` tokens of capacity slack past the last decoded
        position (verify appends before rollback).  Input cache donated.
        """
        self._check_spec(cache, spec_k, tok.shape[0])
        S = prompt.shape[1]
        sig = (n_tokens, spec_k, S)
        fn = self._spec_fns.get(sig)
        if fn is None:
            def run(params, tok, cache, prompt, key):
                B = tok.shape[0]
                H = S + n_tokens + spec_k
                hist = jnp.zeros((B, H), jnp.int32)
                hist = jax.lax.dynamic_update_slice(
                    hist, prompt.astype(jnp.int32), (0, 0))
                hist = jax.lax.dynamic_update_slice(hist, tok, (0, S))
                out_buf = jnp.zeros((B, n_tokens + spec_k), jnp.int32)
                carry = (out_buf, tok, cache, key, hist,
                         jnp.int32(S + 1), jnp.int32(0),
                         jnp.int32(0), jnp.int32(0))
                carry, _ = jax.lax.scan(
                    self._spec_body(params, n_tokens, spec_k), carry, None,
                    length=n_tokens,
                )
                out_buf, _, cache, _, _, _, _, nd, na = carry
                return out_buf[:, :n_tokens], cache, {"drafted": nd,
                                                      "accepted": na}

            fn = jax.jit(self._traced(run),
                         donate_argnums=(2,) if self.donate else ())
            self._spec_fns[sig] = fn
        if key is None:
            key = jax.random.PRNGKey(0)
        return fn(params, tok, cache, prompt, key)

    def generate_spec(self, params, prompt, cache, n_tokens: int, *,
                      spec_k: int, key: Optional[jax.Array] = None):
        """Prefill + speculative decode, matching :meth:`generate`'s
        output bit-for-bit (greedy): the first token comes from the
        prefill logits, the remaining ``n_tokens - 1`` from
        :meth:`decode_spec`.  Returns ``(tokens (1, n_tokens), cache,
        stats)``."""
        # validate BEFORE the prefill donates the cache: a bad spec_k
        # must not consume the caller's buffers
        self._check_spec(cache, spec_k, prompt.shape[0])
        logits, cache = self.prefill(params, prompt, cache)
        tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        if n_tokens == 1:
            return tok0, cache, {"drafted": jnp.int32(0),
                                 "accepted": jnp.int32(0)}
        toks, cache, stats = self.decode_spec(
            params, tok0, cache, n_tokens - 1, prompt=prompt,
            spec_k=spec_k, key=key,
        )
        return jnp.concatenate([tok0, toks], axis=1), cache, stats


@functools.lru_cache(maxsize=64)
def _engine(model, backend, sampler, kv_block, donate) -> Engine:
    return Engine(model, backend=backend, sampler=sampler,
                  kv_block=kv_block, donate=donate)


def generate(params, prompt, cache, n_tokens: int, *, model,
             backend: "AttendBackend | str | None" = None,
             sampler: Optional[Sampler] = None,
             key: Optional[jax.Array] = None, kv_block: int = 512,
             donate: bool = True):
    """Fused generation (module-level convenience over :class:`Engine`).

    One device dispatch for prefill + the whole decode loop; the cache is
    donated (invalid afterwards) unless ``donate=False``.  Engines are
    cached per (model, backend, sampler, kv_block, donate), compiled
    callables per ``n_tokens``.
    """
    backend = None if backend is None else AttendBackend.parse(backend)
    eng = _engine(model, backend, sampler if sampler is not None else GREEDY,
                  kv_block, donate)
    return eng.generate(params, prompt, cache, n_tokens, key=key)
