"""Pipeline parallelism: GPipe-style microbatched schedule over a mesh
axis, built on shard_map + collective_permute.

Each stage owns n_layers/n_stages layers (stacked leading axis sliced by
stage id).  Microbatches stream through: at step t, stage s processes
microbatch (t - s); activations hop stage->stage+1 with ppermute.  The
bubble is (n_stages - 1) / (n_micro + n_stages - 1).

Scope: forward pipeline (inference / activation streaming).  For training
at scale we shard the layer stack (FSDP) instead; the PP path is provided
as the parallelism feature for depth-dominated serving topologies and is
exercised by tests on a 4-device subprocess mesh and by a dry-run config.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_forward"]


def pipeline_forward(
    layer_fn,
    stacked_params,
    x: jax.Array,  # (n_micro, micro_batch, ...) microbatched input
    *,
    mesh,
    axis: str = "pod",
    n_layers: int,
):
    """Run ``layer_fn(params_i, x) -> x`` over n_layers split across the
    ``axis`` mesh dimension, GPipe schedule.

    stacked_params: pytree with leading n_layers axis.
    Returns (n_micro, micro_batch, ...) output.
    """
    n_stages = mesh.shape[axis]
    assert n_layers % n_stages == 0
    per_stage = n_layers // n_stages
    n_micro = x.shape[0]

    def stage_body(params_stage, x_local):
        """Runs on one device of `axis`; params_stage (per_stage, ...)."""
        # shard_map keeps the sharded leading axis as size-1 locally
        params_stage = jax.tree.map(lambda p: p[0], params_stage)
        stage = jax.lax.axis_index(axis)
        n_steps = n_micro + n_stages - 1

        def apply_stage(h):
            def body(h, p_i):
                return layer_fn(p_i, h), None

            h, _ = jax.lax.scan(body, h, params_stage)
            return h

        buf = jnp.zeros_like(x_local)  # (n_micro, mb, ...) output slots
        carry = jnp.zeros_like(x_local[0])  # current activation

        def step(t, state):
            buf, carry = state
            # stage 0 ingests microbatch t; others use what arrived
            mb_in = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(t, 0, n_micro - 1), keepdims=False
            )
            h = jnp.where(stage == 0, mb_in, carry)
            active = (t >= stage) & (t - stage < n_micro)
            out = apply_stage(h)
            out = jnp.where(active, out, h)
            # last stage banks its finished microbatch
            buf = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda b: jax.lax.dynamic_update_index_in_dim(
                    b, out, jnp.clip(t - stage, 0, n_micro - 1), 0
                ),
                lambda b: b,
                buf,
            )
            # hop to next stage (ring; last->first carries garbage, unused)
            nxt = jax.lax.ppermute(
                out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return (buf, nxt)

        buf, _ = jax.lax.fori_loop(0, n_steps, step, (buf, carry))
        # only the last stage's buf is real -> broadcast via masked psum
        buf = jax.lax.psum(
            jnp.where(stage == n_stages - 1, buf, jnp.zeros_like(buf)), axis
        )
        return buf

    # params: stage s gets layers [s*per_stage, (s+1)*per_stage)
    def reshape_params(p):
        return p.reshape((n_stages, per_stage) + p.shape[1:])

    stacked = jax.tree.map(reshape_params, stacked_params)
    fn = jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(P(axis), P()),  # params split by stage; x replicated
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked, x)
