"""Serving CLI over the ``KVCachePolicy`` registry.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --smoke --max-batch 4 --requests 8 \
        --prompt-len 64 --new-tokens 32 \
        [--policy {bf16,int4-srft,int8-per-token,...}] \
        [--backend {gather,blockwise,kernel}] \
        [--temperature T] [--top-k K] [--chunk N] \
        [--http] [--port P] [--stats-json PATH] \
        [--calibrate] [--ckpt-dir DIR]

The serving analogue of launch/train.py: builds the arch (optionally
smoke-reduced), loads params from a checkpoint or initializes them,
optionally calibrates per-channel lambda from a short prompt stream (the
paper's ~2 s one-forward-pass recipe, §7.3), then serves requests
through the continuous-batching engine (launch/batch_engine.py): up to
``--max-batch`` requests share one ragged slot cache, every decode
chunk is one donated-buffer ``lax.scan`` dispatch, finished rows are
masked (never re-traced) and their slots are immediately refilled.

Two front-ends over the same engine:

* the default **closed-loop queue** -- a seeded mixed-prompt-length
  workload (launch/server/trace.py, the same generator the load
  harness replays) streamed to stdout, reporting aggregate tok/s and
  the policy-API compression/footprint block;
* ``--http`` -- the **async serving front-end** (DESIGN.md §12): the
  threaded prefill/decode/detokenize pipeline behind a stdlib
  HTTP/SSE server (``POST /v1/completions`` with ``"stream": true``,
  ``/healthz``, ``/metrics``).  SIGINT drains live streams, retires
  every slot, and prints the final stats block before exiting; a
  second SIGINT cancels instead of draining.

Both paths print the same policy-API compression report through one
shared helper (``_cache_report``), and ``--stats-json`` writes the
machine-readable twin of that block (plus server metrics when
serving) so harnesses assert on JSON instead of parsing stdout.

``--paged`` swaps the dense slot cache for the paged KV pool
(DESIGN.md §10); ``--prefill-chunk`` enables stall-free chunked
admission (DESIGN.md §11).  Families with recurrent state
(ssm/hybrid/audio) have no ragged slot semantics yet and are served
single-stream through launch/engine.py.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import signal
import threading
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.checkpoint.manager import CheckpointManager
from repro.core import calibrate as C
from repro.core.cache_api import AttendBackend, available_policies
from repro.core.transforms import Rotation
from repro.data import DataIterator, SyntheticCorpus
from repro.launch.batch_engine import BatchEngine
from repro.launch.engine import Engine, Sampler
from repro.launch.server import (
    CompletionServer,
    ServingPipeline,
    TraceRecorder,
)
from repro.launch.server.stats import cache_report_data
from repro.launch.server.trace import make_requests
from repro.launch.train import smoke_config
from repro.models import build_model
from repro.models.lm import Rotations


def calibrate_lambdas(model, params, tokens, rots: Rotations) -> Rotations:
    """Static per-channel lambda from one forward pass (paper §7.1)."""
    k_act, v_act = model.collect_kv(params, tokens)
    d = k_act.shape[-1]
    L = k_act.shape[0]

    def fit(stacked: Rotation, act) -> Rotation:
        act = act.reshape(L, -1, d)
        lams = []
        for i in range(L):
            rot_i = jax.tree.map(lambda a: a[i], stacked)
            lams.append(C.static_lambda(rot_i, act[i]))
        return Rotation(stacked.matrix, jnp.stack(lams), stacked.signs,
                        stacked.kind)

    return Rotations(k=fit(rots.k, k_act), v=fit(rots.v, v_act))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="slot-cache capacity: max requests decoding "
                         "together in one dispatch")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of queued requests (mixed prompt "
                         "lengths) to serve")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode tokens per scheduler quantum (one "
                         "fused dispatch each)")
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="longest prompt; the queue mixes this with "
                         "shorter ones (ragged batching)")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--run-len", type=int, default=1,
                    help="consecutive same-length prompts in the "
                         "workload (runs > 1 let bucketed admission "
                         "pack them into one batched prefill)")
    ap.add_argument("--policy", default=None,
                    help=f"cache policy name (default: config; "
                         f"registered: {', '.join(available_policies())})")
    ap.add_argument("--backend", default="gather",
                    choices=[b.value for b in AttendBackend],
                    help="attention read path for decode")
    ap.add_argument("--no-quant", action="store_true",
                    help="shorthand for --policy bf16")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV pool (block "
                         "allocator + page tables + COW prefix sharing; "
                         "DESIGN.md §10)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per physical page (int4: must be a "
                         "multiple of the flush window W)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical pages in the pool (default: the dense "
                         "slot footprint; smaller values oversubscribe "
                         "and exercise LRU preemption)")
    ap.add_argument("--offload-bytes", type=int, default=None,
                    help="host-RAM budget (bytes) for the prefix-page "
                         "offload tier (DESIGN.md §14): pages backing "
                         "registered prefixes are spilled here at "
                         "free time and restored as a memcpy on the "
                         "next hit instead of re-prefilling "
                         "(requires --paged + --prefill-chunk)")
    ap.add_argument("--offload-dir", default=None,
                    help="optional disk spill directory behind the "
                         "host tier: RAM-evicted prefix pages land "
                         "here and promote back on a hit")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked admission prefill (DESIGN.md §11): "
                         "split each prompt into N-token chunks "
                         "interleaved with decode, so long arrivals "
                         "never stall live streams (default: monolithic "
                         "prefill; must be a multiple of the policy "
                         "window and, with --paged, of --page-size)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="prompt tokens admitted per scheduler quantum "
                         "(default: one chunk) -- the prefill-throughput "
                         "vs decode-latency knob: higher admits faster, "
                         "lower bounds the per-quantum stall")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="self-speculative decoding (DESIGN.md §13): "
                         "each decode pass drafts K-1 tokens by prompt "
                         "lookup, verifies all K in one dispatch and "
                         "keeps the exact-match prefix -- greedy only, "
                         "output bit-identical to plain decode (int4: "
                         "K must be <= the flush window W)")
    ap.add_argument("--mesh", default=None,
                    help="shard serving over N devices ('auto' = all "
                         "visible): KV pools/slot caches split by KV "
                         "head over a 'model' mesh axis, params and "
                         "scheduler state replicated -- token streams "
                         "stay bit-identical to single-device "
                         "(DESIGN.md §16).  Heads not divisible by N "
                         "degrade to replication, never an error")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k highest logits")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP/SSE through the threaded "
                         "pipeline (DESIGN.md §12) instead of the "
                         "closed-loop stdout queue")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="HTTP port (0 = ephemeral, printed at boot)")
    ap.add_argument("--admit-queue", type=int, default=64,
                    help="bounded intake depth; a full queue returns "
                         "HTTP 429 (backpressure)")
    ap.add_argument("--s-max", type=int, default=None,
                    help="slot capacity in tokens (default: prompt-len "
                         "+ new-tokens, window-aligned)")
    ap.add_argument("--stats-json", default=None,
                    help="write the cache/pool report (and, with "
                         "--http, server metrics) as JSON to this path")
    ap.add_argument("--trace-out", default=None,
                    help="write the full trace-recorder ring as Chrome "
                         "trace-event JSON here at exit (DESIGN.md §15; "
                         "loads in Perfetto / chrome://tracing)")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="trace ring-buffer capacity in events "
                         "(drop-oldest; bounds recorder memory)")
    ap.add_argument("--no-trace", action="store_true",
                    help="disable the trace recorder entirely (it is "
                         "on by default: measured overhead is <=1% ITL)")
    ap.add_argument("--flight-window", type=float, default=30.0,
                    help="SIGUSR1 flight-recorder dump covers the last "
                         "N seconds of the ring (post-hoc stall "
                         "diagnosis on a live server)")
    ap.add_argument("--calibrate", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    return ap


REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing
    is set here.  Otherwise the cache lives at one fixed path inside
    the checkout (``.jax_cache``): the path is part of the cache key, so
    a path that moved between runs would never hit.  Returns the
    directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Built(NamedTuple):
    """What ``build_engine`` made from the CLI arguments."""

    cfg: Any
    model: Any
    params: Any
    policy: Any
    rots: Any
    mesh: Any
    engine: Optional[BatchEngine]  # None: family served single-stream


def build_engine(args: argparse.Namespace) -> Built:
    """Config, params, cache policy, optional lambda calibration, mesh
    and the continuous-batching ``BatchEngine``: everything a serving
    entry point needs before its first request, from the arguments of
    ``build_parser``.  Families with recurrent state come back with
    ``engine=None`` (served single-stream by ``main``)."""
    backend = AttendBackend.parse(args.backend)
    if args.mesh is not None and backend == AttendBackend.KERNEL:
        raise SystemExit(
            "error: --backend kernel is single-device (Pallas) and cannot "
            "serve a --mesh; use --backend blockwise")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    model = build_model(cfg)
    if not cfg.kv_applicable:
        print(f"[note] {cfg.name} has no attention KV cache "
              f"(family={cfg.family}); running its recurrent-state path")

    # one compiled program instead of one dispatch per op (full-width
    # models draw billions of numbers)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        from repro.optim.adam import adam_init

        ckpt = CheckpointManager(args.ckpt_dir)
        last = ckpt.latest_step()
        if last is not None:
            (params, _opt), _ = ckpt.restore(
                last, (params, adam_init(params))
            )
            print(f"[load] checkpoint step {last}")

    policy_name = "bf16" if args.no_quant else args.policy
    policy = model.cache_policy(policy_name) if cfg.kv_applicable else None

    rots = None
    if args.calibrate and policy is not None \
            and hasattr(policy, "rotation"):
        if cfg.family not in ("dense", "moe", "vlm"):
            # collect_kv (the calibration forward pass) only exists for
            # pure-attention families
            print(f"[calibrate] skipped: family={cfg.family} has no "
                  f"KV-collection pass")
        else:
            it = DataIterator(SyntheticCorpus(args.seed + 1),
                              batch_per_shard=4, seq_len=args.prompt_len)
            calib = jnp.asarray(it.next()["tokens"])
            rots = model.init_rotations(jax.random.PRNGKey(7))
            t0 = time.time()
            rots = calibrate_lambdas(model, params, calib, rots)
            print(f"[calibrate] per-channel lambda in "
                  f"{time.time()-t0:.1f}s")

    mesh = _build_mesh(args.mesh)
    if not (cfg.kv_applicable and cfg.family in ("dense", "moe", "vlm")):
        return Built(cfg, model, params, policy, rots, mesh, None)

    window = getattr(policy, "window", 1) if policy is not None else 1
    s_max = args.s_max
    if s_max is None:
        s_max = args.prompt_len + args.new_tokens + window
        if args.spec_k:
            # verify passes transiently append spec_k tokens past the
            # last kept position (BatchEngine._validate enforces this)
            s_max += args.spec_k
        s_max += (-s_max) % max(window, 1)
    trace = TraceRecorder(capacity=args.trace_buffer,
                          enabled=not args.no_trace)
    engine = BatchEngine(
        model, params, capacity=args.max_batch, s_max=s_max,
        policy=policy, backend=backend,
        sampler=Sampler(temperature=args.temperature, top_k=args.top_k),
        chunk=args.chunk, rots=rots, key=jax.random.PRNGKey(7),
        paged=args.paged, page_size=args.page_size, n_pages=args.pool_pages,
        prefill_chunk=args.prefill_chunk,
        prefill_budget=args.prefill_budget,
        offload_bytes=args.offload_bytes, offload_dir=args.offload_dir,
        spec_k=args.spec_k, trace=trace, mesh=mesh,
    )
    return Built(cfg, model, params, policy, rots, mesh, engine)


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, model, params, policy, rots, mesh, engine = build_engine(args)
    if engine is None:
        it = DataIterator(SyntheticCorpus(args.seed + 1),
                          batch_per_shard=max(args.requests, 1),
                          seq_len=args.prompt_len)
        prompt = jnp.asarray(it.next()["tokens"])
        return _serve_single_stream(
            cfg, model, params, prompt, policy,
            AttendBackend.parse(args.backend),
            Sampler(temperature=args.temperature, top_k=args.top_k), args,
            jax.random.PRNGKey(args.seed + 2), rots, mesh=mesh)

    _install_flight_recorder(engine.trace, args)
    pname = policy.name if policy is not None else "-"
    offload = (f", host offload {args.offload_bytes / 2**20:.0f} MiB"
               + (f" (+disk {args.offload_dir})" if args.offload_dir else "")
               if args.offload_bytes else "")
    layout = (f"paged pool: {engine.n_pages - 1} pages x "
              f"{engine.page_size} tok, COW prefix sharing{offload}"
              if args.paged else "ragged slot cache")
    admission = (f"chunked prefill: {args.prefill_chunk} tok/chunk, "
                 f"{engine.prefill_budget} tok/quantum"
                 if args.prefill_chunk else "monolithic prefill")
    mode = "http/sse pipeline" if args.http else "closed-loop queue"
    spec = (f" spec-k={args.spec_k} (self-speculative, bit-identical)"
            if args.spec_k else "")
    if mesh is not None:
        mode += (f"; mesh-sharded x{mesh.shape['model']} "
                 f"(KV by head, bit-identical)")
    print(f"[serve] arch={cfg.name} policy={pname} "
          f"backend={engine.backend.value} max-batch={args.max_batch} "
          f"new={args.new_tokens} chunk={args.chunk}{spec} "
          f"({mode}; continuous batching: {layout}, {admission}, "
          f"donated scan chunks)")

    if args.http:
        return _serve_http(cfg, engine, policy, args)
    return _serve_queue(engine, policy, args)


def _build_mesh(arg):
    """--mesh N | auto -> a (1, N) ('data','model') device mesh.

    The serving mesh only ever shards over 'model' (KV heads); 'data'
    exists so the same partitioning rules the training tools use apply
    unchanged.  N=1 (or a single-device host) means no mesh at all --
    the engines take the exact single-device code path.
    """
    if arg is None:
        return None
    devs = jax.devices()
    n = len(devs) if arg == "auto" else int(arg)
    if n <= 1:
        return None
    if n > len(devs):
        raise SystemExit(
            f"error: --mesh {n} asks for more devices than the "
            f"{len(devs)} visible (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"to simulate a mesh on CPU)"
        )
    from jax.sharding import Mesh

    return Mesh(np.array(devs[:n]).reshape(1, n), ("data", "model"))


def _install_flight_recorder(trace: TraceRecorder, args) -> None:
    """SIGUSR1 -> dump the last ``--flight-window`` seconds of the
    trace ring to disk (DESIGN.md §15): when a production stall is
    noticed after the fact, the evidence is still in the buffer.  The
    dump runs on its own thread -- the signal handler must not block
    the interrupted serving thread on file IO."""
    if not hasattr(signal, "SIGUSR1"):  # not on this platform
        return
    seq = itertools.count(1)

    def _dump() -> None:
        base = args.trace_out or "trace.json"
        root, ext = os.path.splitext(base)
        path = f"{root}.flight-{next(seq)}{ext or '.json'}"
        n = trace.write(path, last_s=args.flight_window)
        print(f"[trace] flight dump: {n} events "
              f"(last {args.flight_window:g}s) -> {path}", flush=True)

    def _handler(signum, frame):
        threading.Thread(target=_dump, daemon=True).start()

    signal.signal(signal.SIGUSR1, _handler)


def _write_trace_out(trace: TraceRecorder, args) -> None:
    if not args.trace_out:
        return
    n = trace.write(args.trace_out)
    print(f"  [trace] wrote {n} events ({trace.dropped} dropped) "
          f"-> {args.trace_out}")


def _serve_queue(engine: BatchEngine, policy, args) -> None:
    """The closed-loop stdout path: a seeded mixed-length workload
    (launch/server/trace.py -- the load harness replays the same one)
    streamed chunk by chunk.  KeyboardInterrupt drains cleanly: live
    requests are cancelled through ``cancel_all`` (slots retired,
    pages freed) and the final stats block still prints."""
    requests = make_requests(args.requests, prompt_len=args.prompt_len,
                             new_tokens=args.new_tokens, seed=args.seed,
                             run_len=args.run_len)
    for r in requests:
        engine.submit(r)
    t0 = time.time()
    n_tok = 0
    done = []
    timings = {}
    interrupted = False
    try:
        while engine.has_work:
            events, completions = engine.step()
            for rid, toks in events:  # streaming, chunk granularity
                n_tok += len(toks)
            for comp in completions:
                done.append(comp)
                _print_completion(comp)
                t = engine.trace.req_timing(comp.rid)
                if t is not None:
                    timings[str(comp.rid)] = t
    except KeyboardInterrupt:
        interrupted = True
        for comp in engine.cancel_all():
            done.append(comp)
            _print_completion(comp)
    t_total = time.time() - t0

    note = "interrupted; drained" if interrupted else "served"
    print(f"  {note} {len(done)} requests, {n_tok} tokens in "
          f"{t_total:.2f}s -> {n_tok / max(t_total, 1e-9):.1f} tok/s "
          f"aggregate ({_device_label()}; incl. one-time compile)")
    if args.prefill_chunk:
        print(f"  admission: {engine.n_prefill_chunks} prefill chunks, "
              f"{engine.n_reused_tokens} prompt tokens skipped via "
              f"token-level prefix reuse")
    if args.spec_k:
        rate = engine.n_accepted / max(engine.n_drafted, 1)
        print(f"  speculative: {engine.n_accepted}/{engine.n_drafted} "
              f"drafted tokens accepted ({100 * rate:.0f}%; spec-k="
              f"{args.spec_k}, output bit-identical to plain decode)")
    data = _cache_report(policy, engine.cache.get("attn"), engine=engine)
    payload = {
        "mode": "queue", "interrupted": interrupted,
        "requests_done": len(done), "tokens": n_tok,
        "aggregate_tok_s": n_tok / max(t_total, 1e-9),
        "cache": data,
    }
    if timings:
        payload["timings"] = timings
    _write_stats_json(args.stats_json, payload)
    _write_trace_out(engine.trace, args)


def _serve_http(cfg, engine: BatchEngine, policy, args) -> None:
    """The async front-end (DESIGN.md §12): threaded pipeline + SSE
    server.  First SIGINT stops accepting and DRAINS live streams
    before exiting (slots retired, pages freed, final stats printed);
    a second SIGINT cancels the drain and closes streams with
    ``finish_reason="cancelled"``."""
    pipeline = ServingPipeline(engine, admit_queue=args.admit_queue,
                               trace=engine.trace)
    pipeline.start()
    server = CompletionServer(pipeline, host=args.host, port=args.port,
                              vocab_size=cfg.vocab_size)
    print(f"[serve] listening on {server.url}  "
          f"(POST /v1/completions, GET /healthz, GET /metrics)")

    n_int = 0

    def _sigint(signum, frame):
        nonlocal n_int
        n_int += 1
        # serve_forever must be unblocked from another thread
        threading.Thread(target=server.shutdown, daemon=True).start()

    signal.signal(signal.SIGINT, _sigint)
    try:
        server.serve_forever()
    finally:
        cancel = n_int > 1
        print(f"[serve] {'cancelling' if cancel else 'draining'} "
              f"live streams ...")
        drained = pipeline.shutdown(cancel=cancel)
        snap = pipeline.metrics.snapshot()
        print(f"  {'drained' if drained else 'DRAIN TIMED OUT'}: "
              f"{snap['requests_completed']} completed, "
              f"{snap['requests_cancelled']} cancelled, "
              f"{snap['requests_rejected']} rejected (429), "
              f"{snap['tokens_streamed']} tokens streamed")
        ttft, itl = snap["ttft_s"], snap["itl_s"]
        if ttft["count"]:
            print(f"  ttft p50={ttft['p50']*1e3:.0f}ms "
                  f"p99={ttft['p99']*1e3:.0f}ms   "
                  f"itl p50={itl['p50']*1e3:.1f}ms "
                  f"p99={itl['p99']*1e3:.1f}ms")
        data = _cache_report(policy, engine.cache.get("attn"),
                             engine=engine)
        _write_stats_json(args.stats_json, {
            "mode": "http", "drained": drained, "server": snap,
            "queues": pipeline.queue_depths(), "cache": data,
        })
        _write_trace_out(engine.trace, args)


def _device_label() -> str:
    """The device that ran, for the timing lines: ``tpu TPU v5 lite``."""
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind}"


def _print_completion(comp) -> None:
    text = "".join(chr(c) if 32 <= c < 127 else "?"
                   for c in comp.tokens[:24].tolist())
    print(f"  [done] rid={comp.rid} prompt={comp.prompt_len} "
          f"+{len(comp.tokens)} tok ({comp.finish_reason}) "
          f"{text!r}")


def _write_stats_json(path, payload) -> None:
    if not path:
        return
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"  [stats] wrote {path}")


def _cache_report(policy, state, *, engine=None, indent="  ") -> dict:
    """One compression/footprint report for EVERY serving path (the
    batched engine, the HTTP pipeline and the single-stream fallback
    share it, so the paths can never drift apart in what they
    account).  Prints the human block and returns the machine-readable
    dict (``launch/server/stats.py:cache_report_data`` -- what
    ``--stats-json`` writes)."""
    data = cache_report_data(policy, state, engine)
    if not data["kv_applicable"]:
        print(f"{indent}(no attention KV cache: recurrent-state family)")
        return data
    is_paged = data["layout"] == "paged pool"
    extra = "residual+paging metadata" if is_paged else "transient state"
    print(f"{indent}{data['layout']} persistent KV: "
          f"{data['persistent_bytes']/1e3:.1f} KB "
          f"({data['compression_ratio']:.2f}x vs bf16, policy API; "
          f"{data['total_bytes']/1e3:.1f} KB with {extra})")
    stats = data.get("pool")
    if stats:
        print(f"{indent}pool: {stats['pages_used']}/{stats['n_pages']} "
              f"pages used ({100*stats['utilization']:.0f}%, peak "
              f"{stats['peak_pages']}), {stats['pages_per_request']:.1f} "
              f"pages/request, {stats['shared_pages']} COW-shared, "
              f"{stats['preemptions']} preemptions")
        print(f"{indent}pool bytes: {stats['used_page_bytes']/1e3:.1f} KB "
              f"live of {stats['pool_bytes']/1e3:.1f} KB pool "
              f"(dense slot equivalent {stats['dense_equiv_bytes']/1e3:.1f}"
              f" KB)")
        hb = stats["host_bytes"]
        mirrors = hb["refcount_mirror"] + hb["page_table_mirror"]
        print(f"{indent}host bytes: {hb['total']/1e3:.1f} KB "
              f"(mirrors {mirrors/1e3:.1f} KB, "
              f"prefix index {hb['prefix_index']/1e3:.1f} KB, "
              f"offload store {hb['offload_store']/1e3:.1f} KB)")
        off = stats["offload"]
        if off["enabled"]:
            st = off["store"]
            print(f"{indent}offload tier (DESIGN.md §14): "
                  f"{off['spilled_pages']} pages spilled, "
                  f"{off['restored_pages']} restored "
                  f"({off['restored_tokens']} tokens); hits "
                  f"device={off['hits_device']} host={off['hits_host']} "
                  f"miss={off['misses']}; store {st['ram_bytes']/1e3:.1f} "
                  f"KB RAM + {st['disk_bytes']/1e3:.1f} KB disk "
                  f"of {st['capacity_bytes']/1e3:.1f} KB")
    return data


def _serve_single_stream(cfg, model, params, prompt, policy, backend,
                         sampler, args, key, rots=None, mesh=None):
    """Recurrent-state families: fused single-stream engine (no ragged
    slot semantics for ssm/hybrid caches yet)."""
    if getattr(args, "spec_k", None):
        raise SystemExit(
            f"error: --spec-k requires the continuous-batching engine, "
            f"but family={cfg.family} is served single-stream: recurrent "
            f"state (ssm/hybrid/audio) has no truncate_rows rollback "
            f"path, so a rejected draft could not be rewound.  Drop "
            f"--spec-k or serve a pure-attention arch (dense/moe/vlm)."
        )
    if getattr(args, "http", False):
        print(f"[note] --http needs a pure-attention family "
              f"(got {cfg.family}); serving the closed-loop path")
    if getattr(args, "paged", False):
        print(f"[note] --paged needs a pure-attention family "
              f"(got {cfg.family}); serving dense single-stream")
    if getattr(args, "prefill_chunk", None):
        print(f"[note] --prefill-chunk needs the continuous-batching "
              f"engine (family={cfg.family} is served single-stream); "
              f"running one monolithic prefill")
    window = getattr(policy, "window", 1) if policy is not None else 1
    s_max = args.prompt_len + args.new_tokens + window
    s_max += (-s_max) % max(window, 1)
    batch = min(args.max_batch, prompt.shape[0])
    prompt = prompt[:batch]
    cache = model.init_cache(batch, s_max, policy=policy, rots=rots,
                             key=jax.random.PRNGKey(7))
    engine = Engine(model, backend=backend, sampler=sampler, mesh=mesh)
    if mesh is not None:
        params = engine.shard_params(params)
        cache = engine.shard_cache(cache)

    t0 = time.time()
    logits, cache = engine.prefill(params, prompt, cache)
    logits = jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    key, sub = jax.random.split(key)
    tok = engine.sampler.sample(logits[:, -1], sub)[:, None]
    n_steps = args.new_tokens - 1
    t0 = time.time()
    rest, cache = engine.decode(params, tok, cache, n_steps, key=key)
    rest = jax.block_until_ready(rest)
    t_decode = time.time() - t0
    gen = np.concatenate([np.asarray(tok), np.asarray(rest)], axis=1)

    pname = policy.name if policy is not None else "-"
    ms_tok = t_decode * 1e3 / max(n_steps, 1)
    print(f"[serve] arch={cfg.name} policy={pname} "
          f"backend={backend.value} batch={batch} "
          f"prompt={args.prompt_len} new={args.new_tokens} "
          f"(fused scan decode, donated cache; single-stream family)")
    print(f"  prefill: {t_prefill*1e3:.0f} ms "
          f"({batch * args.prompt_len / t_prefill:.0f} prompt tok/s)")
    print(f"  decode:  {ms_tok:.1f} ms/tok   "
          f"{batch * n_steps / max(t_decode, 1e-9):.1f} tok/s "
          f"decode-only ({_device_label()}; incl. one-time compile)")
    data = _cache_report(policy, cache.get("attn"))
    _write_stats_json(getattr(args, "stats_json", None), {
        "mode": "single-stream", "cache": data,
        "decode_ms_per_tok": ms_tok,
    })
    sample = "".join(
        chr(c) if 32 <= c < 127 else "?" for c in gen[0].tolist()
    )
    print(f"  sample continuation (byte-decoded): {sample!r}")


if __name__ == "__main__":
    main()
