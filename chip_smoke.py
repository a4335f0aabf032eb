"""Chip smoke: serve full-width internlm2-1.8b on one TPU through the
normal entry points, and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # (1, 4) 'model' mesh vs one device

One process, no child that touches JAX.  The model is built at its
published widths (24 layers, d_model 2048, 16/8 heads, head_dim 128,
d_ff 8192, vocab 92544) with random weights from ``--seed``; the engine
is the one ``repro.launch.serve`` builds (``build_engine``): paged
int4-srft KV pool, Pallas decode kernel, chunked prefill, 8 slots.

Phases (each an importable function; any failure raises and the script
exits non-zero):

* ``serve_phase``: ``ServingPipeline`` + ``CompletionServer`` on an
  ephemeral localhost port, 8 requests (prompts 1024-2048 tokens from
  ``launch/server/trace.make_requests``, 64 new tokens each) sent by
  client threads, half streamed over SSE and half not; every request
  must finish with its full token count, then the pipeline drains.
* ``read_path_phase``: on a live paged cache, one decode step through
  the kernel read path and one through the GATHER read path over the
  same cache bytes.  Logits must be finite and agree within
  ``READ_PATH_TOL``, and on the chip the compiled kernel step must hold
  the Mosaic custom call (``tpu_custom_call``).
* ``sharded_phase`` (``--chips 4`` only): the same requests on a (1, 4)
  'model' mesh (KV heads split 2 per chip, backend blockwise) against
  the same run on one device in this process.

The timing lines are smoke readings, not benchmarks.  The last line of
stdout is one JSON object naming the device; it is printed only when
every phase passed.  Without a TPU the script exits non-zero first.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.cache_api import AttendBackend  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.server import CompletionServer, ServingPipeline  # noqa: E402
from repro.launch.server.trace import make_requests  # noqa: E402

# Kernel vs GATHER logits over the same cache bytes, as a share of the
# largest |logit| of the step.  The two read paths dequantize the same
# int4 codes but reduce in different orders (the kernel's online softmax
# over 16-token pages vs one softmax over the gathered prefix), and on
# the TPU XLA runs the gather path's fp32 einsums at default (bf16-pass)
# precision while the kernel's dots are fp32.  Each layer's attention
# output is then rounded to bf16 (8 mantissa bits), so one read-path
# ulp flip per layer can move the next layer's inputs by ~2^-8 relative;
# through 24 layers that stays a few percent of the logit scale.
READ_PATH_TOL = 0.05
# The sharded engine's logits against one device's: the same rounding
# argument (collectives reorder the KV-head reductions), same bound.
SHARDED_TOL = 0.05


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def require(ok, msg: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise SmokeFailure(msg)


def smoke_args(*, smoke: bool = False, seed: int = 0,
               backend: str = "kernel", mesh: str | None = None
               ) -> argparse.Namespace:
    """The serving CLI's arguments for the smoke engine.  ``smoke``
    shrinks the model and the prompts to CPU size (the tier-1 test)."""
    if smoke:
        sizes = ["--smoke", "--prompt-len", "64", "--new-tokens", "8",
                 "--prefill-chunk", "16", "--chunk", "4"]
    else:
        sizes = ["--prompt-len", "2048", "--new-tokens", "64",
                 "--prefill-chunk", "256", "--chunk", "8"]
    argv = ["--arch", "internlm2-1.8b", "--policy", "int4-srft",
            "--backend", backend, "--paged", "--page-size", "16",
            "--max-batch", "8", "--requests", "8", "--seed", str(seed),
            *sizes]
    if mesh is not None:
        argv += ["--mesh", mesh]
    return serve.build_parser().parse_args(argv)


def smoke_requests(args):
    return make_requests(args.requests, prompt_len=args.prompt_len,
                         new_tokens=args.new_tokens, seed=args.seed)


def _complete(url: str, req, stream: bool) -> tuple[list[int], str]:
    body = json.dumps({"prompt": np.asarray(req.prompt).tolist(),
                       "max_tokens": req.max_new_tokens,
                       "stream": stream}).encode()
    http = urllib.request.Request(
        url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    toks: list[int] = []
    reason = None
    with urllib.request.urlopen(http, timeout=900) as resp:
        if not stream:
            out = json.loads(resp.read())
            return out["tokens"], out["finish_reason"]
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            ev = json.loads(payload)
            toks.extend(ev["tokens"])
            reason = ev["finish_reason"] or reason
    return toks, reason


def serve_phase(engine, vocab_size: int, requests) -> dict:
    """Serve ``requests`` over HTTP in this process: even-indexed ones
    streamed (SSE), odd ones not.  Returns the token streams by request
    index and what the engine's trace recorded."""
    pipeline = ServingPipeline(engine).start()
    server = CompletionServer(pipeline, host="127.0.0.1", port=0,
                              vocab_size=vocab_size)
    srv = threading.Thread(target=server.serve_forever, daemon=True)
    srv.start()
    results: dict[int, tuple[list[int], str]] = {}
    errors: list[Exception] = []

    def client(i, req):
        try:
            results[i] = _complete(server.url, req, stream=i % 2 == 0)
        except Exception as e:  # re-raised on the main thread
            errors.append(e)

    t0 = time.perf_counter()
    clients = [threading.Thread(target=client, args=(i, r))
               for i, r in enumerate(requests)]
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    wall = time.perf_counter() - t0
    drained = pipeline.shutdown()
    server.shutdown()
    srv.join(timeout=10)
    if errors:
        raise errors[0]
    require(drained, "pipeline did not drain")
    for i, req in enumerate(requests):
        toks, reason = results[i]
        require(reason in ("length", "eos"),
                f"request {i} finished with {reason!r}")
        if reason == "length":
            require(len(toks) == req.max_new_tokens,
                    f"request {i}: {len(toks)} of {req.max_new_tokens} "
                    f"tokens")
    chunks: dict[int, list[float]] = {}
    for ev in engine.trace.export()["traceEvents"]:
        if ev["name"] == "decode.chunk":
            chunks.setdefault(ev["args"]["steps"], []).append(ev["dur"])
    # the first dispatch of each quantum length compiles it
    steady = chunks.get(engine.chunk, [])[1:]
    return {
        "tokens": {i: results[i][0] for i in results},
        "finish": {i: results[i][1] for i in results},
        "wall_s": wall,
        "prompt_tokens": int(sum(len(r.prompt) for r in requests)),
        "new_tokens": int(sum(len(t) for t, _ in results.values())),
        "steady_quantum_ms": (float(np.median(steady)) / 1e3
                              if steady else None),
        "n_steady_quanta": len(steady),
    }


def probe_requests(engine, args) -> list:
    """Four fresh prompts (new seed) at the serve phase's lengths, so no
    new prefill shape compiles, shortest first and with budgets up to
    ``s_max``: every row is still decoding when the last one is in."""
    reqs = make_requests(4, prompt_len=args.prompt_len,
                         new_tokens=args.new_tokens, seed=args.seed + 1)
    reqs = sorted(reqs, key=lambda r: len(r.prompt))
    return [dataclasses.replace(r, rid=10_000 + i,
                                max_new_tokens=engine.s_max - len(r.prompt))
            for i, r in enumerate(reqs)]


def fill(engine, requests) -> None:
    """Admit ``requests`` until every one of them is a live row."""
    for r in requests:
        engine.submit(r)
    while engine.pending:
        engine.step()
    require(engine.n_active == len(requests),
            f"{engine.n_active} of {len(requests)} rows live")


def step_logits(engine, backend: AttendBackend):
    """One decode step of ``engine``'s live cache through ``backend``,
    not donated, so the cache bytes stay as they are.  Returns
    ``(logits (B, V) fp32 of the active rows, compiled text)``."""
    model, active = engine.model, np.asarray(engine.active)

    def step(params, tok, cache, act):
        logits, _ = model.decode_step(params, tok, cache,
                                      kv_block=engine.kv_block,
                                      backend=backend, active=act)
        return logits[:, -1].astype(jnp.float32)

    fn = jax.jit(engine._traced(step, "decode_logits"))
    argv = (engine.params, engine.tok, engine.cache, jnp.asarray(active))
    compiled = fn.lower(*argv).compile()
    logits = np.asarray(compiled(*argv))[active]
    return logits, compiled.as_text()


def read_path_phase(engine, requests) -> dict:
    """Kernel vs GATHER decode logits over the same paged cache bytes."""
    fill(engine, requests)
    lk, text = step_logits(engine, AttendBackend.KERNEL)
    lg, _ = step_logits(engine, AttendBackend.GATHER)
    engine.cancel_all()
    require(np.isfinite(lk).all() and np.isfinite(lg).all(),
            "non-finite decode logits")
    rel = float(np.abs(lk - lg).max()) / float(np.abs(lg).max())
    require(rel <= READ_PATH_TOL,
            f"kernel vs gather logits differ by {rel:.3g} of the logit "
            f"scale (tolerance {READ_PATH_TOL})")
    return {
        "rel_max_diff": rel,
        "argmax_agree": float(np.mean(lk.argmax(-1) == lg.argmax(-1))),
        "mosaic_in_step": "tpu_custom_call" in text,
    }


def sharded_phase(seed: int, n_chips: int, *, smoke: bool = False) -> dict:
    """The smoke requests on a (1, n_chips) 'model' mesh (blockwise)
    against the same run on one device, in this process: token streams
    and one step's decode logits."""
    out = {}
    for mesh in (None, str(n_chips)):
        args = smoke_args(smoke=smoke, seed=seed, backend="blockwise",
                          mesh=mesh)
        built = serve.build_engine(args)
        reqs = smoke_requests(args)
        served = serve_phase(built.engine, built.cfg.vocab_size, reqs)
        fill(built.engine, probe_requests(built.engine, args))
        logits, _ = step_logits(built.engine, AttendBackend.BLOCKWISE)
        built.engine.cancel_all()
        out[mesh] = (served, logits)
        del built
    (one, l1), (many, ln) = out[None], out[str(n_chips)]
    mismatched = [i for i in one["tokens"]
                  if one["tokens"][i] != many["tokens"][i]]
    require(np.isfinite(ln).all(), "non-finite sharded logits")
    rel = float(np.abs(ln - l1).max()) / float(np.abs(l1).max())
    require(rel <= SHARDED_TOL,
            f"sharded vs one-device logits differ by {rel:.3g} of the "
            f"logit scale (tolerance {SHARDED_TOL})")
    return {"mismatched_requests": mismatched,
            "n_requests": len(one["tokens"]),
            "bit_equal_logits": bool(np.array_equal(l1, ln)),
            "rel_max_diff": rel,
            "quantum_ms_one": one["steady_quantum_ms"],
            "quantum_ms_mesh": many["steady_quantum_ms"]}


class _CompileClock:
    """Sums JAX's own compile-duration events (trace, lowering, backend
    compile) for the whole process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in self.EVENTS:
            self.seconds += secs


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the (1, 4) mesh phase and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}")
    if len(jax.devices()) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX "
                         f"sees {len(jax.devices())} devices")
    cache_dir = serve.enable_compile_cache()
    clock = _CompileClock()
    _say(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
         f"{cache_dir}")

    if args.chips == 4:
        res = sharded_phase(args.seed, 4)
        _say(f"sharded (1, 4) vs one device: "
             f"{len(res['mismatched_requests'])}/{res['n_requests']} "
             f"token streams differ {res['mismatched_requests']}; logits "
             f"bit-equal={res['bit_equal_logits']} max |diff| "
             f"{res['rel_max_diff']:.3g} of scale (tol {SHARDED_TOL})")
        _say(f"smoke reading, not a benchmark: steady ms/quantum one "
             f"device {res['quantum_ms_one']}, mesh "
             f"{res['quantum_ms_mesh']}; compile {clock.seconds:.1f} s")
    else:
        sargs = smoke_args(seed=args.seed)
        t0 = time.perf_counter()
        built = serve.build_engine(sargs)
        cfg, engine = built.cfg, built.engine
        n_params = sum(x.size for x in jax.tree.leaves(built.params))
        _say(f"model {cfg.name}: {cfg.n_layers} layers, d_model "
             f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
             f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
             f"{cfg.vocab_size}, {n_params / 1e9:.3f}e9 params (random, "
             f"seed {args.seed}); engine: paged int4-srft, backend "
             f"{engine.backend.value}, {engine.n_pages - 1} pages x "
             f"{engine.page_size}, prefill chunk {engine.prefill_chunk}, "
             f"max batch {engine.capacity}; built in "
             f"{time.perf_counter() - t0:.1f} s")
        reqs = smoke_requests(sargs)
        res = serve_phase(engine, cfg.vocab_size, reqs)
        _say(f"served {len(reqs)} requests over HTTP "
             f"({sum(1 for i in res['tokens'] if i % 2 == 0)} streamed): "
             f"{res['prompt_tokens']} prompt tokens, {res['new_tokens']} "
             f"new tokens, finish {sorted(set(res['finish'].values()))}")
        _say(f"smoke reading, not a benchmark: wall {res['wall_s']:.2f} s "
             f"incl. compile; compile {clock.seconds:.1f} s; steady "
             f"{res['steady_quantum_ms']} ms per {engine.chunk}-token "
             f"decode quantum (median of {res['n_steady_quanta']})")
        rp = read_path_phase(engine, probe_requests(engine, sargs))
        _say(f"kernel vs gather logits: max |diff| {rp['rel_max_diff']:.3g}"
             f" of scale (tol {READ_PATH_TOL}), argmax agree "
             f"{rp['argmax_agree']:.2f}; Mosaic call in decode step "
             f"{rp['mosaic_in_step']}")
        require(rp["mosaic_in_step"],
                "compiled decode step holds no tpu_custom_call")
    stats = dev.memory_stats() or {}
    _say(f"smoke reading: peak HBM bytes "
         f"{stats.get('peak_bytes_in_use', 'not reported')}; compile "
         f"{clock.seconds:.1f} s in all")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
