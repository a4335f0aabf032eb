"""The system under test, built from the program's public pieces:
``build_model``, ``BatchEngine`` (with the benchmark's weights and
rotation tables), ``ServingPipeline`` and ``CompletionServer`` on an
ephemeral localhost port.  ``warm_up`` runs every shape a cell's traffic
uses through the engine once, so that nothing compiles in the window.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any

import jax
import numpy as np

from bench import model as bmodel


@dataclasses.dataclass
class System:
    cfg: Any
    model: Any
    weights: Any
    tables: dict
    engine: Any = None
    pipeline: Any = None
    server: Any = None
    server_thread: Any = None
    steps: list = dataclasses.field(default_factory=list)

    @property
    def url_parts(self) -> tuple[str, int]:
        return self.server.host, self.server.port


def s_max_for(plan, window: int) -> int:
    """Slot capacity in tokens: the longest prompt and output of the
    mix plus one flush window (what ``serve.build_engine`` sets)."""
    s = max(plan.prompt_lens) + plan.max_output + window
    return s + (-s) % window


def build(conf: dict, seed: int, plan) -> System:
    from repro.launch.batch_engine import BatchEngine
    from repro.launch.server import TraceRecorder
    from repro.models import build_model

    cfg = bmodel.model_config(conf)
    mdl = build_model(cfg)
    weights = bmodel.make_weights(mdl, seed, conf["initializer_range"])
    tables = bmodel.make_rotation_tables(cfg, seed)
    sv = conf["serving"]
    engine = BatchEngine(
        mdl, weights, capacity=sv["slots"],
        s_max=s_max_for(plan, cfg.kv_window),
        policy=conf["kv_cache"]["policy"], backend=sv["backend"],
        chunk=sv["chunk"], rots=bmodel.program_rotations(tables),
        key=bmodel.seed_key(seed, bmodel.STREAM_ENGINE),
        paged=sv["paged"], page_size=sv["page_size"],
        prefill_chunk=sv["prefill_chunk"],
        prefill_budget=sv["prefill_budget"],
        trace=TraceRecorder(capacity=1 << 20),
    )
    return System(cfg, mdl, weights, tables, engine)


def warm_up(system: System, plan, seed: int) -> int:
    """Compile and run once every program the window will drive: the
    chunked-prefill program of each prompt length, every decode quantum
    length ``n_steps`` from 1 to ``chunk``, the slot insert and reset.
    Requests go straight to the engine, one at a time, so each quantum
    length is reached exactly.  Returns the number of requests run."""
    from repro.launch.batch_engine import Request

    eng = system.engine
    rng = bmodel.seed_rng(seed, 97)
    vocab = system.cfg.vocab_size
    shortest = min(plan.prompt_lens)
    # (prompt length, decode steps after the admission token)
    jobs = [(n, eng.chunk) for n in plan.prompt_lens]
    jobs += [(shortest, k) for k in range(eng.chunk - 1, 0, -1)]
    for i, (n, steps) in enumerate(jobs):
        prompt = rng.integers(0, vocab, n, dtype=np.int64).astype(np.int32)
        eng.submit(Request(rid=-1 - i, prompt=prompt,
                           max_new_tokens=steps + 1))
        while eng.has_work:
            eng.step()
    return len(jobs)


def serve(system: System) -> None:
    """Start the pipeline and the HTTP server; record every engine step
    (time, and tokens per request) through the engine's step listeners,
    which the work counts of the per-layer metrics read."""
    from repro.launch.server import CompletionServer, ServingPipeline
    import time

    eng = system.engine

    def on_step(events, completions):
        system.steps.append((time.perf_counter(),
                             [(rid, len(t)) for rid, t in events]))

    eng.step_listeners.append(on_step)
    system.pipeline = ServingPipeline(eng, admit_queue=1024).start()
    system.server = CompletionServer(system.pipeline, host="127.0.0.1",
                                     port=0, vocab_size=system.cfg.vocab_size)
    system.server_thread = threading.Thread(
        target=system.server.serve_forever, daemon=True, name="http")
    system.server_thread.start()


def stop(system: System) -> None:
    """Cancel what is still in flight, stop every thread and drop the
    engine's device state (cache pool, staging rows)."""
    if system.pipeline is not None:
        system.pipeline.shutdown(cancel=True, timeout=30.0)
    if system.server is not None:
        system.server.shutdown()
        system.server_thread.join(timeout=10.0)
    system.engine = system.pipeline = system.server = None
    jax.clear_caches()
