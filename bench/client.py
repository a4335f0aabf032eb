"""Clients that drive the served path over HTTP/SSE, and what they saw.

Each request is one ``POST /v1/completions`` with ``"stream": true``,
read event by event; every token is stamped with the host clock at the
moment its event was read.  A closed loop runs one thread per client.
"""
from __future__ import annotations

import dataclasses
import http.client
import json
import threading
import time
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Record:
    """What one client saw of one request (host clock, seconds)."""

    idx: int
    prompt: np.ndarray
    max_tokens: int
    sent: Optional[float] = None
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finish: Optional[str] = None
    error: Optional[str] = None

    @property
    def first_token(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


def stream_one(host: str, port: int, rec: Record, timeout: float) -> None:
    """Send ``rec``'s request and read its SSE stream to the end."""
    body = json.dumps({"prompt": rec.prompt.tolist(),
                       "max_tokens": rec.max_tokens, "stream": True})
    rec.sent = time.perf_counter()
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec.error = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return
        while True:
            line = resp.readline()
            if not line:
                break
            t = time.perf_counter()
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            payload = line[6:]
            if payload == b"[DONE]":
                break
            ev = json.loads(payload)
            toks = ev.get("tokens") or []
            rec.tokens.extend(toks)
            rec.token_times.extend([t] * len(toks))
            if ev.get("finish_reason"):
                rec.finish = ev["finish_reason"]
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


class ClosedLoop:
    """One thread per client of ``plan``; client ``c`` sends its request
    of wave 0, 1, 2, ... one after another until ``stop`` is set.
    ``first_wave`` holds each client's first request's record once it
    exists."""

    def __init__(self, host, port, plan, timeout: float):
        self.host, self.port, self.timeout = host, port, timeout
        self.plan = plan
        self.stop = threading.Event()
        self.records: list[Record] = []
        self.first_wave: list[Record] = []
        self._lock = threading.Lock()
        self._threads = [threading.Thread(target=self._client, args=(c,),
                                          daemon=True, name=f"client-{c}")
                         for c in range(plan.clients)]

    def _client(self, c: int) -> None:
        j = 0
        while not self.stop.is_set():
            req = self.plan.wave(j)[c]
            rec = Record(req.idx, req.prompt, req.max_tokens)
            with self._lock:
                self.records.append(rec)
                if j == 0:
                    self.first_wave.append(rec)
            stream_one(self.host, self.port, rec, self.timeout)
            j += 1

    def start(self) -> "ClosedLoop":
        for t in self._threads:
            t.start()
        return self

    def all_decoding(self) -> bool:
        with self._lock:
            wave = list(self.first_wave)
        return (len(wave) == len(self._threads)
                and all(r.first_token is not None or r.error
                        for r in wave))

    def join(self, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(deadline - time.monotonic(), 0.0))
        return not any(t.is_alive() for t in self._threads)
