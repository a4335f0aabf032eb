"""End-to-end smoke of the HTTP/SSE serving front-end (DESIGN.md §12).

Boots ``python -m repro.launch.serve --http`` as a real subprocess on
an ephemeral port and drives the full request cycle a client would:

1. wait for the boot banner, parse the listening URL;
2. stream one completion over SSE (``stream: true``) and check the
   event framing (token events, ``finish_reason``, ``data: [DONE]``);
3. fetch the same prompt unstreamed and check the token streams match
   (the SSE path is a view of the same engine stream, not a fork);
4. scrape ``/healthz`` and ``/metrics`` and check the served request
   is visible in the counters;
5. saturate the (``--admit-queue 1``) intake with a concurrent burst
   and check the 429 carries a ``Retry-After`` header plus a
   ``retry_after_s`` JSON field (ISSUE-8 backpressure contract);
6. pull ``GET /debug/trace`` after the served load and validate it
   with ``check_trace.py`` (valid Chrome-trace JSON, spans nest, every
   streamed token covered by its request span), then SIGUSR1 the
   server and validate the flight-recorder dump it writes;
7. SIGINT the server, check it drains and exits 0, and validate the
   final ``--trace-out`` file.

Everything is stdlib (urllib) -- CI's server-smoke job runs exactly
this file.  Exit status is non-zero on any failed check.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

from check_trace import check_trace, check_trace_file

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _boot(trace_out: str) -> tuple[subprocess.Popen, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--smoke", "--http",
         "--port", "0", "--max-batch", "2", "--prompt-len", "16",
         "--new-tokens", "8", "--policy", "int4-srft",
         # one waiter max: a concurrent burst must 429 (checked below)
         "--admit-queue", "1", "--trace-out", trace_out],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env,
    )
    deadline = time.monotonic() + 300
    lines = []
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise AssertionError(
                "server exited before listening:\n" + "".join(lines)
            )
        lines.append(line)
        if "listening on" in line:
            url = line.split("listening on", 1)[1].split()[0]
            return proc, url
    raise AssertionError("server never printed its listening URL")


def _post(url: str, body: dict, timeout: float = 300.0):
    req = urllib.request.Request(
        url + "/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _stream_completion(url: str, prompt, max_tokens: int) -> list[int]:
    toks: list[int] = []
    saw_done = saw_finish = False
    with _post(url, {"prompt": prompt, "max_tokens": max_tokens,
                     "stream": True}) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream"), \
            f"not SSE: {resp.headers['Content-Type']}"
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                saw_done = True
                break
            ev = json.loads(payload)
            toks.extend(ev["tokens"])
            if ev["finish_reason"] is not None:
                saw_finish = True
    assert saw_finish, "stream ended without a finish_reason event"
    assert saw_done, "stream ended without data: [DONE]"
    return toks


def main() -> None:
    tmpdir = tempfile.mkdtemp(prefix="server_smoke_trace_")
    trace_out = os.path.join(tmpdir, "trace.json")
    proc, url = _boot(trace_out)
    try:
        print(f"[server_smoke] serving at {url}")

        toks = _stream_completion(url, "hello world", 6)
        assert len(toks) == 6, f"streamed {len(toks)} tokens, wanted 6"
        print(f"[server_smoke] SSE completion: {len(toks)} tokens")

        with _post(url, {"prompt": "hello world", "max_tokens": 6,
                         "stream": False}) as resp:
            body = json.loads(resp.read())
        assert body["tokens"] == toks, (
            f"unstreamed tokens {body['tokens']} != streamed {toks}"
        )
        assert body["finish_reason"] == "length", body
        timing = body.get("timing")
        assert timing is not None, f"no timing breakdown in {body}"
        for key in ("queue_wait_s", "prefill_s", "decode_s", "detok_s",
                    "total_s"):
            assert key in timing and timing[key] >= 0, timing
        print(f"[server_smoke] unstreamed completion matches: "
              f"{body['text']!r} (total {timing['total_s']:.3f}s)")

        with urllib.request.urlopen(url + "/healthz", timeout=60) as resp:
            health = json.loads(resp.read())
        assert health["ok"] and health["slots_capacity"] == 2, health

        with urllib.request.urlopen(url + "/metrics", timeout=60) as resp:
            metrics = resp.read().decode()
        for marker in ("server_requests_completed_total 2",
                       "server_tokens_streamed_total 12",
                       "server_ttft_seconds{quantile=\"0.5\"}"):
            assert marker in metrics, (
                f"missing {marker!r} in /metrics:\n{metrics}"
            )
        print("[server_smoke] /healthz + /metrics OK")

        # backpressure: with --admit-queue 1, a concurrent burst must
        # bounce at least one request with 429 + Retry-After.  The
        # window is one engine dispatch wide, so retry the burst a few
        # times rather than trusting a single race.
        rejected = None
        deadline = time.monotonic() + 120
        while rejected is None and time.monotonic() < deadline:
            results = [None] * 6

            def _worker(i):
                try:
                    with _post(url, {"prompt": "hello world",
                                     "max_tokens": 8,
                                     "stream": False}) as r:
                        r.read()
                except urllib.error.HTTPError as e:
                    results[i] = (e.code, dict(e.headers), e.read())

            threads = [threading.Thread(target=_worker, args=(i,))
                       for i in range(len(results))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            rejected = next((r for r in results if r), None)
        assert rejected is not None, "burst never produced a 429"
        code, headers, body = rejected
        assert code == 429, f"burst rejection was {code}, wanted 429"
        retry_after = headers.get("Retry-After")
        assert retry_after is not None, (
            f"429 without Retry-After header: {headers}"
        )
        assert int(retry_after) >= 1, f"Retry-After {retry_after!r} < 1"
        payload = json.loads(body)
        assert payload["retry_after_s"] == int(retry_after), payload
        assert payload.get("retry") is True, payload
        print(f"[server_smoke] 429 backpressure: "
              f"Retry-After={retry_after}s")

        # flight recorder: /debug/trace after the served load must be
        # a valid Chrome trace with every streamed token covered by
        # its request span (check_trace.py enforces the contract)
        with urllib.request.urlopen(url + "/debug/trace",
                                    timeout=60) as resp:
            trace = json.loads(resp.read())
        problems = check_trace(trace)
        assert not problems, "\n".join(["/debug/trace invalid:"] + problems)
        names = {e["name"] for e in trace["traceEvents"]}
        for need in ("request", "decode.chunk", "decode.dispatch",
                     "decode.wait", "decode.post", "detok"):
            assert need in names, f"no {need!r} events in /debug/trace"
        # bucketed admission prefills through admit_packed; chunked
        # admission through prefill.chunk; direct submit through
        # engine.prefill -- any of the three covers the prefill stage
        prefills = {"engine.prefill", "prefill.packed", "prefill.chunk"}
        assert names & prefills, (
            f"no prefill span in /debug/trace (have {sorted(names)})"
        )
        n_live = len(trace["traceEvents"])
        with urllib.request.urlopen(url + "/debug/trace?last_s=1e9",
                                    timeout=60) as resp:
            windowed = json.loads(resp.read())
        assert not check_trace(windowed), "windowed /debug/trace invalid"
        print(f"[server_smoke] /debug/trace OK ({n_live} events)")

        if hasattr(signal, "SIGUSR1"):
            flight = os.path.join(tmpdir, "trace.flight-1.json")
            proc.send_signal(signal.SIGUSR1)
            deadline = time.monotonic() + 60
            while not os.path.exists(flight) \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
            time.sleep(0.2)  # let the dump thread finish the write
            problems = check_trace_file(flight)
            assert not problems, \
                "\n".join([f"flight dump {flight} invalid:"] + problems)
            print("[server_smoke] SIGUSR1 flight dump OK")

        proc.send_signal(signal.SIGINT)
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0, (
            f"server exited {proc.returncode}:\n{out}"
        )
        assert "drained" in out, f"no drain confirmation:\n{out}"
        print("[server_smoke] SIGINT -> drained, exit 0")

        problems = check_trace_file(trace_out)
        assert not problems, \
            "\n".join([f"--trace-out {trace_out} invalid:"] + problems)
        print("[server_smoke] final --trace-out OK")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    print("[server_smoke] PASS")


if __name__ == "__main__":
    main()
