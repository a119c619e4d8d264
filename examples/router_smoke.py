"""Network front door smoke: HTTP/SSE server + 2-replica prefix-affine router.

The CI ``router-smoke`` job's scenario, runnable by hand:

1. launches ``python -m repro.frontend.http_server --replicas 2`` as a real
   subprocess (its own process, own engines, SIGINT-driven lifecycle);
2. replays a shared-prefix workload through the HTTP client;
3. cancels a request mid-stream over HTTP and checks it aborts server-side;
4. reads ``GET /v1/stats`` and checks the router's prefix directory took
   hits (the shared stream landed on its holder) and that every replica
   kept the one-readback-per-round zero-sync invariant;
5. sends SIGINT and checks the server drains gracefully and exits 0;
6. only then runs the same prompts on an in-process single engine and checks
   the SSE token streams were **bit-identical** to it (replicas share seed-0
   params, so routing must never change greedy tokens).

The parent touches no device until the server has exited: an accelerator
belongs to one process at a time. The child inherits ``JAX_PLATFORMS``.

    PYTHONPATH=src JAX_PLATFORMS=cpu python examples/router_smoke.py
"""
from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.frontend.client import EngineHttpClient  # noqa: E402
from repro.frontend.http_server import build_backend  # noqa: E402


def launch_server(replicas: int = 2) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env["PYTHONUNBUFFERED"] = "1"
    return subprocess.Popen(
        [sys.executable, "-m", "repro.frontend.http_server",
         "--port", "0", "--replicas", str(replicas),
         "--kv-tokens", "2048", "--max-budget", "256", "--drain-s", "20"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def wait_banner(proc: subprocess.Popen, deadline_s: float = 120.0) -> int:
    """Parse the 'listening on http://host:port' banner; returns the port."""
    t_end = time.perf_counter() + deadline_s
    while time.perf_counter() < t_end:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"server exited early: {proc.poll()}")
        sys.stdout.write(f"[server] {line}")
        m = re.search(r"listening on http://[^:]+:(\d+)", line)
        if m:
            return int(m.group(1))
    raise TimeoutError("no listening banner")


def main() -> None:
    rng = np.random.default_rng(0)
    system = rng.integers(1, 1000, 48).tolist()
    prompts = [system + rng.integers(1, 1000, 16).tolist() for _ in range(5)]

    proc = launch_server(replicas=2)
    try:
        port = wait_banner(proc)
        cli = EngineHttpClient(port=port, timeout=180.0)
        cli.wait_ready(60.0)

        # --- shared-prefix stream over SSE -----------------------------------
        # (sequential so each request's pages are committed — and in the
        # directory — before the next one routes)
        streamed = []
        for p in prompts:
            h = cli.generate(p, slo_class="interactive", max_output=5)
            streamed.append(h.result())
            assert h.finish_reason == "length", h.finish_reason

        # --- mid-stream cancel over HTTP -------------------------------------
        h = cli.generate(rng.integers(1, 1000, 64).tolist(), max_output=256)
        got = []
        for tok in h.tokens():
            got.append(tok)
            if len(got) == 1:
                assert h.cancel(), "cancel reported not-live"
        assert h.aborted, f"finish_reason={h.finish_reason}"
        assert len(got) < 256, "cancel did not stop the stream"
        print(f"cancel OK: aborted mid-stream after {len(got)} tokens")

        # --- router + invariant checks over /v1/stats ------------------------
        st = cli.stats()
        routing = st["routing"]
        assert routing["policy"] == "prefix-affine"
        hit_rate = routing["directory"]["hit_rate"]
        assert hit_rate > 0, f"directory never hit: {routing['directory']}"
        assert routing["affine_hits"] >= len(prompts) - 1, routing
        for i, rep in enumerate(st["replicas"]):
            eng = rep["engine"]
            assert eng["token_readbacks"] == eng["iterations"], \
                f"replica {i}: zero-sync broken ({eng['token_readbacks']} " \
                f"readbacks / {eng['iterations']} rounds)"
        print(f"router OK: directory hit rate {hit_rate:.2f}, "
              f"routed={routing['routed']}, one readback/round per replica")

        # --- graceful drain on SIGINT ----------------------------------------
        proc.send_signal(signal.SIGINT)
        out, _ = proc.communicate(timeout=60)
        sys.stdout.write("".join(f"[server] {l}\n"
                                 for l in out.splitlines() if l))
        assert proc.returncode == 0, f"exit code {proc.returncode}"
        assert "drained" in out, "no drain report in server output"
        print("shutdown OK: SIGINT drained and exited 0")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)

    # --- SSE parity against an in-process single engine ----------------------
    # (same prompts, same seed-0 params; built only now that the server child
    # has exited and released the device)
    ref_backend = build_backend(replicas=1, kv_tokens=2048, max_budget=256)
    reference = [ref_backend.submit(np.asarray(p, np.int32),
                                    max_output=5).result() for p in prompts]
    ref_backend.close()
    for i, (toks, ref) in enumerate(zip(streamed, reference)):
        assert toks == ref, f"prompt {i}: HTTP {toks} != in-process {ref}"
    print(f"parity OK: {len(prompts)} SSE streams bit-identical "
          f"to the in-process engine")
    print("ROUTER SMOKE PASSED")


if __name__ == "__main__":
    main()
