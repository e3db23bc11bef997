"""The load generator: a jax-free child process.  It must never import jax
(a second process that touches the chip fails or hangs), so it imports only
the standard library and ``benchmark.lib.traffic``.

    python -m benchmark.lib.client --url http://127.0.0.1:PORT --traffic FILE
        --seed N --seconds S --vocab V --t0 MONOTONIC --out samples.json

Clocks: ``time.monotonic()`` everywhere; on Linux it is one system-wide
clock, so the parent's and the child's stamps compare directly.  ``--t0`` is
the instant the ramp starts; the measured window is
``[t0 + ramp_s, t0 + ramp_s + seconds]``.

Open loop: request ``i`` is *due* at ``t0 + due_s``; a scheduler thread
sleeps until then and hands it to a thread of its own.  Every latency is
taken from the due instant, and how late each send ran is recorded, so that
a starved generator is not read as a fast server.  Requests due after the
window's end are not sent.  Closed loop: ``clients`` threads each send the
next request of the plan when their last one completes, until the window
ends; what is still in flight then is abandoned (sockets closed).

One sample per request: due, sent, the arrival stamp of every streamed
token (tokens of one SSE event share a stamp), the token ids and the
log-probability the engine gave each, status, the engine's own TTFT from
the ``X-MLT-TTFT-S`` header, and whether the stream ended early.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional
from urllib.parse import urlparse

from benchmark.lib import traffic as traffic_mod

ABANDONED = "abandoned at the window's end"
CLIENT_START_GAP_S = 0.005   # closed loop: clients start in plan order


def stream_request(host: str, port: int, prompt: List[int], n_out: int,
                   sampling: Dict, timeout: float,
                   stop: Optional[threading.Event] = None,
                   out: Optional[Dict] = None) -> Dict:
    """One ``"stream": true`` PUT /api.  Fills ``out`` (the request's
    sample) in place, so a request cut off at the window's end keeps the
    tokens it had received."""
    body = {"prompts": [" ".join(map(str, prompt))],
            "tokens_to_generate": int(n_out), "stream": True, **sampling}
    out = {} if out is None else out
    out.update({"status": None, "token_t": [], "tokens": [], "logprobs": [],
                "error": None,
                "engine_ttft_s": None, "done_t": None, "sent_t": None})
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        out["sent_t"] = time.monotonic()
        conn.request("PUT", "/api", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200:
            out["error"] = resp.read(300).decode(errors="replace")
            return out
        ttft = resp.getheader("X-MLT-TTFT-S")
        out["engine_ttft_s"] = float(ttft) if ttft else None
        event = None
        while True:
            if stop is not None and stop.is_set():
                out["error"] = ABANDONED
                return out
            line = resp.fp.readline()
            if not line:
                out["error"] = "stream ended without a terminal event"
                return out
            now = time.monotonic()
            line = line.strip()
            if line.startswith(b"event:"):
                event = line[6:].strip()
            elif line.startswith(b"data:"):
                if event == b"token":
                    frame = json.loads(line[5:])
                    out["token_t"].extend([now] * len(frame["tokens"]))
                    out["tokens"].extend(frame["tokens"])
                    out["logprobs"].extend(frame["logprobs"])
                elif event == b"done":
                    out["done_t"] = now
                    return out
                elif event == b"error":
                    out["error"] = line[5:300].decode(errors="replace")
                    return out
    except (OSError, http.client.HTTPException, ValueError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
        return out
    finally:
        conn.close()


def run_open(url, plan, mix, t0, seconds, timeout) -> List[Dict]:
    host, port = urlparse(url).hostname, urlparse(url).port
    end = t0 + float(mix.get("ramp_s", 0.0)) + seconds
    samples: List[Dict] = []
    lock = threading.Lock()
    threads = []

    def one(req, due):
        s = stream_request(host, port, req["prompt"], req["n_out"],
                           mix.get("sampling", {}), timeout)
        s.update(id=req["id"], due_t=due, n_prompt=len(req["prompt"]),
                 n_out=req["n_out"], prefix=req["prefix"])
        with lock:
            samples.append(s)

    for req in plan["requests"]:
        due = t0 + req["due_s"]
        if due > end:
            break
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(req, due), daemon=True)
        th.start()
        threads.append(th)
    deadline = end + float(mix.get("drain_s", 90.0))
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    with lock:
        done = {s["id"] for s in samples}
        out = list(samples)
    for req in plan["requests"]:
        if t0 + req["due_s"] <= end and req["id"] not in done:
            out.append({"id": req["id"], "due_t": t0 + req["due_s"],
                        "sent_t": None, "status": None, "token_t": [],
                        "tokens": [], "logprobs": [],
                        "error": "no answer within the drain limit",
                        "n_prompt": len(req["prompt"]), "n_out": req["n_out"],
                        "prefix": req["prefix"], "engine_ttft_s": None,
                        "done_t": None})
    return out


def run_closed(url, plan, mix, t0, seconds, timeout) -> List[Dict]:
    host, port = urlparse(url).hostname, urlparse(url).port
    end = t0 + float(mix.get("ramp_s", 0.0)) + seconds
    stop = threading.Event()
    samples: List[Dict] = []
    lock = threading.Lock()
    cursor = iter(plan["requests"])

    def client(delay: float):
        time.sleep(delay)   # a fixed order of first arrivals, not a race
        while not stop.is_set():
            with lock:
                req = next(cursor, None)
                if req is None:
                    return
                s = {"id": req["id"], "n_prompt": len(req["prompt"]),
                     "n_out": req["n_out"], "prefix": req["prefix"]}
                samples.append(s)
            stream_request(host, port, req["prompt"], req["n_out"],
                           mix.get("sampling", {}), timeout, stop, s)

    wait = t0 - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    for i in range(int(mix["clients"])):
        threading.Thread(target=client, args=(i * CLIENT_START_GAP_S,),
                         daemon=True).start()
    time.sleep(max(0.0, end - time.monotonic()))
    stop.set()
    time.sleep(0.5)   # streams leave at their next token; waiters are cut
    with lock:
        out = []
        for s in samples:   # a snapshot: threads still blocked are abandoned
            c = dict(s, **{k: list(s.get(k, []))
                           for k in ("token_t", "tokens", "logprobs")})
            if c.get("done_t") is None and not c.get("error"):
                c["error"] = ABANDONED
            c["due_t"] = c.get("sent_t")
            out.append(c)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--url", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--t0", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=300.0)
    args = ap.parse_args(argv)
    if "jax" in sys.modules:
        raise RuntimeError("the client must stay off jax")
    mix = traffic_mod.load(args.traffic)
    plan = traffic_mod.request_plan(mix, args.seed, args.seconds, args.vocab)
    runner = {"open_loop": run_open, "closed_loop": run_closed}[mix["kind"]]
    samples = runner(args.url, plan, mix, args.t0, args.seconds, args.timeout)
    with open(args.out, "w") as f:
        json.dump({"t0": args.t0, "ramp_s": float(mix.get("ramp_s", 0.0)),
                   "seconds": args.seconds, "samples": samples}, f)
        f.flush()
    # threads still blocked on a socket are daemons: leave without them
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
