#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX (stdlib HTTP
and threads only), so it shares no interpreter lock with the engine.

It reads one JSON object from standard input,
    {"url": ..., "requests": [{"due_s", "prompt_ids", "max_tokens",
     "temperature"}], "drain_cap_s": ..., "window_s": ...},
waits for the line `go` (so that both sides agree on the instant 0), sends
each request at its due time whether or not earlier ones have finished
(open loop) to the proxy's streaming route, and reads the server-sent
events, stamping each token's arrival on its own clock. After the last due
time it waits up to `drain_cap_s` for open requests; those still open count
as failed. It writes one JSON object to standard output: per request
`sent_late_s` (sent minus due), `first_s` (first token minus DUE),
`last_s`, `tokens`, `token_s` (each token's arrival after the instant 0),
`ok`, `error`."""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse


def one_request(url, req, t0, out, i):
    parsed = urlparse(url)
    rec = {"due_s": req["due_s"], "ok": False, "tokens": 0, "error": None,
           "first_s": None, "last_s": None, "sent_late_s": None,
           "token_s": []}
    out[i] = rec
    body = json.dumps({"prompt_ids": req["prompt_ids"],
                       "max_tokens": req["max_tokens"],
                       "temperature": req["temperature"]})
    conn = http.client.HTTPConnection(parsed.hostname, parsed.port, timeout=600)
    try:
        rec["sent_late_s"] = time.perf_counter() - t0 - req["due_s"]
        conn.request("POST", parsed.path, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"http {resp.status}: {resp.read()[:200]!r}"
            return
        done = False
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            data = line[5:].strip()
            if data == b"[DONE]":
                done = True
                break
            now = time.perf_counter() - t0
            value = json.loads(data)
            if isinstance(value, dict) and "error" in value:
                rec["error"] = str(value["error"])[:200]
                return
            rec["tokens"] += 1
            rec["token_s"].append(now)
            if rec["first_s"] is None:
                rec["first_s"] = now - req["due_s"]
            rec["last_s"] = now - req["due_s"]
        rec["ok"] = done and rec["tokens"] == req["max_tokens"]
        if not rec["ok"] and rec["error"] is None:
            rec["error"] = f"{rec['tokens']} tokens of {req['max_tokens']}"
    except Exception as e:  # noqa: BLE001 — recorded as this request's failure
        rec["error"] = repr(e)[:200]
    finally:
        conn.close()


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    requests = sorted(plan["requests"], key=lambda r: r["due_s"])
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1
    t0 = time.perf_counter()
    out = [None] * len(requests)
    threads = []
    for i, req in enumerate(requests):
        delay = req["due_s"] - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=one_request, daemon=True,
                             args=(plan["url"], req, t0, out, i))
        t.start()
        threads.append(t)
    deadline = t0 + max(plan["window_s"], requests[-1]["due_s"]) \
        + plan["drain_cap_s"]
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    records = []
    for rec in out:
        rec = dict(rec)  # a thread still open keeps writing its own copy
        if not rec["ok"] and rec["error"] is None:
            rec["error"] = "still open at the drain cap"
        records.append(rec)
    print(json.dumps({"records": records,
                      "elapsed_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
