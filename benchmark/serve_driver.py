"""Cells of kind `serve`: `serve.run(LLMServer)` on one replica, requests
over HTTP to the proxy's streaming route as token ids, greedy, in an open
loop at the cell's fixed `rate_rps`. The load comes from a child process
(benchmark/loadgen.py) that never imports JAX; every latency is on its
clock, from the instant a request was DUE."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional

import numpy as np

from . import checks, common, traffic, weights

APP = "bench"


# -- the program under test --------------------------------------------------


def start_server(cell: Dict[str, Any], seed: int):
    """-> (handle, base url). Returns once the replica answers: its first
    call waits for __init__ (weights, decode-span compiles) and raises what
    __init__ raised (copied from chip_smoke.py, PR 23)."""
    import ray_tpu
    from ray_tpu import serve

    spec = cell["config"]

    def seeded_weights():
        return (weights.make_weights(spec, seed),
                common.family(spec).model_config(spec))

    ray_tpu.init()
    app = serve.LLMServer.bind(params_fn=seeded_weights,
                               engine_config=dict(cell["engine"]),
                               tensor_parallel=cell["tensor_parallel"])
    handle = serve.run(app, name=APP)
    handle.options("stats").remote({}).result(timeout=900.0)
    return handle, f"http://127.0.0.1:{serve.http_port()}/{APP}"


def stop_server() -> None:
    import ray_tpu
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def post(url: str, payload: Dict[str, Any], timeout: float = 600.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())["result"]


def post_all(url: str, payloads: List[Dict[str, Any]]) -> List[Any]:
    """Concurrent non-streaming POSTs; raises the first failure."""
    out: List[Any] = [None] * len(payloads)

    def ask(i):
        try:
            out[i] = post(url, payloads[i])
        except Exception as e:  # noqa: BLE001 — re-raised below
            out[i] = e

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(payloads))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in out:
        if isinstance(r, Exception):
            raise common.BenchFailure(f"request failed: {r!r}") from r
    return out


# -- warm-up -----------------------------------------------------------------


def shape_class(prompt_len: int, max_tokens: int, ecfg) -> tuple:
    """Which compiled shapes a request touches, from the engine's OWN sizes
    (read from its EngineConfig, never copied): a prompt above
    `prefill_chunk` goes through the one chunk program; a shorter one
    through the bucket that holds it, and its cache is written by a program
    whose shape is the count of whole pages it fills."""
    if ecfg.chunked_prefill and prompt_len > ecfg.prefill_chunk:
        return ("chunked",)
    bucket = next((b for b in ecfg.prefill_buckets if b >= prompt_len),
                  ecfg.prefill_buckets[-1])
    pages = -(-(prompt_len + max_tokens) // ecfg.page_size)
    return ("bucket", bucket, min(pages, bucket // ecfg.page_size))


def warm_set(requests: List[Dict[str, Any]], ecfg, vocab: int,
             seed: int) -> List[Dict[str, Any]]:
    """One request for each shape class of the run's traffic, with token
    ids of its own (no prefix shared with the window's requests) and the
    fewest output tokens that keep it in its class."""
    classes: Dict[tuple, Dict[str, Any]] = {}
    for r in requests:
        n = len(r["prompt_ids"])
        key = shape_class(n, r["max_tokens"], ecfg)
        if key not in classes:
            fewest = next(m for m in range(2, r["max_tokens"] + 1)
                          if shape_class(n, m, ecfg) == key)
            classes[key] = {"prompt_len": n, "max_tokens": fewest,
                            "temperature": r["temperature"]}
    rng = np.random.default_rng((seed, 1))
    return [{"prompt_ids": rng.integers(3, vocab, w["prompt_len"]).tolist(),
             "max_tokens": w["max_tokens"], "temperature": w["temperature"]}
            for _, w in sorted(classes.items())]


# -- one open-loop window ----------------------------------------------------


class Poller(threading.Thread):
    """Samples the engine's own stats (traced runs and sweeps only)."""

    def __init__(self, handle, period_s: float = 0.05):
        super().__init__(daemon=True)
        self.handle, self.period_s = handle, period_s
        self.samples: List[Dict[str, Any]] = []
        self._stop_event = threading.Event()

    def run(self) -> None:
        while not self._stop_event.wait(self.period_s):
            stats = self.handle.options("stats").remote({}).result(timeout=60)
            self.samples.append({"t": time.perf_counter(),
                                 "active": stats["active"]})

    def stop(self) -> None:
        self._stop_event.set()
        self.join()


def offer(url: str, requests: List[Dict[str, Any]], seconds: float,
          drain_cap_s: float, during=None) -> Dict[str, Any]:
    """Run the load generator over `requests`; `during(t0)` runs in this
    thread while the window is open. -> its records and the instant 0 on
    this process's clock."""
    child = subprocess.Popen(
        [sys.executable, os.path.join(common.HERE, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        plan = {"url": url + "/stream", "requests": requests,
                "drain_cap_s": drain_cap_s, "window_s": seconds}
        child.stdin.write(json.dumps(plan) + "\n")
        child.stdin.flush()
        if child.stdout.readline().strip() != "ready":
            raise common.BenchFailure("the load generator did not start")
        t0 = time.perf_counter()
        child.stdin.write("go\n")
        child.stdin.flush()
        if during is not None:
            during(t0)
        line = child.stdout.readline()
        if not line:
            raise common.BenchFailure("the load generator gave no result")
        out = json.loads(line)
        out["t0"] = t0
        return out
    finally:
        child.stdin.close()
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait()


def summarize(records: List[Dict[str, Any]], requests: List[Dict[str, Any]],
              seconds: float, miss_s: float) -> Dict[str, Any]:
    """The end-to-end numbers of one window. A failed request counts as
    missing: its latencies are `miss_s` (window + drain cap)."""
    ok = [r for r in records if r["ok"]]
    ttft = [r["first_s"] if r["ok"] else miss_s for r in records]
    tpot = [(r["last_s"] - r["first_s"]) / (r["tokens"] - 1) if r["ok"]
            else miss_s for r in records if not r["ok"] or r["tokens"] > 1]
    # over all gaps between tokens of all requests (a failed request
    # counts as one gap of `miss_s`): thousands of gaps, where a p95 over
    # requests is one request's number
    streamed = [r for r in ok if r["tokens"] > 1]
    gaps_s = sum(r["last_s"] - r["first_s"] for r in streamed) \
        + miss_s * (len(records) - len(ok))
    gaps = sum(r["tokens"] - 1 for r in streamed) + len(records) - len(ok)
    in_window = sum(1 for r in ok for t in r["token_s"] if t < seconds)
    late = [r["sent_late_s"] for r in records if r["sent_late_s"] is not None]
    return {
        "attempted": len(records), "failed": len(records) - len(ok),
        "serve_out_tokens_per_s": in_window / seconds,
        "ttft_p50_ms": 1000 * common.percentile(ttft, 50),
        "ttft_p95_ms": 1000 * common.percentile(ttft, 95),
        "tpot_p50_ms": 1000 * common.percentile(tpot, 50),
        "tpot_p95_ms": 1000 * common.percentile(tpot, 95),
        "tpot_mean_ms": 1000 * gaps_s / gaps,
        "sent_late_median_ms": 1000 * common.percentile(late, 50),
        "sent_late_max_ms": 1000 * max(late),
        "errors": sorted({r["error"] for r in records if r["error"]})[:5],
    }


def awaiting_first(records, seconds: float, parts: int = 4) -> List[float]:
    """Mean number of requests due but without a first token yet, in each
    of `parts` equal parts of the window: the backlog, on the client's
    clock. It grows from part to part where the rate is past the knee."""
    grid = np.linspace(0, seconds, 40 * parts, endpoint=False)
    due = np.array([r["due_s"] for r in records])
    first = np.array([r["due_s"] + r["first_s"] if r["first_s"] is not None
                      else np.inf for r in records])
    waiting = [(np.sum((due <= t) & (first > t))) for t in grid]
    return [float(np.mean(chunk)) for chunk in np.split(np.array(waiting), parts)]


# -- the cell ----------------------------------------------------------------


def choose_sample(requests, ecfg, n: int, seed: int) -> List[Dict[str, Any]]:
    """A seeded sample of the window's requests, shared evenly between the
    prefill paths that the traffic takes (chunked above `prefill_chunk`,
    bucketed at or under it): n // 2 from each of two, n from a single one."""
    rng = np.random.default_rng((seed, 2))
    paths: Dict[bool, List[int]] = {}
    for i, r in enumerate(requests):
        paths.setdefault(len(r["prompt_ids"]) > ecfg.prefill_chunk, []).append(i)
    picked: List[int] = []
    for chunked in sorted(paths, reverse=True):
        take = min(len(paths[chunked]), n // len(paths))
        picked += list(rng.choice(paths[chunked], take, replace=False))
    return [requests[i] for i in picked]


def run(cell: Dict[str, Any], args, device: Dict[str, Any], watch,
        t_start: float, tracer) -> Optional[Dict[str, Any]]:
    from ray_tpu.serve.engine import EngineConfig

    spec, mix = cell["config"], cell["traffic"]
    ecfg = EngineConfig(**cell["engine"])
    sweep = [float(x) for x in args.sweep.split(",")] if args.sweep else []
    ladder = sweep or [cell["rate_rps"]]
    plans = [traffic.requests(mix, args.seed + i, rate, args.seconds,
                              spec["vocab_size"])
             for i, rate in enumerate(ladder)]
    t_server = time.perf_counter()
    handle, url = start_server(cell, args.seed)
    common.say(replica_ready_s=time.perf_counter() - t_server,
               compiles=watch.snapshot())
    poller = None
    try:
        warm = warm_set([r for p in plans for r in p], ecfg,
                        spec["vocab_size"], args.seed)
        t_warm = time.perf_counter()
        # through the streaming route, as the window's requests go; the
        # replay's non-streaming route runs the same engine programs
        offer(url, [dict(w, due_s=0.0) for w in warm], 1.0, 300.0)
        post_all(url, warm[:1])
        common.say(warmed=len(warm), seconds=time.perf_counter() - t_warm,
                   compiles=watch.snapshot())
        if sweep:
            return run_sweep(cell, args, url, ladder, plans)
        requests = plans[0]
        before, compiles_before = common.counters(), watch.snapshot()
        traced: Dict[str, float] = {}
        if tracer:
            poller = Poller(handle)

        def during(t0: float) -> None:
            if not tracer:
                return
            poller.start()
            time.sleep(1.0)
            tracer.start()
            traced["t0"] = time.perf_counter()
            time.sleep(tracer.seconds)
            traced["t1"] = time.perf_counter()
            tracer.stop()

        setup_s = time.perf_counter() - t_start
        result = offer(url, requests, args.seconds, cell["drain_cap_s"], during)
        if poller:
            poller.stop()
        after = common.counters()
        compiles = watch.snapshot()["compiles"] - compiles_before["compiles"]
        records = result["records"]
        numbers = summarize(records, requests, args.seconds,
                            args.seconds + cell["drain_cap_s"])
        common.say(window=numbers,
                   awaiting_first_by_quarter=awaiting_first(records, args.seconds))
        sample = choose_sample(requests, ecfg, cell["check"]["sample"], args.seed)
        # the replay bounds its outputs: a 512-token answer at today's
        # 105 ms a token would add a minute to every run
        replies = post_all(url, [
            {"prompt_ids": r["prompt_ids"],
             "max_tokens": min(r["max_tokens"], cell["check"]["max_tokens"]),
             "temperature": r["temperature"]} for r in sample])
        compiles_replay = watch.snapshot()["compiles"] \
            - compiles_before["compiles"] - compiles
        peak = common.memory_peak_bytes(cell["chips"])
    finally:
        if poller and poller.is_alive():
            poller.stop()
        stop_server()
    common.say(device_bytes_in_use_after_shutdown=common.wait_for_free_memory(
        cell["chips"]))
    no_compiles = compiles == 0
    common.say(check="compiles_in_window", value=compiles, limit=0,
               ok=no_compiles, in_replay=compiles_replay)
    # how late the generator ran is reported, not judged: latencies count
    # from the DUE instant, so a late send is already charged to the system
    common.say(generator_sent_late_max_ms=numbers["sent_late_max_ms"],
               median_ms=numbers["sent_late_median_ms"])
    ok = checks.serve(cell, args.seed, [
        {"prompt_ids": r["prompt_ids"], "token_ids": reply["token_ids"],
         "logprobs": reply["logprobs"]} for r, reply in zip(sample, replies)])
    run_info = {"records": records, "requests": [
        {"due_s": r["due_s"], "prompt_len": len(r["prompt_ids"]),
         "max_tokens": r["max_tokens"]} for r in requests],
        "t0": result["t0"], "seconds": args.seconds, **numbers}
    common.keep_run(cell["name"], run_info)
    if tracer:
        run_info.update(traced_s=traced["t1"] - traced["t0"],
                        traced_from_s=traced["t0"] - result["t0"],
                        traced_to_s=traced["t1"] - result["t0"],
                        polls=poller.samples,
                        max_batch_size=ecfg.max_batch_size)
    return {
        "correct": bool(no_compiles and ok
                        and numbers["failed"] < numbers["attempted"]),
        "attempted": numbers["attempted"], "failed": numbers["failed"],
        "end_to_end": {**numbers, "setup_s": setup_s},
        "memory_peak_bytes": peak, "run": run_info,
        "counters": (before, after),
    }


def run_sweep(cell, args, url, ladder, plans) -> None:
    """A ladder of rates in one process and one set-up; prints one row per
    rate. No result line follows a sweep."""
    for rate, requests in zip(ladder, plans):
        result = offer(url, requests, args.seconds, cell["drain_cap_s"])
        numbers = summarize(result["records"], requests, args.seconds,
                            args.seconds + cell["drain_cap_s"])
        numbers.pop("errors")
        common.say(sweep_rate_rps=rate, **numbers,
                   awaiting_first_by_quarter=awaiting_first(
                       result["records"], args.seconds),
                   drained_s=result["elapsed_s"])
