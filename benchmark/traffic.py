"""The one general traffic generator. A mix is a data file of parameters
(benchmark/traffic/<mix>.json); this reads it and makes the run's inputs
from --seed. A new mix is a new data file, never new code, so what a mix
can say is wide: classes of requests with their own prompt and output
lengths (a mixture), Poisson or bursty arrivals, prefixes shared between
requests, and sessions of several turns whose prompts grow.

Every seed gets the SAME sizes and arrival instants: the sizes are the
distributions' own quantiles (a stratified sample), and their pairing and
order are drawn once from the mix's own `schedule_seed`. --seed draws the
token ids (and the weights). A seed that changed the sizes would change the
work; even a seed that only reordered them moves a tail over some tens of
requests by which long prompts happen to arrive together (PR 24, chip:
another order of the same 29 sizes moved `ttft_p95` from 4.4 to 13 s), and
the spread between seeds would swamp the spread between two builds. So a
tail read here is the tail of ONE schedule, held still to compare builds,
not an estimate of the distribution's. Training documents are permuted by
the seed: a step's cost does not depend on where its documents end.

Keys of a `requests` mix:
  arrivals       {"process": "poisson"} or {"process": "gamma", "cv": c};
                 the cell's `rate_rps` is the rate of these arrivals:
                 requests, or sessions where the mix has `sessions`
  prompt_len, output_len
                 one class of requests; or
  classes        [{"weight", "prompt_len", "output_len"}, ...]: a mixture
  shared_prefix  absent/null, or {"count", "len", "zipf_s"}: each request
                 (each session) starts with one of `count` prefixes of
                 `len` tokens, chosen with Zipf weights 1 / rank^zipf_s
  sessions       absent/null, or {"turns": dist, "turn_gap_s": dist}: an
                 arrival opens a session; turn t is due `turn_gap_s` after
                 turn t-1 (open loop: whether or not that one has ended)
                 and its prompt is the session's whole history, earlier
                 prompts and stand-in answers of the earlier turns'
                 `max_tokens`, plus new tokens of `prompt_len`
  temperature, schedule_seed
A dist is {"dist": "lognormal", "median", "sigma", "min", "max"},
{"dist": "uniform", "min", "max"} or {"dist": "constant", "value"}."""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def quantile_values(dist: Dict[str, Any], n: int) -> np.ndarray:
    """n values at the mid-quantiles (i + 0.5) / n of the distribution."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        normal = NormalDist()
        z = np.array([normal.inv_cdf(float(q)) for q in u])
        values = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(values, dist["min"], dist["max"])
    if dist["dist"] == "uniform":
        return dist["min"] + (dist["max"] - dist["min"]) * u
    if dist["dist"] == "constant":
        return np.full(n, float(dist["value"]))
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def quantile_sizes(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The same, rounded to whole numbers (lengths, counts)."""
    return np.rint(quantile_values(dist, n)).astype(np.int64)


def shares(weights, n: int) -> List[int]:
    """n split in proportion to `weights`, by largest remainder."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def arrival_gaps(arrivals: Dict[str, Any], n: int) -> np.ndarray:
    """n inter-arrival gaps of mean 1, the mid-quantiles of the process's
    gap distribution: exponential for `poisson`, gamma of the given
    coefficient of variation for `gamma` (bursts)."""
    u = (np.arange(n) + 0.5) / n
    if arrivals["process"] == "poisson":
        gaps = -np.log1p(-u)
    elif arrivals["process"] == "gamma":
        # no inverse gamma CDF in numpy: a large fixed sample's quantiles
        shape = 1.0 / arrivals["cv"] ** 2
        sample = np.random.default_rng(0).gamma(shape, 1.0 / shape, 200_000)
        gaps = np.quantile(sample, u)
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return gaps / gaps.mean()


def _sizes(mix: Dict[str, Any], n: int, schedule) -> tuple:
    """Prompt and output lengths of n requests: each class's own quantiles,
    paired within the class by the schedule."""
    classes = mix.get("classes") or [
        {"weight": 1.0, "prompt_len": mix["prompt_len"],
         "output_len": mix["output_len"]}]
    prompt, output = [], []
    for c, count in zip(classes, shares([c["weight"] for c in classes], n)):
        prompt.append(quantile_sizes(c["prompt_len"], count))
        output.append(quantile_sizes(c["output_len"], count)[
            schedule.permutation(count)])
    return np.concatenate(prompt), np.concatenate(output)


def requests(mix: Dict[str, Any], seed: int, rate_rps: float, seconds: float,
             vocab_size: int, reserved_ids: int = 3) -> List[Dict[str, Any]]:
    """Open-loop requests due inside [0, seconds), in the order they are
    due: `due_s`, `prompt_ids`, `max_tokens`, `temperature`. rate x seconds
    arrivals; sizes and instants are the mix's, token ids the seed's."""
    if mix["kind"] != "requests":
        raise ValueError(f"mix kind {mix['kind']!r} makes no requests")
    n = max(1, int(round(rate_rps * seconds)))
    rng = np.random.default_rng(seed)
    schedule = np.random.default_rng((mix["schedule_seed"], n))
    sessions, shared = mix.get("sessions"), mix.get("shared_prefix")
    turns = (quantile_sizes(sessions["turns"], n)[schedule.permutation(n)]
             if sessions else np.ones(n, np.int64))
    total = int(turns.sum())
    prompt, output = _sizes(mix, total, schedule)
    order = schedule.permutation(total)
    gaps = arrival_gaps(mix["arrivals"], n)[schedule.permutation(n)]
    start = (np.cumsum(gaps) - gaps[0] * 0.5) * (seconds / n)
    temperature = float(mix.get("temperature", 0.0))
    if shared:
        # prefixes from a stream of their own, so that a mix without them
        # draws the same token ids as before
        prefix_rng = np.random.default_rng((seed, 4))
        prefixes = [prefix_rng.integers(reserved_ids, vocab_size,
                                        shared["len"]).tolist()
                    for _ in range(shared["count"])]
        zipf = 1.0 / np.arange(1, shared["count"] + 1) ** shared["zipf_s"]
        which = np.repeat(np.arange(shared["count"]),
                          shares(zipf, n))[schedule.permutation(n)]
    if sessions:
        turn_gap = quantile_values(sessions["turn_gap_s"], total)[
            schedule.permutation(total)]

    def fresh(count):
        return rng.integers(reserved_ids, vocab_size, int(count)).tolist()

    out, at = [], 0
    for i in range(n):
        history = list(prefixes[which[i]]) if shared else []
        due = float(start[i])
        for t in range(int(turns[i])):
            j = order[at]
            if t:
                due += float(turn_gap[at])
            at += 1
            history = history + fresh(prompt[j])
            if due < seconds:
                out.append({"due_s": due, "prompt_ids": history,
                            "max_tokens": int(output[j]),
                            "temperature": temperature})
            if t + 1 < turns[i]:
                history = history + fresh(output[j])  # the stand-in answer
    return sorted(out, key=lambda r: r["due_s"])


def packed_rows(mix: Dict[str, Any], seed: int, n_rows: int,
                vocab_size: int, reserved_ids: int = 3) -> np.ndarray:
    """[n_rows, row_tokens + 1] int32: documents of the mix's lengths, in an
    order drawn from the seed, token ids uniform from the seed, joined by
    the separator and cut into rows (a document may span two rows, as in a
    packed pretraining corpus). Column t+1 is the target of column t."""
    if mix["kind"] != "documents":
        raise ValueError(f"mix kind {mix['kind']!r} makes no documents")
    rng = np.random.default_rng(seed)
    lens = quantile_sizes(mix["doc_len"], mix["docs_in_pool"])
    width = mix["row_tokens"] + 1
    need = n_rows * width
    tokens = rng.integers(reserved_ids, vocab_size, need, dtype=np.int32)
    # separators fall where documents end; the pool of lengths repeats in a
    # fresh order until the rows are full
    ends, at = [], 0
    while at < need:
        for doc in lens[rng.permutation(len(lens))]:
            at += int(doc) + 1
            if at > need:
                break
            ends.append(at - 1)
    tokens[np.asarray(ends, np.int64)] = mix["separator_id"]
    return tokens.reshape(n_rows, width)
