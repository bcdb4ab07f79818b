"""How `correct` is decided: the program's outputs against the plain
reference of the configuration's family (benchmark/families/), outside the
timed window, on the run's own seeded weights. Every number compared is
printed beside its limit; the limits are data in the cell's file
(`check.limits`), each set from the two readings PERF.md gives: the largest
that sound runs gave and the smallest that the control gave.

The numbers are chosen to be steady from seed to seed and to move with
precision: per-position quantities reduced by a root mean square, never a
mean of signed errors (which a lower precision leaves nearly unchanged)."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from . import common, weights


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Print each number beside its limit; -> all within. A number with no
    limit is information only; a limit with no number fails."""
    ok = True
    for name, value in numbers.items():
        limit = limits.get(name)
        within = limit is None or (np.isfinite(value) and value <= limit)
        common.say(check=name, value=float(value), limit=limit, ok=bool(within))
        ok = ok and within
    for name in limits:
        if name not in numbers:
            common.say(check=name, value=None, limit=limits[name], ok=False)
            ok = False
    return ok


# -- serve -------------------------------------------------------------------


def serve_numbers(ref_logits: np.ndarray, tokens: Sequence[int],
                  logprobs: Sequence[float]) -> Dict[str, np.ndarray]:
    """One request. ref_logits [n, V] float32 at the positions that
    predicted `tokens`. -> per position: the error of the served
    log-probability, and how far the served token's reference logit lies
    under the reference maximum (0 where the reference agrees with greedy)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens)
    lse = np.log(np.sum(np.exp(ref_logits - ref_logits.max(-1, keepdims=True)),
                        axis=-1)) + ref_logits.max(-1)
    picked = ref_logits[np.arange(len(tokens)), tokens]
    return {"logprob_err": np.abs(np.asarray(logprobs, np.float64)
                                  - (picked - lse)),
            "greedy_gap": ref_logits.max(-1) - picked}


def reduce_serve(per_request: List[Dict[str, np.ndarray]]) -> Dict[str, float]:
    err = np.concatenate([r["logprob_err"] for r in per_request])
    gap = np.concatenate([r["greedy_gap"] for r in per_request])
    return {"logprob_rms_err": float(np.sqrt(np.mean(err ** 2))),
            # medians: where experts are routed, a rounding error now and
            # then flips a token's expert and moves its logits by whole
            # units; the rms then counts flips, the median still rounding
            "logprob_p50_err": float(np.median(err)),
            "logprob_p75_err": float(np.percentile(err, 75)),
            "greedy_gap_p90": float(np.percentile(gap, 90)),
            "logprob_max_err": float(err.max()),
            "greedy_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "greedy_gap_max": float(gap.max()),
            "positions": float(len(err))}


def reference_logits(params, spec, prompt: Sequence[int],
                     output: Sequence[int], mode=None) -> np.ndarray:
    """Prompt + output in one forward pass of the reference; -> logits at
    the positions that predict each output token."""
    import jax.numpy as jnp

    family = common.family(spec)
    seq = list(prompt) + list(output)
    padded = np.zeros((-(-len(seq) // family.PAD_TO) * family.PAD_TO,),
                      np.int32)
    padded[: len(seq)] = seq  # right padding is invisible to causal attention
    at = len(prompt) - 1 + np.arange(len(output))
    return np.asarray(family.logits_at(
        params, jnp.asarray(padded), jnp.asarray(at), spec, mode))


def serve(cell: Dict[str, Any], seed: int,
          samples: List[Dict[str, Any]]) -> bool:
    """samples: {"prompt_ids", "token_ids", "logprobs"} as the route
    returned them. The engine must be gone from the device by now."""
    spec = cell["config"]
    params = weights.make_weights(spec, seed)
    per_request = []
    for s in samples:
        if len(s["token_ids"]) != len(s["logprobs"]) or not s["token_ids"]:
            common.say(check="sample_shape", ok=False,
                       tokens=len(s["token_ids"]), logprobs=len(s["logprobs"]))
            return False
        logits = reference_logits(params, spec, s["prompt_ids"], s["token_ids"])
        per_request.append(serve_numbers(logits, s["token_ids"], s["logprobs"]))
    return judge(reduce_serve(per_request), cell["check"]["limits"])


# -- train -------------------------------------------------------------------


def train_numbers(nll, grads, ref_nll, ref_grads) -> Dict[str, float]:
    nll, ref_nll = np.asarray(nll, np.float64), np.asarray(ref_nll, np.float64)
    g, rg = np.asarray(grads, np.float64), np.asarray(ref_grads, np.float64)
    return {"nll_rms_err": float(np.sqrt(np.mean((nll - ref_nll) ** 2))),
            "grad_rel_err": float(np.linalg.norm(g - rg) / np.linalg.norm(rg))}


def train(cell: Dict[str, Any], seed: int, first_batch: np.ndarray,
          first_metrics: Dict[str, float]) -> bool:
    """first_batch [rows, T + 1]; first_metrics: what the step reported for
    it on the seed's initial weights. The train state must be gone."""
    import jax.numpy as jnp

    spec = cell["config"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    params = weights.make_weights(spec, seed)
    rows = first_batch[: cell["check"]["rows"]]
    numbers, ref_means = [], []
    for row in rows:
        tokens, targets = jnp.asarray(row[:-1]), jnp.asarray(row[1:])
        nll, g = family.program_probe(cfg, params, tokens, targets)
        ref_nll, ref_g = family.nll_and_norm_grads(params, tokens, targets,
                                                   spec)
        numbers.append(train_numbers(nll, g, ref_nll, ref_g))
        ref_means.append(float(jnp.mean(ref_nll)))
    out = {k: max(n[k] for n in numbers) for k in numbers[0]}
    if len(rows) == len(first_batch):
        # the step's own report: cross-entropy of the whole first batch
        out["step_loss_err"] = abs(first_metrics["ce_loss"]
                                   - float(np.mean(ref_means)))
    return judge(out, cell["check"]["limits"])
