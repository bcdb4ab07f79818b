#!/usr/bin/env python3
"""The control of `correct`, on the chip at a cell's own size: the plain
reference of the configuration's family put in the program's place and
computed in the nearest precision below the configuration's bfloat16 (the
family's `modes`: for mistral int8 and fp8 weights; with --modes
kv-int8,kv-fp8 an int8 or fp8 cache of keys and values), compared with the
float32 reference exactly as a run compares the program. Its numbers have
to come out far ABOVE the limits in the cell's file; PERF.md records the
smallest of them beside the largest a sound run gave.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--modes int8,fp8,kv-int8,kv-fp8] [--samples 4]

The benchmark's own runs never run this. The same comparison at a size a
test can hold is benchmark/tests/test_reference.py."""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark import checks, common, traffic, weights  # noqa: E402


def log_softmax(logits):
    logits = np.asarray(logits, np.float64)
    m = logits.max(-1, keepdims=True)
    return logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))


def serve_control(cell, seed, n_samples, modes):
    """Prompts of the cell's own traffic (both prefill paths' lengths),
    continued by tokens from the seed; the control 'serves' its own greedy
    token and log-probability at every continued position."""
    spec = cell["config"]
    params = weights.make_weights(spec, seed)
    requests = traffic.requests(cell["traffic"], seed, cell["rate_rps"], 40,
                                spec["vocab_size"])
    rng = np.random.default_rng((seed, 3))
    picked = rng.choice(len(requests), n_samples, replace=False)
    per_mode = {m: [] for m in modes}
    for i in picked:
        r = requests[int(i)]
        output = rng.integers(3, spec["vocab_size"], r["max_tokens"])
        exact = checks.reference_logits(params, spec, r["prompt_ids"], output)
        for mode in modes:
            low = checks.reference_logits(params, spec, r["prompt_ids"],
                                          output, mode)
            tokens = low.argmax(-1)
            lp = log_softmax(low)[np.arange(len(tokens)), tokens]
            per_mode[mode].append(checks.serve_numbers(exact, tokens, lp))
    return {m: checks.reduce_serve(v) for m, v in per_mode.items()}


def train_control(cell, seed, modes):
    import jax.numpy as jnp

    spec = cell["config"]
    reference = common.family(spec).nll_and_norm_grads
    params = weights.make_weights(spec, seed)
    row = traffic.packed_rows(cell["traffic"], seed, 1, spec["vocab_size"])[0]
    tokens, targets = jnp.asarray(row[:-1]), jnp.asarray(row[1:])
    exact = reference(params, tokens, targets, spec)
    out = {}
    for mode in modes:
        low = reference(params, tokens, targets, spec, mode)
        out[mode] = checks.train_numbers(*low, *exact)
        out[mode]["step_loss_err"] = abs(float(jnp.mean(low[0]))
                                         - float(jnp.mean(exact[0])))
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--samples", type=int, default=4)
    parser.add_argument("--modes", default="int8,fp8")
    args = parser.parse_args()
    modes = args.modes.split(",")
    cell = common.load_cell(args.workload)
    unknown = set(modes) - set(common.family(cell["config"]).modes)
    if unknown:
        parser.error(f"the family's reference has no mode {sorted(unknown)}")
    common.require_tpu(cell["chips"])
    common.enable_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        if cell["kind"] == "train":
            numbers = train_control(cell, seed, modes)
        else:
            numbers = serve_control(cell, seed, args.samples, modes)
        for mode, n in numbers.items():
            common.say(control=mode, workload=cell["name"], seed=seed, **n,
                       limits=cell["check"]["limits"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
