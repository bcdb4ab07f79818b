#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the repo's north-star slice (Dataset -> JaxTrainer -> orbax
checkpoint -> serve.run -> HTTP completion, the path of
examples/pretrain_and_serve.py) once on one TPU v5e at the published
widths of `llama3-8b`, in ONE process that owns the chip from start to
end. Only depth is cut; the weights are random, made from a seed.

    python chip_smoke.py             # one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: fsdp=4 vs 1 device, tp=4 vs 1

Every line of standard output is one JSON object. The last line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`;
a phase that fails makes it `{"ok": false, ...}` and the exit code 1.
Before that line the script stops every process it started (the pool's
forkserver and multiprocessing's resource tracker outlive
`ray_tpu.shutdown()`) and fails if anything else is still running.
There is no CPU mode: without an accelerator the device phase fails before
any model code runs. tests/test_chip_smoke.py imports the phase and check
functions and hands them a small Plan on the CPU.
"""

from __future__ import annotations

import argparse
import ctypes
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import threading
import time
import traceback
import urllib.request
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# The one cut. llama3-8b has 32 layers; one 16 GiB v5e holds 8 of them for
# train and serve alike. memory_analysis() of the train step compiled for a
# described v5e (bf16 parameters and gradients, factored optimizer
# statistics, batch 2 x seq 2048; rehearsal of PR 23, no chip):
#   n_layers  8: arguments 5.21 GiB (aliased to outputs) + temporaries
#                6.80 GiB = 12.01 GiB -> 3.7 GiB to spare
#   n_layers 16: 4.54 B parameters; parameters + gradients alone are
#                16.9 GiB -> does not fit
# The four-chip comparison runs batch 4 (one row per fsdp shard): 13.12 GiB
# on its single device, 6.90 GiB per device under fsdp=4. The engine's
# programs at this depth (decode span, chunk prefill, bucket prefill) need
# at most 6.59 GiB.
N_LAYERS = 8
PUBLISHED_LAYERS = 32

# -- the correctness check, fixed by ISSUE 23 before the first chip run ------
# (a) train
LOSS_DROP = 0.5           # mean(last two losses) <= first - LOSS_DROP
FIRST_LOSS_TOL = 0.5      # |first loss - ln(vocab_size)| <= this
# (b) serve vs the plain reference
MARGIN = 0.25             # reference top-1 minus top-2 logit
MIN_CONFIDENT = 0.75      # share of generated positions above MARGIN
# (c) kernels
KERNEL_TOL = 2e-2         # max|out - ref| / max|ref|
# four chips
FSDP_LOSS_TOL = 2e-2      # per-step |loss(fsdp=4) - loss(1 device)|
MAX_BYTES_SPREAD = 1.5    # largest bytes_in_use / smallest, after placement
# block_until_ready must have waited: the scalar readback after it may take
# at most this share of the step (plus a millisecond of host noise)
READBACK_SHARE = 0.1
# after the runtime's shutdown, what it started may take this long to be gone
# (a pool worker polls for its parent once a second); then it is a leak
EXIT_GRACE_S = 10.0


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def emit(**obj: Any) -> None:
    print(json.dumps(obj), flush=True)


@dataclasses.dataclass(frozen=True)
class Plan:
    """What one run drives. `Plan()` is the chip run; tests build small ones."""

    model: str = "llama3-8b"
    model_overrides: Dict[str, Any] = dataclasses.field(
        default_factory=lambda: {"n_layers": N_LAYERS})
    seed: int = 0
    # train: `corpus_rows` rows of seq+1 tokens, repeated until `steps` steps
    seq: int = 2048
    batch: int = 2
    steps: int = 8
    corpus_rows: int = 4
    # bf16 masters quantize every update to at least one ulp of the weight
    # (0.4%), and adafactor's first steps are sign-like, so the whole model
    # moves coherently: on the chip every peak rate from 2e-5 to 1e-3 sent
    # the loss from 11.9 to 23..44 by step 3. At 5e-6 only the small
    # weights move and the loss falls monotonically (sweep in CHANGES.md).
    learning_rate: float = 5e-6
    warmup_steps: int = 1
    # tokens are i.i.d. Zipf(2) over a small seeded alphabet: the best
    # predictor is the unigram, which a few steps learn, and whose top-1
    # leads top-2 by 2*ln(2) logits — well clear of MARGIN
    alphabet: int = 64
    # serve: 32..1500 tokens, so prompts land on both sides of the engine's
    # prefill_chunk (bucketed prefill at or below it, chunked above)
    prompt_lens: Sequence[int] = (32, 100, 200, 256, 300, 700, 1100, 1500)
    max_tokens: int = 64
    # default EngineConfig except what the prompts force: room for
    # 1500 + 64 tokens per sequence and pages for 8 of them at once
    engine: Dict[str, Any] = dataclasses.field(
        default_factory=lambda: {"max_seq_len": 2048, "max_pages": 1032})
    # tpu_custom_calls the compiled train step must hold: flash forward is
    # one, its backward two (dq, dk/dv). Only a rehearsal off the chip, at
    # widths no kernel covers, hands the phase functions 0.
    min_train_custom_calls: int = 3

    def cfg(self):
        from ray_tpu.models import get_config

        return get_config(self.model, **self.model_overrides)

    def expected_first_loss(self) -> float:
        """ln V, the constant ISSUE 23 fixed (11.76 at 128256).

        It is exact for zero logits. init_params draws the head with std
        0.02, so at d_model 4096 the initial logits have variance 1.64 and
        uniformly random targets start at ln V + 0.82 = 12.58 (12.57 read
        on the CPU). This corpus is not uniform: most targets are a few
        tokens, whose own initial logits shift the first loss by a
        seed-dependent amount of about +-0.8. At seed 0 the chip read
        11.87, inside the window. See CHANGES.md, PR 23."""
        return math.log(self.cfg().vocab_size)

    def token_stream(self, n: int, salt: int):
        import numpy as np

        cfg = self.cfg()
        alphabet = np.random.default_rng(self.seed).choice(
            cfg.vocab_size, size=self.alphabet, replace=False)
        p = 1.0 / np.arange(1, self.alphabet + 1) ** 2
        rng = np.random.default_rng((self.seed, salt))
        return alphabet[rng.choice(self.alphabet, size=n, p=p / p.sum())]

    def corpus(self):
        """[corpus_rows, seq + 1] int32, made from the seed."""
        import numpy as np

        flat = self.token_stream(self.corpus_rows * (self.seq + 1), salt=1)
        return flat.reshape(self.corpus_rows, self.seq + 1).astype(np.int32)

    def prompts(self) -> List[List[int]]:
        return [self.token_stream(n, salt=100 + i).tolist()
                for i, n in enumerate(self.prompt_lens)]


# ---------------------------------------------------------------------------
# what the smoke decides (unit-tested on the CPU)
# ---------------------------------------------------------------------------


def last_line(ok: bool, device: Optional[Dict[str, Any]], **extra: Any) -> str:
    return json.dumps({"ok": ok, "device": device, **extra})


def check_losses(losses: Sequence[float], expected_first: float) -> Dict[str, Any]:
    """Check (a): finite, starts where random weights start, and falls."""
    if len(losses) < 3 or not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"losses missing or not finite: {list(losses)}")
    first, tail = losses[0], (losses[-1] + losses[-2]) / 2
    if abs(first - expected_first) > FIRST_LOSS_TOL:
        raise SmokeFailure(
            f"first loss {first:.4f} is not within {FIRST_LOSS_TOL} of "
            f"{expected_first:.4f}")
    if tail > first - LOSS_DROP:
        raise SmokeFailure(
            f"loss did not fall by {LOSS_DROP}: first {first:.4f}, mean of "
            f"last two {tail:.4f}")
    return {"first": first, "expected_first": expected_first,
            "mean_last_two": tail}


def check_margin(top2: Any, argmax: Any, served: Any) -> Dict[str, int]:
    """Check (b) for one request. top2 [n, 2]: the reference's two largest
    logits at each generated position; argmax [n]; served [n]. Wherever
    the reference is confident, the served token must be its argmax."""
    import numpy as np

    top2, argmax, served = map(np.asarray, (top2, argmax, served))
    confident = (top2[:, 0] - top2[:, 1]) > MARGIN
    wrong = confident & (argmax != served)
    if wrong.any():
        i = int(np.flatnonzero(wrong)[0])
        raise SmokeFailure(
            f"served token {int(served[i])} at generated position {i} is not "
            f"the reference's argmax {int(argmax[i])} (margin "
            f"{float(top2[i, 0] - top2[i, 1]):.3f} > {MARGIN}); "
            f"{int(wrong.sum())} such positions")
    return {"positions": int(len(served)), "confident": int(confident.sum())}


def check_not_vacuous(counts: Sequence[Dict[str, int]]) -> Dict[str, Any]:
    positions = sum(c["positions"] for c in counts)
    confident = sum(c["confident"] for c in counts)
    if positions == 0 or confident < MIN_CONFIDENT * positions:
        raise SmokeFailure(
            f"check (b) is vacuous: the reference is confident at "
            f"{confident} of {positions} generated positions, fewer than "
            f"{MIN_CONFIDENT:.0%}")
    return {"positions": positions, "confident": confident,
            "confident_share": confident / positions}


def check_kernel(name: str, out: Any, ref: Any) -> float:
    """Check (c): max|out - ref| relative to max|ref|."""
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
    if not err <= KERNEL_TOL:  # also catches nan
        raise SmokeFailure(f"kernel {name}: relative error {err} > {KERNEL_TOL}")
    return err


def check_fsdp_losses(sharded: Sequence[float], single: Sequence[float]) -> float:
    if len(sharded) != len(single):
        raise SmokeFailure(f"step counts differ: {len(sharded)} vs {len(single)}")
    worst = max(abs(a - b) for a, b in zip(sharded, single))
    if not worst <= FSDP_LOSS_TOL:
        raise SmokeFailure(
            f"fsdp=4 and one device disagree by {worst} > {FSDP_LOSS_TOL}: "
            f"{list(sharded)} vs {list(single)}")
    return worst


def check_bytes_spread(what: str, bytes_in_use: Sequence[int]) -> float:
    """'Everything on the first device' must fail."""
    if min(bytes_in_use) <= 0:
        raise SmokeFailure(f"{what}: a device holds nothing: {list(bytes_in_use)}")
    spread = max(bytes_in_use) / min(bytes_in_use)
    if spread > MAX_BYTES_SPREAD:
        raise SmokeFailure(
            f"{what}: bytes_in_use {list(bytes_in_use)} spread {spread:.2f} "
            f"> {MAX_BYTES_SPREAD}")
    return spread


def check_readback(step_s: float, readback_s: float) -> None:
    if readback_s > READBACK_SHARE * step_s + 1e-3:
        raise SmokeFailure(
            f"block_until_ready returned early: the scalar readback after "
            f"it took {readback_s:.4f}s of a {step_s:.4f}s step")


# ---------------------------------------------------------------------------
# compile seconds per phase, from jax's own events
# ---------------------------------------------------------------------------

_compile = {"seconds": 0.0, "cache_hits": 0}


def _watch_compiles() -> None:
    import jax

    def on_duration(event: str, seconds: float, **_kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _compile["seconds"] += seconds

    def on_event(event: str, **_kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _compile["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def run_phase(name: str, fn, *args: Any) -> Any:
    before, t0 = dict(_compile), time.perf_counter()
    out = fn(*args)
    emit(phase=name, seconds=round(time.perf_counter() - t0, 3),
         compile_seconds=round(_compile["seconds"] - before["seconds"], 3),
         compile_cache_hits=_compile["cache_hits"] - before["cache_hits"],
         peak_bytes_in_use=_peak_bytes())
    return out


def _memory_stats(device) -> Dict[str, Any]:
    return device.memory_stats() or {}  # the CPU backend reports none


def _peak_bytes() -> Optional[int]:
    import jax

    return _memory_stats(jax.devices()[0]).get("peak_bytes_in_use")


def _bytes_in_use() -> List[int]:
    import jax

    return [int(_memory_stats(d).get("bytes_in_use", 0)) for d in jax.devices()]


# ---------------------------------------------------------------------------
# leave nothing running
# ---------------------------------------------------------------------------


def adopt_orphans() -> None:
    """PR_SET_CHILD_SUBREAPER: a descendant whose own parent dies falls to
    this process, not to init, so `_children` sees every process the run
    started for as long as it lives."""
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(36, 1, 0, 0, 0) != 0:
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_CHILD_SUBREAPER)")


def _children() -> Dict[int, str]:
    """pid -> name of every live process whose parent is this one; what
    has exited is reaped on the way."""
    me, out = os.getpid(), {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                stat = f.read()
        except OSError:  # gone since listdir
            continue
        name = stat[stat.index("(") + 1: stat.rindex(")")]
        state, ppid = stat[stat.rindex(")") + 2:].split()[:2]
        if int(ppid) != me:
            continue
        if state == "Z":
            try:
                os.waitpid(int(entry), os.WNOHANG)
            except ChildProcessError:  # its owner reaped it meanwhile
                pass
            continue
        out[int(entry)] = name
    return out


def stop_children() -> Dict[str, Any]:
    """Stop what outlives `ray_tpu.shutdown()` by design — the pool's
    forkserver and multiprocessing's resource tracker, which otherwise go
    only up to a second after this process — then wait for every other
    child to be gone. What is still there after EXIT_GRACE_S is killed and
    fails the run. (On the chip the backend itself has no child process.)"""
    from multiprocessing import forkserver, resource_tracker

    at_entry = _children()
    forkserver._forkserver._stop()  # closes its alive pipe and waits for it
    resource_tracker._resource_tracker._stop()
    deadline = time.monotonic() + EXIT_GRACE_S
    while (left := _children()) and time.monotonic() < deadline:
        time.sleep(0.05)
    for pid in left:
        try:
            os.kill(pid, 9)
            os.waitpid(pid, 0)
        except (ProcessLookupError, ChildProcessError):  # went by itself
            pass
    if left:
        raise SmokeFailure(
            f"still running {EXIT_GRACE_S}s after shutdown, killed: {left}")
    return {"alive_after_shutdown": sorted(at_entry.values())}


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: jax reports {device}")
    if len(devices) < chips:
        raise SmokeFailure(f"--chips {chips} needs that many devices: {device}")
    return device


# ---------------------------------------------------------------------------
# phase 2: kernels
# ---------------------------------------------------------------------------


def kernel_cases(cfg, page_size: int, seq: int, batch: int = 8,
                 chunk: int = 256, span: int = 4, seed: int = 0):
    """-> [(name, op, reference, args)]: the public ops at cfg's head
    geometry in bf16, each beside the reference that lives next to it.
    `reference` takes the same args cast to f32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import (
        flash_attention,
        mha_reference,
        paged_attention_chunk,
        paged_attention_decode,
        paged_attention_verify,
        pool_shape,
        rms_norm,
        rms_norm_reference,
    )
    from ray_tpu.ops.paged_attention import (
        _chunk_reference,
        _paged_reference,
        _verify_reference,
    )

    H, KVH, D = cfg.n_heads, cfg.kv_heads, cfg.hdim
    scale = D ** -0.5
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rand(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    q, k, v = rand(1, seq, H, D), rand(1, seq, KVH, D), rand(1, seq, KVH, D)
    w_out = rand(1, seq, H, D)  # cotangent of the attention output

    def flash_grads(attention):
        def grads(q, k, v, w):
            return jax.grad(
                lambda q, k, v: jnp.sum(
                    attention(q, k, v, causal=True).astype(jnp.float32)
                    * w.astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)
        return grads

    pages_per_seq = seq // page_size
    n_pages = batch * pages_per_seq + 1
    # a pool of two layers; the ops attend over its last one
    layer = 1
    k_pages = rand(*pool_shape(2, n_pages, page_size, KVH, D))
    v_pages = rand(*pool_shape(2, n_pages, page_size, KVH, D))
    rng = np.random.default_rng(seed)
    table = jnp.asarray(
        rng.permutation(n_pages - 1)[: batch * pages_per_seq].reshape(
            batch, pages_per_seq) + 1, jnp.int32)
    lengths = jnp.asarray(rng.integers(1, seq - span, batch), jnp.int32)
    start = (seq // 2) // chunk * chunk

    return [
        ("flash_attention_fwd",
         lambda q, k, v: flash_attention(q, k, v, causal=True),
         lambda q, k, v: mha_reference(q, k, v, causal=True),
         (q, k, v)),
        ("flash_attention_bwd", flash_grads(flash_attention),
         flash_grads(mha_reference), (q, k, v, w_out)),
        ("paged_attention_decode",
         lambda *a: paged_attention_decode(*a, layer),
         lambda *a: _paged_reference(*a, layer, scale),
         (rand(batch, H, D), k_pages, v_pages, table, lengths)),
        ("paged_attention_chunk",
         lambda q, kp, vp, pt: paged_attention_chunk(
             q, kp, vp, pt, start, start + chunk, layer),
         lambda q, kp, vp, pt: _chunk_reference(
             q, kp, vp, pt, start, start + chunk, layer, scale),
         (rand(chunk, H, D), k_pages, v_pages, table[0])),
        ("paged_attention_verify",
         lambda *a: paged_attention_verify(*a, layer),
         lambda *a: _verify_reference(*a, layer, scale),
         (rand(batch, span, H, D), k_pages, v_pages, table, lengths)),
        ("rms_norm", lambda x, w: rms_norm(x, w, eps=cfg.norm_eps),
         lambda x, w: rms_norm_reference(x, w, eps=cfg.norm_eps),
         (rand(seq, cfg.d_model), rand(cfg.d_model))),
    ]


def run_kernel_case(name, op, reference, args) -> Dict[str, Any]:
    """Compile and run one op; -> its error against the f32 reference and
    the count of `tpu_custom_call` in its lowered text."""
    import jax
    import jax.numpy as jnp

    lowered = jax.jit(op).lower(*args)
    custom_calls = lowered.as_text().count("tpu_custom_call")
    out = lowered.compile()(*args)
    f32 = [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
           else a for a in args]
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(reference)(*f32)
    errs = [check_kernel(name, o, r)
            for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref))]
    return {"kernel": name, "rel_err": max(errs), "custom_calls": custom_calls}


def phase_kernels(plan: Plan) -> None:
    from ray_tpu.serve.engine import EngineConfig

    cfg = plan.cfg()
    page_size = EngineConfig(**plan.engine).page_size
    for case in kernel_cases(cfg, page_size, plan.seq, seed=plan.seed):
        row = run_kernel_case(*case)
        emit(**row, tol=KERNEL_TOL)
        if row["custom_calls"] < 1:
            raise SmokeFailure(
                f"kernel {row['kernel']}: no tpu_custom_call in the lowered "
                "text: a shape gate took the XLA path")


# ---------------------------------------------------------------------------
# phase 3: train
# ---------------------------------------------------------------------------


def train_loop(config: Dict[str, Any]) -> None:
    """The gang member: runs in the runtime process, which owns the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.comm.mesh import MeshSpec, build_mesh
    from ray_tpu.train.checkpoint import save_pytree
    from ray_tpu.train.lm import (
        batch_shardings,
        init_train_state,
        make_global_batch,
        make_optimizer,
        make_train_step,
    )

    plan = Plan(**config["plan"])
    cfg = plan.cfg()
    n_dev = math.prod(config["mesh_axes"].values())
    mesh = build_mesh(MeshSpec.create(**config["mesh_axes"]),
                      devices=jax.devices()[:n_dev])
    # the llama-2b recipe: bf16 parameters (hence gradients) and
    # factored second moments, through the library's own optimizer
    opt = make_optimizer(learning_rate=plan.learning_rate,
                         warmup_steps=plan.warmup_steps,
                         total_steps=plan.steps, factored=True)
    state, state_shardings = init_train_state(
        cfg, mesh, jax.random.PRNGKey(plan.seed), opt,
        param_dtype=jnp.bfloat16)
    jax.block_until_ready(state)
    placed = _bytes_in_use()[:n_dev]
    shardings = batch_shardings(mesh)

    def batches():
        shard = train.get_dataset_shard("train")
        while True:  # the corpus is small enough to repeat
            for batch in shard.iter_batches(batch_size=plan.batch):
                toks = np.stack([np.asarray(t) for t in batch["tokens"]])
                yield make_global_batch(
                    {"tokens": toks[:, :-1], "targets": toks[:, 1:]}, shardings)

    it = batches()
    batch = next(it)
    with mesh:
        t0 = time.perf_counter()
        # the new state keeps the layout it was born with (a compiled
        # program takes no other); the metrics are scalars, replicated
        step = jax.jit(
            make_train_step(cfg, opt), donate_argnums=0,
            out_shardings=(state_shardings, state_shardings["step"]),
        ).lower(state, batch).compile()
        compile_s = time.perf_counter() - t0
        custom_calls = step.as_text().count("tpu_custom_call")
        for i in range(plan.steps):
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
            jax.block_until_ready((state, metrics))
            t1 = time.perf_counter()
            loss = float(metrics["loss"])
            t2 = time.perf_counter()
            train.report({
                "step": i, "loss": loss, "step_seconds": t1 - t0,
                "readback_seconds_after_block": t2 - t1,
                "compile_seconds": compile_s, "custom_calls": custom_calls,
                "bytes_in_use_after_placement": placed,
            })
            if i + 1 < plan.steps:
                batch = next(it)
    if config["checkpoint"] and train.get_context().get_world_rank() == 0:
        save_pytree(state["params"], config["checkpoint"])


def phase_train(plan: Plan, mesh_axes: Dict[str, int], run_name: str,
                checkpoint: Optional[str]) -> List[Dict[str, Any]]:
    """Dataset -> JaxTrainer.fit() with the gang member in this process;
    -> the per-step reports. Checks (a), the flash custom calls in the
    compiled step, and that block_until_ready waited."""
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ds = rt_data.from_items([{"tokens": row} for row in plan.corpus()])
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"plan": dataclasses.asdict(plan),
                           "mesh_axes": mesh_axes, "checkpoint": checkpoint},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     mesh_shape=mesh_axes),
        run_config=RunConfig(name=run_name,
                             storage_path=os.path.join(OUT_DIR, "runs")),
        datasets={"train": ds},
    )
    result = trainer.fit()
    if result.error is not None:
        raise SmokeFailure(f"training failed: {result.error!r}") from result.error
    history = result.metrics_history
    for row in history:
        emit(train=run_name, **row)
    if len(history) != plan.steps:
        raise SmokeFailure(f"{len(history)} steps reported, not {plan.steps}")
    emit(check="a", train=run_name,
         **check_losses([r["loss"] for r in history], plan.expected_first_loss()))
    if history[0]["custom_calls"] < plan.min_train_custom_calls:
        raise SmokeFailure(
            f"the compiled train step holds {history[0]['custom_calls']} "
            "tpu_custom_call, fewer than flash forward + backward")
    for row in history[1:]:
        check_readback(row["step_seconds"], row["readback_seconds_after_block"])
    return history


# ---------------------------------------------------------------------------
# phase 4: serve, and the plain reference it is compared with
# ---------------------------------------------------------------------------


def _param_template(cfg):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import init_params

    return jax.eval_shape(
        lambda key: jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                 init_params(cfg, key)),
        jax.random.PRNGKey(0))


def _post(port: int, route: str, payload: Dict[str, Any]) -> Dict[str, Any]:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{route}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read())["result"]


def phase_serve(plan: Plan, checkpoint: str, tensor_parallel: int = 1,
                app_name: str = "smoke") -> List[List[int]]:
    """serve.run the repo's LLM deployment on the trained checkpoint, then
    one concurrent HTTP POST per prompt; -> the served tokens."""
    import jax

    from ray_tpu import serve
    from ray_tpu.serve.engine import EngineConfig

    plan_dict = dataclasses.asdict(plan)

    def load_trained():
        # bf16 straight from the checkpoint: no f32 copy of the model ever
        # exists on the device. Under tensor parallelism every leaf lands
        # on its shards, never whole on the first device.
        from ray_tpu.comm.mesh import MeshSpec, build_mesh
        from ray_tpu.models import param_axes
        from ray_tpu.parallel.sharding import tree_shardings
        from ray_tpu.train.checkpoint import load_pytree

        cfg = Plan(**plan_dict).cfg()
        mesh = build_mesh(MeshSpec.create(tp=tensor_parallel),
                          devices=jax.devices()[:tensor_parallel])
        return load_pytree(
            checkpoint, target=_param_template(cfg),
            shardings=tree_shardings(param_axes(cfg), mesh)), cfg

    prompts = plan.prompts()
    chunk = EngineConfig(**plan.engine).prefill_chunk
    paths = ["chunked" if len(p) > chunk else "bucketed" for p in prompts]
    if len(set(paths)) != 2:
        raise SmokeFailure(f"prompts must take both prefill paths: {paths}")
    app = serve.LLMServer.bind(params_fn=load_trained, engine_config=plan.engine,
                               tensor_parallel=tensor_parallel)
    t0 = time.perf_counter()
    handle = serve.run(app, name=app_name)
    # serve.run returns once the replica is asked for, not once it is up:
    # its first call answers only after __init__ (load + warmup compiles),
    # and raises what __init__ raised instead of letting the controller
    # respawn a replica that cannot start
    handle.options("stats").remote({}).result(timeout=900.0)
    placed = _bytes_in_use()[:tensor_parallel]
    emit(serve=app_name, tensor_parallel=tensor_parallel,
         replica_ready_seconds=round(time.perf_counter() - t0, 3),
         bytes_in_use_after_placement=placed)
    if tensor_parallel > 1:
        what = f"engine under tensor_parallel={tensor_parallel}"
        emit(check="bytes_spread", what=what,
             spread=check_bytes_spread(what, placed))
    served: List[Any] = [None] * len(prompts)

    def ask(i: int) -> None:
        try:
            served[i] = _post(serve.http_port(), app_name, {
                "prompt_ids": prompts[i], "max_tokens": plan.max_tokens,
                "temperature": 0.0})
        except BaseException as e:  # noqa: BLE001 — re-raised below
            served[i] = e

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    serve.delete(app_name)
    out = []
    for i, r in enumerate(served):
        if isinstance(r, BaseException):
            raise SmokeFailure(f"request {i} failed: {r!r}") from r
        if len(r["token_ids"]) != plan.max_tokens:
            raise SmokeFailure(
                f"request {i}: {len(r['token_ids'])} tokens, not {plan.max_tokens}")
        emit(serve=app_name, request=i, prompt_tokens=len(prompts[i]),
             prefill_path=paths[i], ttft_seconds=r["ttft_s"],
             latency_seconds=r["latency_s"], tokens=len(r["token_ids"]))
        out.append([int(t) for t in r["token_ids"]])
    return out


def reference_top2(plan: Plan, checkpoint: str,
                   sequences: Sequence[Sequence[int]]):
    """The plain reference: models.forward on the same bf16 weights, whole
    sequence, no paging and no Pallas, logits in f32. -> per sequence
    (top2 [T, 2], argmax [T]) at every position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import forward
    from ray_tpu.train.checkpoint import load_pytree

    cfg = plan.cfg()
    template = _param_template(cfg)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = load_pytree(checkpoint, target=template,
                         shardings=jax.tree.map(lambda _: one, template))
    # one shape for all: right padding is invisible to causal attention
    T = -(-max(len(s) for s in sequences) // 128) * 128

    def top2(params, tokens):
        logits, _ = forward(params, tokens, cfg)
        vals, idx = jax.lax.top_k(logits[0], 2)
        return vals, idx[:, 0]

    # dispatch reads the switch while tracing: lower under it, then restore
    prev = os.environ.get("RAY_TPU_FORCE_PALLAS")
    os.environ["RAY_TPU_FORCE_PALLAS"] = "0"
    try:
        lowered = jax.jit(top2).lower(
            params, jax.ShapeDtypeStruct((1, T), jnp.int32))
    finally:
        if prev is None:
            del os.environ["RAY_TPU_FORCE_PALLAS"]
        else:
            os.environ["RAY_TPU_FORCE_PALLAS"] = prev
    if "tpu_custom_call" in lowered.as_text():
        raise SmokeFailure("the plain reference lowered to a Pallas kernel")
    run = lowered.compile()
    out = []
    for s in sequences:
        padded = np.zeros((1, T), np.int32)
        padded[0, : len(s)] = s
        vals, idx = run(params, jnp.asarray(padded))
        out.append((np.asarray(vals), np.asarray(idx)))
    return out


def phase_reference(plan: Plan, checkpoint: str,
                    served: Dict[str, List[List[int]]]) -> None:
    """Check (b): teacher-force prompt + served tokens through the
    reference; position len(prompt) - 1 + i predicts served token i."""
    prompts = plan.prompts()
    emit(reference="start", bytes_in_use=_bytes_in_use())
    for name, outs in served.items():
        ref = reference_top2(plan, checkpoint,
                             [p + o for p, o in zip(prompts, outs)])
        counts = []
        for i, (p, o, (top2, argmax)) in enumerate(zip(prompts, outs, ref)):
            at = slice(len(p) - 1, len(p) - 1 + len(o))
            counts.append(check_margin(top2[at], argmax[at], o))
            emit(check="b", serve=name, request=i, **counts[-1])
        emit(check="b", serve=name, margin=MARGIN, **check_not_vacuous(counts))


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------


def run_one_chip(plan: Plan) -> None:
    import ray_tpu
    from ray_tpu import serve

    checkpoint = os.path.join(OUT_DIR, "checkpoint")
    ray_tpu.init()
    try:
        run_phase("train", phase_train, plan, {"dp": 1}, "train", checkpoint)
        gc.collect()
        served = run_phase("serve", phase_serve, plan, checkpoint)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    emit(phase="shutdown", clean=True)
    gc.collect()
    run_phase("reference", phase_reference, plan, checkpoint, {"tp1": served})


def run_four_chips(plan: Plan) -> None:
    """Only what exists across chips, and what it is compared with."""
    import ray_tpu
    from ray_tpu import serve

    # one row per fsdp shard
    plan = dataclasses.replace(plan, batch=4)
    checkpoint = os.path.join(OUT_DIR, "checkpoint")
    ray_tpu.init()
    try:
        sharded = run_phase("train_fsdp4", phase_train, plan, {"fsdp": 4},
                            "train_fsdp4", checkpoint)
        emit(check="bytes_spread", what="train state under fsdp=4",
             spread=check_bytes_spread(
                 "train state under fsdp=4",
                 sharded[0]["bytes_in_use_after_placement"]))
        gc.collect()
        single = run_phase("train_1dev", phase_train, plan, {"fsdp": 1},
                           "train_1dev", None)
        emit(check="fsdp_vs_single", tol=FSDP_LOSS_TOL,
             worst=check_fsdp_losses([r["loss"] for r in sharded],
                                     [r["loss"] for r in single]))
        served = {}
        for tp in (4, 1):
            gc.collect()
            served[f"tp{tp}"] = run_phase(
                f"serve_tp{tp}", phase_serve, plan, checkpoint, tp, f"smoke_tp{tp}")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    emit(phase="shutdown", clean=True)
    gc.collect()
    run_phase("reference", phase_reference, plan, checkpoint, served)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the four-chip path and what it is "
                             "compared with")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    plan = Plan(seed=args.seed)
    device = None
    try:
        try:
            adopt_orphans()
            device = run_phase("device", phase_device, args.chips)
            from ray_tpu.util.compile_cache import enable_compile_cache

            emit(model=plan.model,
                 reduced=f"n_layers {PUBLISHED_LAYERS} -> {N_LAYERS}",
                 compile_cache=enable_compile_cache(), seed=plan.seed)
            _watch_compiles()
            if args.chips == 4:
                run_four_chips(plan)
            else:
                run_phase("kernels", phase_kernels, plan)
                run_one_chip(plan)
        finally:
            # 5 GiB of weights: not something to carry back from the chip
            shutil.rmtree(os.path.join(OUT_DIR, "checkpoint"), ignore_errors=True)
            emit(phase="processes", **stop_children())
    except BaseException as e:  # noqa: BLE001 — reported as a failure, never a pass
        traceback.print_exc()
        print(last_line(False, device, error=repr(e)), flush=True)
        return 1
    print(last_line(True, device), flush=True)
    return 0

if __name__ == "__main__":
    # run as the module `chip_smoke`, not `__main__`: the trainer ships
    # train_loop to the gang by reference, as it would any user's module
    import chip_smoke

    sys.exit(chip_smoke.main())
