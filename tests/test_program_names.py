"""Names on the device side: every program the engine and the trainer jit
has a module name that says what it is, every Pallas kernel a `name=`, and
the parts of a block a `named_scope`. Lowered on the CPU; a profile on the
chip shows the same names (PERF.md section 3)."""

import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import get_config, init_params
from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                  _gather_pages_jit, _scatter_pages_jit)

B = 4


def _scoped(text: str, scope: str) -> bool:
    """`scope` is a component of some location's name stack (under a
    gradient the forward's scopes read `jvp(scope)`, the backward's
    `transpose(jvp(scope))`)."""
    return re.search(r'loc\("(?:[^"]*[/(])?%s[/")]' % re.escape(scope),
                     text) is not None


def _module(lowered) -> str:
    (name,) = re.findall(r"module @(\S+)", lowered.as_text())
    return name


def _pallas_names(fn, *args):
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        walk(getattr(inner, "jaxpr", inner))

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.fixture(scope="module", params=["tiny-llama", "tiny-moe"])
def engine(request):
    cfg = get_config(request.param)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(params, cfg, EngineConfig(
        max_batch_size=B, max_pages=64, max_seq_len=160,
        prefill_buckets=(16, 32), prefill_chunk=32,
        speculation={"mode": "ngram", "num_speculative_tokens": 3}))


def _args(eng, program):
    """What `program` takes, as the engine itself describes it."""
    return eng.programs()[program].args


def test_engine_programs_have_module_names_and_scopes(engine):
    ffn = "moe" if engine.cfg.is_moe else "ffn"
    # a module a sampler, whatever the span: its length is an argument
    for span, adv, want in ((4, False, "jit_decode_span"),
                            (engine.ecfg.span_rows, True,
                             "jit_decode_span_adv")):
        low = engine._decode(span, adv).lower(*_args(engine, "decode_span"))
        assert _module(low) == want
    text = low.as_text(debug_info=True)
    for scope in ("embed", "attn", "kv_write", ffn, "lm_head", "sample"):
        assert _scoped(text, scope), scope
    assert re.search(r'loc\("(?:[^"]*/)?attn/kv_write/', text)
    if engine.cfg.is_moe:
        # a decode step is a row of one token: nothing can be dropped, so
        # the program dispatches nothing (the train step at 1.25 below
        # keeps all four scopes)
        for scope in ("route", "experts", "combine"):
            assert re.search(r'loc\("(?:[^"]*/)?moe/%s[/"]' % scope, text)
        assert not re.search(r'loc\("(?:[^"]*/)?moe/dispatch[/"]', text)
    for export, want in ((False, "jit_chunk_prefill_32"),
                         (True, "jit_chunk_prefill_32_export")):
        low = engine._chunk_fn(32, export).lower(
            *_args(engine, "chunk_prefill_32"))
        assert _module(low) == want
    text = low.as_text(debug_info=True)
    for scope in ("embed", "attn", "kv_write", ffn, "lm_head"):
        assert _scoped(text, scope), scope
    # the bucket program keeps the module name the benchmark keys on and
    # carries its shape class as a scope
    low = engine.programs(batch_sizes=(2,))["prefill_bucket_16x2"].lower()
    assert _module(low) == "jit_run"
    text = low.as_text(debug_info=True)
    for scope in ("prefill_bucket_16x2", "embed", "attn", ffn, "lm_head"):
        assert _scoped(text, scope), scope


def test_page_and_verify_programs_have_module_names(engine):
    cache = jnp.zeros((engine.cfg.n_layers, 32, engine.cfg.kv_heads,
                       engine.cfg.hdim), engine.k_pages.dtype)
    pages = jnp.arange(1, 3, dtype=jnp.int32)
    low = _scatter_pages_jit.lower(engine.k_pages, engine.v_pages, cache,
                                   cache, pages)
    assert _module(low) == "jit_scatter_pages"
    low = _gather_pages_jit.lower(engine.k_pages, engine.v_pages, pages,
                                  engine.cfg.kv_heads)
    assert _module(low) == "jit_gather_pages"
    low = engine.programs()["verify_3"].lower()
    assert _module(low) == "jit_verify_3"


def _scans(jaxpr):
    """Every `scan` equation of the jaxpr, at any depth."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    yield from _scans(getattr(inner, "jaxpr", inner))


@pytest.fixture(scope="module")
def draft_engine():
    cfg = get_config("tiny-llama")
    return InferenceEngine(
        init_params(cfg, jax.random.PRNGKey(0)), cfg, EngineConfig(
            max_batch_size=B, max_pages=64, max_seq_len=160,
            prefill_buckets=(16, 32), prefill_chunk=32,
            speculation={"mode": "draft", "num_speculative_tokens": 3}))


def _draft_chunk(eng):
    d = eng._spec.proposer
    return d._chunk_fn(32), d.k_pages, (
        d.params, d.k_pages, d.v_pages, jnp.zeros((32,), jnp.int32),
        jnp.int32(0), d._tables[0])


def _draft_propose(eng):
    d = eng._spec.proposer
    zeros = jnp.zeros((B,), jnp.int32)
    return d._propose_fn, d.k_pages, (
        d.params, d.k_pages, d.v_pages, zeros, zeros, zeros, d._tables)


# name -> engine -> (jitted program, its pool, arguments)
POOL_PROGRAMS = {
    "decode_span": lambda e: (
        e._decode(4), e.k_pages, _args(e, "decode_span")),
    "chunk_step": lambda e: (
        e._chunk_fn(32), e.k_pages, _args(e, "chunk_prefill_32")),
    "chunk_step_export": lambda e: (
        e._chunk_fn(32, True), e.k_pages, _args(e, "chunk_prefill_32")),
    "spec_verify": lambda e: (
        e._spec._verify(False), e.k_pages, _args(e, "verify_3")),
    "spec_draft_chunk": _draft_chunk,
    "spec_draft_propose": _draft_propose,
}


@pytest.mark.parametrize("program", sorted(POOL_PROGRAMS))
def test_the_pool_is_carried_through_every_scan_never_stacked(
        program, draft_engine):
    """One page pool, updated in place: a scan that takes the pool as a
    scanned input and returns it as a stacked output makes XLA keep a
    second pool and copy a layer's slab in and out each step (PERF.md,
    PR 26). So wherever a scan holds something pool-shaped, it is a carry
    (index < num_carry), on the way in and on the way out."""
    fn, pool, args = POOL_PROGRAMS[program](draft_engine)
    slab = pool.shape[1:]  # a layer's slab, stacked, is the pool's shape

    def pool_shaped(var):
        shape = var.aval.shape
        return len(shape) >= len(slab) and shape[-len(slab):] == slab

    carried = 0
    for eqn in _scans(jax.make_jaxpr(fn)(*args).jaxpr):
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        for i, var in enumerate(eqn.outvars):
            if pool_shaped(var):
                assert i < n_carry, (program, "stacked output", var.aval)
                carried += 1
        for i, var in enumerate(eqn.invars[n_consts:]):
            if pool_shaped(var):
                assert i < n_carry, (program, "scanned input", var.aval)
    assert carried >= 2  # k and v went through at least the layer scan


def test_the_train_step_keeps_its_module_name_and_gains_scopes():
    from ray_tpu.train.lm import make_optimizer, make_train_step

    cfg = get_config("tiny-moe")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, total_steps=10)
    state = {"step": jnp.zeros((), jnp.int32), "params": params,
             "opt_state": opt.init(params)}
    toks = jnp.ones((2, 32), jnp.int32)
    low = jax.jit(make_train_step(cfg, opt)).lower(
        state, {"tokens": toks, "targets": toks})
    assert _module(low) == "jit_step"  # the benchmark's `train_step` group
    text = low.as_text(debug_info=True)
    for scope in ("embed", "attn", "moe", "route", "dispatch", "experts",
                  "combine", "lm_head", "loss", "optimizer"):
        assert _scoped(text, scope), scope
    # the backward pass carries the forward's scopes
    assert re.search(r'loc\("[^"]*transpose\(jvp\(lm_head\)\)/', text)


def test_every_pallas_kernel_is_named():
    from ray_tpu.ops import (flash_attention, paged_attention_chunk,
                             paged_attention_decode, paged_attention_verify,
                             rms_norm)

    q = jnp.ones((1, 128, 2, 128), jnp.bfloat16)
    kv = jnp.ones((1, 128, 1, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).sum().astype(jnp.float32)

    assert _pallas_names(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) == [
        "flash_fwd", "flash_bwd_dq"]  # ONE backward pass over the tiles
    assert _pallas_names(lambda x, w: rms_norm(x, w, eps=1e-5),
                         jnp.ones((8, 128)), jnp.ones((128,))) == ["rms_norm"]
    pages = jnp.ones((1, 1, 9, 16, 128), jnp.bfloat16)  # [L, 1, P, ps, KVH*hd]
    table = jnp.zeros((2, 4), jnp.int32)
    assert _pallas_names(
        lambda q: paged_attention_decode(q, pages, pages, table,
                                         jnp.ones((2,), jnp.int32), layer=0),
        jnp.ones((2, 2, 128), jnp.bfloat16)) == ["paged_decode"]
    assert _pallas_names(
        lambda q: paged_attention_chunk(q, pages, pages, table[0], 0, 16,
                                        layer=0),
        jnp.ones((16, 2, 128), jnp.bfloat16)) == ["paged_chunk"]
    assert _pallas_names(
        lambda q: paged_attention_verify(q, pages, pages, table,
                                         jnp.zeros((2,), jnp.int32), layer=0),
        jnp.ones((2, 3, 2, 128), jnp.bfloat16)) == ["paged_verify"]
