"""The plain reference of the Solar Open 2 family (Solar-Open2-250B,
upstage): every layer's equations in straightforward jax.numpy, float32,
matmuls at `highest` precision. No kernels, no WY form, no cache, no pages,
no state carried between calls, no batching, nothing imported from the
program.

    every layer l:  x = x + Mix_l(RMSNorm(x));  x = x + MoE(RMSNorm(x))
    RMSNorm: x / sqrt(mean(x^2) + eps) * w, eps `rms_norm_eps`.
    Mix_l is GQA where l is in `gqa_layers`, else Kimi Delta Attention
    (KDA, arXiv:2510.26692; `linear_attn_config`: H heads of d, K taps):
      KDA   [q~ ; k~ ; v~] = x W_in            (D -> 3 H d)
            each channel through a causal depthwise convolution of K taps
            (zero before t = 0, no bias), then SiLU; per head
            q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(d), k = k~ / sqrt(|k~|^2 + 1e-6)
            g_t = -exp(A_log[h]) softplus(f_b(f_a(x_t)) + dt_bias)  in R^{H x d}:
            a decay a KEY CHANNEL, through the low-rank pair f_a: D -> r,
            f_b: r -> H d (`kda_use_full_proj` false), `A_log` a head,
            `dt_bias` a lane;  beta_t = 2 sigmoid(x_t W_b) a head (the 2 is
            `kda_allow_neg_eigval`)
            S_0 = 0 [d, d] per head, and a token at a time
                S' = Diag(exp g_t) S_{t-1}
                S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
                o_t = S_t^T q_t
            mix = concat_h(RMSNorm_d(o_t; w) * sigmoid(g_b(g_a(x_t)) + b)) W_out,
            g_a: D -> r, g_b: r -> H d with a bias
      GQA   q = x W_q (H heads of hd), k = x W_k, v = x W_v (`num_key_value_heads`
            of hd), NO positional encoding (`use_rope` false), no norm on q
            or k; mix = (softmax(q k^T / sqrt(hd), causal) v
                         * sigmoid(x W_g)) W_o, the gate lane by lane over
            the H hd lanes, from the same normed input as q (`use_gqa_gate`)
    MoE (every layer: `first_k_dense_replace` 0): score = sigmoid(x W_r) in
            float32 over ALL `n_routed_experts_total` outputs; the
            `num_experts_per_tok` largest of score + bias are chosen; their
            weights are the scores WITHOUT the bias over the sum of all the
            chosen (`norm_topk_prob`), times `routed_scaling_factor`. A
            chosen expert that is not held here (`held_experts_first` .. +
            the held count) adds nothing: its term is another chip's. Beside
            them `n_shared_experts` shared experts that every token passes
            through with weight 1, computed whole (every chip of the layer
            computes them; in a sum over the chips they count ONCE). An
            expert is W_down (silu(x W_gate) * x W_up).
    Head: final RMSNorm, logits = x W_head (untied), over the held slice of
    the vocabulary.

Departures from the published description, none in the mathematics: the
projections are stored as the program's tree stores them (`d_in` [D, 3 H d]
in the order q, k, v: three published matrices side by side; `d_conv` [K,
channels] with tap K-1 on the current position; the shared experts as ONE
gated FFN `sh_in` / `sh_gate` / `sh_out` of n x w); attention goes over
blocks of queries, the experts one at a time and the head over blocks of
the vocabulary, so the reference fits the chip beside the bfloat16 weights.
The weights are the program's tree (`layers`: a list of segments, each a
tuple with one dict per layer of its period, stacked over repeats); the
reference walks it in order and tells a layer's kind by its index. A layer
is two jitted programs (`_mix`, `_experts`); a pass builds the ones that a
new length needs (both mixers, the experts, the head) side by side
(`_programs`, `_built`) and keeps them, the head takes the positions asked
for in whole blocks of `HEAD_ROWS`, and the experts take the tokens in
blocks of `ROWS`, those alone that hold a token at or before the last
position asked for (the rest is the caller's right padding): the
compiler's and the chip's time, not the mathematics.

`mode` is the control's part: "int8" / "fp8" round every matmul weight (the
head too) per output column; "state-bf16" rounds the delta-rule state to
bfloat16 after every token and "router-bf16" computes the router's scores
in bfloat16 (both reported without a limit: the program's are float32)."""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark.reference.model import dense_ffn, quantize, rms_norm

Q_BLOCK = 256
VOCAB_BLOCK = 8192
HEAD_ROWS = 64  # the positions asked for go through the head in whole blocks
ROWS = 1024     # ... and a sequence's tokens through the experts
MATMULS = frozenset((
    "d_in", "d_fa", "d_fb", "d_b", "d_ga", "d_gb", "d_out", "wq", "wk", "wv",
    "wg", "wo", "router", "w_in", "w_gate", "w_out", "sh_in", "sh_gate",
    "sh_out"))
EXPERTS = ("w_in", "w_gate", "w_out")


def rounds_weights(mode):
    """The control's weight precision ("state-bf16" and "router-bf16" are
    controls of their own and leave the weights)."""
    return mode if mode in ("int8", "fp8") else None


def static(spec: Dict[str, Any]):
    """What the equations read of the configuration, hashable for jit."""
    linear = spec["linear_attn_config"]
    return (("heads", spec["num_attention_heads"]),
            ("kv_heads", spec["num_key_value_heads"]),
            ("head_dim", spec["head_dim"]), ("eps", spec["rms_norm_eps"]),
            ("lin_heads", linear["num_heads"]), ("lin_dim", linear["head_dim"]),
            ("taps", linear["short_conv_kernel_size"]),
            ("neg_eigval", bool(spec["kda_allow_neg_eigval"])),
            ("k", spec["num_experts_per_tok"]),
            ("norm_topk", bool(spec["norm_topk_prob"])),
            ("scale", float(spec["routed_scaling_factor"])),
            ("first", spec["held_experts_first"]))


def kind_of(l: int, spec: Dict[str, Any]) -> str:
    return "attn" if l in spec["gqa_layers"] else "gdn"


def delta_rule(q, k, v, g, beta, mode=None):
    """q, k, g [T,H,d]; v [T,H,dv]; beta [T,H] -> o [T,H,dv]: the recurrence
    as a plain scan from S_0 = 0, the decay a key channel's."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S                        # [H,dk,dv]
        d = b_t[:, None] * (v_t - jnp.einsum("hij,hi->hj", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        if mode == "state-bf16":
            # reduce_precision is never elided (a convert pair is, on the
            # chip: benchmark/reference/olmo_hybrid.py)
            S = jax.lax.reduce_precision(S, 8, 7)
        return S, jnp.einsum("hij,hi->hj", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def kda(x, lp, spec, mode=None):
    """x [T, D] (normed) -> [T, D]."""
    T = x.shape[0]
    H, d, K = spec["lin_heads"], spec["lin_dim"], spec["taps"]

    def part(i):
        """q~, k~ or v~ (i = 0, 1, 2): its columns of `d_in` and of the
        taps, one part at a time so that a long prompt's activations fit."""
        cols = slice(i * H * d, (i + 1) * H * d)
        u = x @ lp["d_in"][:, cols]
        padded = jnp.concatenate(
            [jnp.zeros((K - 1, H * d), x.dtype), u], axis=0)
        conv = jnp.zeros_like(u)
        for j in range(K):  # tap K-1 multiplies the current position
            conv = conv + padded[j:j + T] * lp["d_conv"][j, cols]
        return jax.nn.silu(conv).reshape(T, H, d)

    def unit(u):
        return u / jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(part(0)) / d ** 0.5, unit(part(1)), part(2)
    step = jax.nn.softplus((x @ lp["d_fa"]) @ lp["d_fb"] + lp["d_dt_b"])
    g = -jnp.exp(lp["d_A_log"])[:, None] * step.reshape(T, H, d)
    beta = (2.0 if spec["neg_eigval"] else 1.0) * jax.nn.sigmoid(x @ lp["d_b"])
    o = rms_norm(delta_rule(q, k, v, g, beta, mode), lp["d_norm"], spec["eps"])
    gate = jax.nn.sigmoid((x @ lp["d_ga"]) @ lp["d_gb"] + lp["d_gb_b"])
    return (o.reshape(T, H * d) * gate) @ lp["d_out"]


def gqa(x, lp, spec):
    """x [T, D] (normed) -> [T, D]: no positions, gated heads."""
    T = x.shape[0]
    H, hd = spec["heads"], spec["head_dim"]
    q = jnp.einsum("td,dhk->thk", x, lp["wq"])
    k = jnp.einsum("td,dhk->thk", x, lp["wk"])
    v = jnp.einsum("td,dhk->thk", x, lp["wv"])
    KVH = spec["kv_heads"]
    q = q.reshape(T, KVH, H // KVH, hd)  # query heads by the kv head they share
    block = min(Q_BLOCK, T)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = jnp.einsum("qgrk,tgk->grqt", qb, k) / hd ** 0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grqt,tgk->qgrk", p, v)

    o = jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, H, hd)
    gate = jax.nn.sigmoid(jnp.einsum("td,dhk->thk", x, lp["wg"]))
    return jnp.einsum("thk,hkd->td", o * gate, lp["wo"])


def route(b, lp, spec, mode=None):
    """b [T, D] -> (weights [T, k], expert ids [T, k]) over ALL the outputs
    of the router, held here or not."""
    if mode == "router-bf16":
        low = jnp.bfloat16
        score = jax.nn.sigmoid(
            b.astype(low) @ lp["router"].astype(low)).astype(jnp.float32)
    else:
        score = jax.nn.sigmoid(b @ lp["router"])
    _, ids = jax.lax.top_k(score + lp["router_bias"], spec["k"])
    w = jnp.take_along_axis(score, ids, axis=-1)
    if spec["norm_topk"]:  # over all the chosen: the sum is the router's
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * spec["scale"], ids


def moe(b, lp, spec, mode=None, shared=True):
    """This chip's part of the expert layer over b [T, D]: the held experts
    (the weights' leading axis, from `held_experts_first`) that a token
    chose, weighted, and, if `shared`, the shared experts whole."""
    w, ids = route(b, lp, spec, mode)
    low = rounds_weights(mode)

    def f32(m):
        return quantize(m, low).astype(jnp.float32)

    def one_expert(out, expert):
        e, w_in, w_gate, w_out = expert
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)   # [T]
        return out + gate[:, None] * dense_ffn(
            b, f32(w_in), f32(w_gate), f32(w_out)), None

    held = lp["w_in"].shape[0]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(b), (
        spec["first"] + jnp.arange(held), lp["w_in"], lp["w_gate"],
        lp["w_out"]))
    if shared:
        out = out + dense_ffn(b, f32(lp["sh_in"]), f32(lp["sh_gate"]),
                              f32(lp["sh_out"]))
    return out


def _f32(lp, mode):
    """Every weight but the experts' in float32, the matmuls' rounded
    through `mode` (the experts are converted one at a time, in `moe`)."""
    low = rounds_weights(mode)
    return {name: (w if name in EXPERTS else
                   (quantize(w, low) if name in MATMULS else w)
                   .astype(jnp.float32)) for name, w in lp.items()}


@functools.partial(jax.jit,
                   static_argnames=("kind", "items", "mode", "precision"))
def _mix(x, lp, kind, items, mode, precision="highest"):
    """-> (x + Mix(N1(x)), N2 of that). `precision` is the reference's own
    everywhere; the family's draw of the weights passes a sample through
    these layers at "default" (benchmark/families/solar_open2.py)."""
    spec = dict(items)
    with jax.default_matmul_precision(precision):
        w = _f32({n: a for n, a in lp.items() if n not in EXPERTS}, mode)
        h = rms_norm(x, w["ln1"], spec["eps"])
        x = x + (gqa(h, w, spec) if kind == "attn" else kda(h, w, spec, mode))
        return x, rms_norm(x, w["ln2"], spec["eps"])


@functools.partial(jax.jit,
                   static_argnames=("items", "mode", "shared", "precision"))
def _experts(b, lp, items, mode, shared=True, precision="highest"):
    with jax.default_matmul_precision(precision):
        return moe(b, _f32(lp, mode), dict(items), mode, shared)


SECOND_HALF = (*EXPERTS, "router", "router_bias", "sh_in", "sh_gate", "sh_out")

# (program, static arguments, the arguments' shapes) -> its executable
_BUILT: Dict[Any, Any] = {}


def _built(jobs: List[tuple]) -> List[Any]:
    """jobs [(jitted function, arguments, static arguments)] -> each one's
    executable for those arguments' shapes, the ones not built yet compiled
    SIDE BY SIDE, a thread each. No part of the mathematics: a program with
    float32 products at `highest` takes the TPU's compiler 4 to 9 s whatever
    its size, a first pass needs four (both mixers, the experts and the
    head) and a pass over a new length both mixers again (my compiles for a
    described v5e, PR 52: 13 s side by side, 20 s one after the other)."""
    def key(fn, args, static):
        return (fn.__name__, tuple(sorted(static.items())),
                jax.tree.structure(args),
                tuple((a.shape, str(a.dtype)) for a in jax.tree.leaves(args)))

    keys = [key(*job) for job in jobs]
    new = {k: job for k, job in zip(keys, jobs) if k not in _BUILT}
    if new:
        with ThreadPoolExecutor(len(new)) as pool:
            done = pool.map(lambda job: job[0].lower(*job[1], **job[2])
                            .compile(), new.values())
            _BUILT.update(zip(new, done))
    return [_BUILT[k] for k in keys]


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_block(x, final_norm, head, eps, mode):
    """x [n, D], head [D, columns] (a block of the untied head)."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        return x @ quantize(head, rounds_weights(mode)).astype(jnp.float32)


def _programs(params, T, rows, spec, mode):
    """The executables of one pass over a sequence of T tokens that asks
    for `rows` positions: {kind: its mixer} for the kinds the model has,
    {rows: the experts' over a block of that many tokens}, {columns: the
    head's over a block of that width}, built together where the length is
    new."""
    items = static(spec)
    D, V = params["lm_head"].shape

    def shape_of(a, *shape):
        return jax.ShapeDtypeStruct(shape or a.shape, a.dtype)

    x = jax.ShapeDtypeStruct((T, D), jnp.float32)
    first = {}   # a layer of each kind, by its shapes
    for l, stacked in enumerate(params["layers"][0]):
        first.setdefault(kind_of(l, spec), jax.tree.map(
            lambda a: shape_of(a, *a.shape[1:]), stacked))
    lp = next(iter(first.values()))
    blocks = sorted({min(ROWS, T - i) for i in range(0, T, ROWS)})
    widths = sorted({min(VOCAB_BLOCK, V - i) for i in range(0, V, VOCAB_BLOCK)})
    built = iter(_built(
        [(_mix, (x, first[kind]), dict(kind=kind, items=items, mode=mode))
         for kind in first]
        + [(_experts, (jax.ShapeDtypeStruct((n, D), jnp.float32),
                       {name: lp[name] for name in SECOND_HALF}),
            dict(items=items, mode=mode)) for n in blocks]
        + [(_head_block,
            (jax.ShapeDtypeStruct((rows, D), jnp.float32),
             shape_of(params["final_norm"]), shape_of(params["lm_head"], D, w)),
            dict(eps=spec["rms_norm_eps"], mode=mode)) for w in widths]))
    return (dict(zip(first, built)), dict(zip(blocks, built)),
            dict(zip(widths, built)))


# a block of rows read and added to at a start that is an ARGUMENT: a slice
# at a constant start is a program of its own to the compiler, sixteen a
# length
@functools.partial(jax.jit, static_argnames="n")
def _rows(b, start, n):
    return jax.lax.dynamic_slice_in_dim(b, start, n, 0)


@jax.jit
def _add_rows(x, term, start):
    rows = jax.lax.dynamic_slice_in_dim(x, start, len(term), 0) + term
    return jax.lax.dynamic_update_slice_in_dim(x, rows, start, 0)


def hidden_states(params, tokens, spec, mode=None, rows=HEAD_ROWS, real=None):
    """tokens [T] -> final hidden state [T, D] (before the last norm): every
    layer in order, x = x + Mix(N1(x)) (`_mix`), x = x + MoE(N2(x))
    (`_experts`), each through its executable for this length. Of the T
    tokens the first `real` count and the rest is right padding, which no
    earlier position sees: the experts, each token's own business and the
    larger part of a pass, take the blocks of ROWS that hold a real token
    and leave the padding's rows without their term."""
    mix, experts, _ = _programs(params, len(tokens), rows, spec, mode)
    T = len(tokens)
    x = params["embed"][tokens].astype(jnp.float32)
    used = range(0, T if real is None else min(real, T), ROWS)
    l = 0
    for segment in params["layers"]:
        repeats = jax.tree.leaves(segment)[0].shape[0]
        for rep in range(repeats):
            for stacked in segment:
                lp = jax.tree.map(lambda a: a[rep], stacked)
                x, b = mix[kind_of(l, spec)](x, lp)
                half = {n: lp[n] for n in SECOND_HALF}
                for i in used:
                    n = min(ROWS, T - i)
                    x = _add_rows(x, experts[n](_rows(b, i, n), half), i)
                l += 1
    assert l == spec["num_hidden_layers"]
    return x


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`;
    what follows the last of them is taken for padding."""
    n = len(positions)
    at = jnp.pad(positions, (0, -n % HEAD_ROWS))
    *_, head_block = _programs(params, len(tokens), len(at), spec, mode)
    x = hidden_states(params, tokens, spec, mode, len(at),
                      real=int(jnp.max(positions)) + 1)[at]
    blocks = [params["lm_head"][:, i:i + VOCAB_BLOCK]
              for i in range(0, params["lm_head"].shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(
        [head_block[block.shape[1]](x, params["final_norm"], block)
         for block in blocks], axis=-1)[:n]
