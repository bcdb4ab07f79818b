"""Model configs for the built-in decoder-only transformer families.

Covers the BASELINE.md workload set: GPT-2 125M, Llama-3 8B, Mixtral 8x7B,
plus tiny variants for tests. One config class drives all families —
differences (norm type, activation, positional scheme, GQA, MoE) are fields,
not subclasses, so the same sharded forward/train/serve path covers every
family. A model whose layers differ names each layer's mixer in
`layer_kinds` and says which second halves are dense (a StackConfig).
models/stack.py runs both on the serve path: a plain ModelConfig is the
stack whose every layer is "attn". A StackConfig whose kinds are all in
`TRAINABLE_KINDS` ("attn", "swa" and "mla", with dense or expert second
halves; one residual stream or several, `hc_streams`; a multi-token
prediction block in the loss, `mtp_depth`) trains too, through the same
`forward` / `loss_fn` / `param_axes` / `make_train_step` as the one-block
models; the other kinds' ops have no backward yet
(`StackConfig.untrainable`).

A window layer's keys are held in one of two ways. The "window" kind
(differential pairs, one family) keeps them in per-slot state: every decode
slot owns a fixed ring of pages whatever its sequence holds. The "swa" kind
keeps them in a second page space that the engine's allocator serves beside
THE pool: a sequence's ring is a table of pages it was given
(`window_paged`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# what a layer's mixer can be when a StackConfig's `layer_kinds` names them
# (models/stack.py); a plain ModelConfig's layers are all "attn"
LAYER_KINDS = ("attn", "conv", "mamba", "window", "full", "gmu", "cross",
               "gdn", "mla2", "swa", "ssd", "mla")
# the kinds whose attention is latent (MLA): a token's row in THE pool is its
# latent beside the rotary key the heads share, and the pool holds no values.
# `mla2` writes two rows a layer (two attentions), `mla` one
LATENT_KINDS = {"mla2": 2, "mla": 1}
# the second halves' gated activations: down(act(gate x) * (up x))
GATED_ACTIVATIONS = ("swiglu", "reglu")
# a token's row in a pool of latents: the latent and the shared rotary key
# side by side, padded with zeros to whole 128-lane tiles
_LANES = 128
# the kinds whose attention is differential over pairs of heads
_DIFFERENTIAL = ("window", "full", "cross")
# the kinds a stack can be TRAINED with (every op they run has a backward:
# the flash kernels with and without a window, the grouped expert product;
# "mla": a training row has no past, so its latent attention is the plain
# form, keys and values up-projected once into the flash kernels, and the
# paged latent ops, which have no backward, are never reached);
# the others wait for a backward through the op named beside them
TRAINABLE_KINDS = ("attn", "swa", "mla")
_NO_BACKWARD = {"mamba": "ops/ssm.py", "gmu": "ops/ssm.py",
                "gdn": "ops/gdn.py", "ssd": "ops/ssd.py",
                "mla2": "ops/mla_attention.py: nothing has differentiated "
                        "the double block and its shortcut experts",
                "conv": "the short convolution's tail state",
                "window": "the differential pairs' plain form",
                "full": "the differential pairs' plain form",
                "cross": "the differential pairs' plain form"}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    n_kv_heads: Optional[int] = None  # None -> MHA
    head_dim: Optional[int] = None  # None -> d_model // n_heads
    max_seq_len: int = 2048
    # architecture family knobs
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    activation: str = "swiglu"  # swiglu | reglu | gelu
    positional: str = "rope"  # rope | learned
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    # MoE (0 experts -> dense)
    num_experts: int = 0
    num_selected_experts: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # training numerics
    dtype: str = "bfloat16"
    # True: the layer loop's checkpoint keeps a layer's attention half and,
    # of a dense gated FFN's `gate` and `up` products, as many as the shapes
    # of the traced step leave room for on the device (models/transformer.py
    # `kept_under_remat`: none where the backend reports no memory); the
    # backward recomputes the rest. False keeps everything
    remat: bool = True
    logits_softcap: Optional[float] = None
    # attention implementation: "flash" (Pallas/XLA blockwise, seq gathered)
    # or "ring" (sequence-parallel ring attention over the sp mesh axis)
    attn_impl: str = "flash"

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def hdim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def cache_dims(self) -> Tuple[int, int, int]:
        """(layers, kv heads, head size) of the keys and values a serving
        cache holds for this model."""
        return self.n_layers, self.kv_heads, self.hdim

    # what StackConfig (below) answers otherwise: one kind of layer, rotary
    # (or learned-position) attention over every layer's own pages and then
    # the FFN or the experts (top k of the router's logits, softmax over
    # the chosen), no norm on queries and keys, no gate on the heads'
    # outputs, and no state beside the pages
    is_stack = False
    has_state = False
    qk_norm = False
    qk_norm_whole = False
    attn_gate = False
    post_norm = False
    norm_place = "pre"
    router_bias_rate = 0.0
    router = "softmax"
    n_dense_layers = 0
    conv_tail = (0, 0, 0)
    gdn_dims = (0, 0, 0, 0)
    ssd_dims = (0, 0, 0, 0, 0)
    # the four scalars of a StackConfig, at the values that emit nothing
    embedding_multiplier = 1.0
    attention_multiplier = None
    residual_multiplier = 1.0
    logits_scaling = 1.0
    # every expert there is is held and computes: none lives on another
    # chip, none is the identity
    experts_first = 0
    experts_zero = 0
    latent_cache = False
    router_input = "ffn"
    window_paged = False
    d_ff_shared = 0
    # one residual stream, one head in the loss, plain rotary tables
    hc_streams = 1
    mtp_depth = 0
    rope_yarn = None

    @property
    def experts_routed(self) -> int:
        """Experts with weights that the router chooses among, wherever
        they are held."""
        return self.num_experts

    @property
    def router_width(self) -> int:
        """The router's outputs: every routed expert, held here or not,
        and the zero-compute ones after them."""
        return self.experts_routed + self.experts_zero

    @property
    def counts_choices(self) -> bool:
        """Whether a token's choices can fall outside the held experts, so
        that where they fell is worth counting."""
        return self.router_width != self.num_experts

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return ("attn",) * self.n_layers

    @property
    def second_halves(self) -> Tuple[str, ...]:
        """Each layer's second half: "ffn", or "moe" where the model has
        experts and the layer is past the leading dense ones."""
        return tuple("moe" if self.is_moe and l >= self.n_dense_layers
                     else "ffn" for l in range(self.n_layers))

    @property
    def expert_ff(self) -> int:
        """An expert's width (the dense FFN's unless the model says)."""
        return self.d_ff

    def count(self, kind: str) -> int:
        return self.layer_kinds.count(kind)

    def segments(self) -> Tuple[Tuple[int, Tuple[str, ...], int], ...]:
        """The stack as runs of whole periods: (first layer, the period's
        kinds, repeats). A layer is a (mixer, second half) pair and a
        period a period of pairs; every repeat of a period has its first
        repeat's second halves (`second_halves[first + i]`). A run of two
        or more equal periods (the period of up to four layers whose
        repeats cover the most layers wins, the shortest of equals) is
        scanned; a layer that belongs to none is a run of its own, once.
        Equal layers are ONE scan; SambaY's mamba/window pairs, then one
        mamba and one full layer, then gmu/cross pairs are three scans'
        worth of programs, whatever the depth; two leading dense conv
        layers and then attn/conv/conv/conv periods of expert layers are
        two; gdn/gdn/gdn/attn periods are one (not a scan of three and a
        layer alone, over and over)."""
        kinds, out, i = self.layer_kinds, [], 0
        pairs = tuple(zip(kinds, self.second_halves))
        while i < len(pairs):
            best = (1, 1)
            for p in range(1, 5):
                r = 1
                while pairs[i + r * p:i + (r + 1) * p] == pairs[i:i + p]:
                    r += 1
                if r >= 2 and p * r > best[0] * best[1]:
                    best = (p, r)
            out.append((i, kinds[i:i + best[0]], best[1]))
            i += best[0] * best[1]
        return tuple(out)

    def param_count(self) -> int:
        """Parameter count (embeddings included once if tied)."""
        D, F, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab_size
        H, KVH, hd = self.n_heads, self.kv_heads, self.hdim
        attn = D * H * hd + 2 * D * KVH * hd + H * hd * D
        if self.activation in GATED_ACTIVATIONS:
            ffn = 3 * D * F
        else:
            ffn = 2 * D * F + F + D  # gelu mlp with biases
        if self.is_moe:
            ffn = self.num_experts * ffn + D * self.num_experts
        norms = 2 * D * (2 if self.norm == "layernorm" else 1)
        emb = V * D * (1 if self.tie_embeddings else 2)
        pos = self.max_seq_len * D if self.positional == "learned" else 0
        final = D * (2 if self.norm == "layernorm" else 1)
        return L * (attn + ffn + norms) + emb + pos + final


@dataclasses.dataclass(frozen=True)
class StackConfig(ModelConfig):
    """A model whose layers differ. A subclass and not more fields of
    ModelConfig: the benchmark pins the one-block models' ModelConfig field
    by field (benchmark/tests/test_families.py)."""

    # A stack of unlike layers (models/stack.py): one mixer kind per layer,
    # each followed by its second half, the dense FFN or (past the
    # `n_dense_layers` leading ones, where there are experts) the experts.
    # Two families need one. The first: "mamba": selective state space;
    # "window" / "full": attention over the last `window` keys / all keys,
    # "full" writing THE cache that every later "cross" layer (queries
    # only) reads; "gmu": gates the last mamba layer's scan output; its
    # attention is differential over pairs of heads with biases on its
    # projections, its norms LayerNorm, and nothing encodes positions. The
    # second: "conv": a gated short convolution (`conv_taps` taps a
    # channel, the model's width) beside "attn", the one-block models'
    # rotary GQA over the layer's own pages, here with RMS-normalised
    # queries and keys (`qk_norm`); RMSNorm; two dense layers and then
    # experts of their own width (`d_ff_expert`), chosen by sigmoid scores
    # plus a per-expert bias (`router="sigmoid"`). The third: "gdn": the
    # gated delta rule, linear attention whose state is a [key, value]
    # matrix per head and sequence (`gdn_heads` of `gdn_key_dim` x
    # `gdn_value_dim`), after a short convolution of `conv_taps` taps over
    # its q, k and v channels, beside "attn" with no positions and queries
    # and keys normalised over the WHOLE projected vector
    # (`qk_norm_whole`); its norms follow their sublayers (`post_norm`). A
    # rule below belongs to the kind it names, not to a stack; another
    # family gets a field when it comes. The fourth: "mla2": ONE published
    # layer that is two blocks, each latent attention (MLA: queries through
    # a rank-`q_lora_rank` bottleneck, keys and values up-projected from
    # ONE latent of `kv_lora_rank` a token beside ONE rotary key of
    # `qk_rope_dim` shared by the heads; that row is all the cache holds,
    # `latent_cache`) and a dense FFN, with the experts computed on the
    # first block's normed stream and joined after the second block's FFN
    # (a shortcut). Its router is a softmax over ALL its outputs
    # (`router="softmax_all"`): `experts_routed` experts with weights, of
    # which this model HOLDS `num_experts` from `experts_first` on (the
    # others' share of the sum is another chip's and is left out), then
    # `experts_zero` experts that are the identity. The fifth: "swa":
    # rotary GQA (`rope_theta`, whatever `positional` says of the "attn"
    # layers beside it, which here encode no position) over the last
    # `window` keys, each layer keeping its OWN keys in a second page space
    # of THE pool's row shape (`window_paged`); its experts are many and
    # small, gated by a ReLU (`activation="reglu"`), and its router reads
    # the layer's input stream, before the first norm and the attention
    # (`router_input="layer"`). The sixth: "mla": latent attention as ONE
    # mixer a layer, followed by the layer's own second half like any other
    # kind: a leading dense layer (`n_dense_layers`) and then experts chosen
    # by sigmoid score + bias, renormalised and scaled, beside SHARED experts
    # that every token passes through with weight 1 (`d_ff_shared`: one gated
    # FFN of that width; n shared experts of width w are one of n x w). Its
    # queries are one projection where `q_lora_rank` is 0: no bottleneck and
    # no norm on the query side. What the fourth family's rules say of latents
    # (`latent_cache`, `latent_row`, `cache_dims`, one pool and no values, no
    # other kind beside them in a stack: two shapes of pool rows) are the
    # LATENT kinds' together (`LATENT_KINDS`); what is `mla2`'s alone is the
    # double block, its bottleneck on the queries and the experts it carries.
    # The seventh: "gdn" whose decay is a VECTOR over the key channels (Kimi
    # Delta Attention): g = -exp(A_log[head]) softplus(f_b(f_a(x)) + dt_bias)
    # a head AND key lane through a low-rank pair of `gdn_channel_rank`
    # (`dt_bias` a lane, beta a projection of its own), and its output gate a
    # low-rank pair of `gdn_gate_rank` with a bias under a SIGMOID, beside
    # "attn" with no positions whose heads' outputs are gated lane by lane by
    # sigmoid(x W_g) before the out-projection (`attn_gate`); every layer's
    # second half is a SHARE of many small experts (sigmoid score + bias,
    # renormalised over ALL the chosen, held or not) beside a shared expert
    # that this chip computes whole, as every chip of the layer would.
    layer_kinds: Tuple[str, ...] = ()
    window: int = 0
    ssm_inner: int = 0        # mamba / gmu inner width
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    ssm_heads: int = 0        # "ssd": heads (of ssm_inner / ssm_heads lanes)
    ssm_groups: int = 1       # "ssd": groups of heads that share B and C
    conv_taps: int = 3        # the "conv" and "gdn" kinds' kernel length
    qk_norm: bool = False     # "attn": RMSNorm each head of q and k
    qk_norm_whole: bool = False  # ... or all of q's (k's) heads as one
    # False: x + Mix(norm(x)), x + FFN(norm(x)); True: the norm follows the
    # sublayer, x + norm(Mix(x)), x + norm(FFN(x)): the older spelling of
    # `norm_place="post"`, which it is folded into (and always equals)
    post_norm: bool = False
    # where a sublayer's norms stand: "pre" (before it), "post" (after it,
    # in its place) or "both" (a sandwich: x + norm'(Mix(norm(x))), four
    # norms a layer: `ln1`, `ln1_post`, `ln2`, `ln2_post`). ONE field says
    # it; the layers read this and never `post_norm`
    norm_place: str = "pre"
    gdn_heads: int = 0        # "gdn": heads, and each one's state [dk, dv]
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    # "gdn": beta = 2 sigmoid(.), so a step's eigenvalue 1 - beta may be < 0
    gdn_neg_eigval: bool = False
    # "gdn": 0: ONE decay a head, [a ; b] = x W_ab. r > 0: a decay a key
    # channel through the low-rank pair D -> r -> heads x key_dim
    gdn_channel_rank: int = 0
    # "gdn": 0: the output gate is silu(x W_gate). r > 0: sigmoid of a
    # low-rank pair D -> r -> heads x value_dim with a bias
    gdn_gate_rank: int = 0
    # "attn" / "swa": the heads' outputs times sigmoid(x W_g), lane by lane
    attn_gate: bool = False
    n_dense_layers: int = 0   # leading layers whose second half is dense
    d_ff_expert: int = 0      # an expert's width (0: d_ff)
    # the shared experts' width, all of them together as ONE gated FFN that
    # every token of an expert layer passes through beside its routed
    # experts, with weight 1 (0: none, and nothing is emitted)
    d_ff_shared: int = 0
    # "softmax": top k of the logits, softmax over the chosen. "sigmoid":
    # sigmoid scores, the choice made on score + a per-expert bias, the
    # weights the chosen scores WITHOUT it, over their sum (+ 1e-6) if
    # `norm_topk`, times `routed_scale` (parallel/moe.py)
    # "softmax_all": softmax over all `router_width` outputs, then as
    # "sigmoid" (choice by score + bias, weights the scores without it)
    router: str = "softmax"
    norm_topk: bool = True
    routed_scale: float = 1.0
    # "sigmoid" / "softmax_all": after the optimizer's update a TRAIN step
    # moves each expert's bias by this much towards an even load,
    # b_e += rate * sign(mean(n) - n_e), n_e the step's count of choices of
    # expert e; the bias takes no gradient (0: the bias stays as it is)
    router_bias_rate: float = 0.0
    n_routed_experts: int = 0  # routed experts that exist (0: num_experts)
    experts_first: int = 0    # the first routed expert held here
    experts_zero: int = 0     # identity experts after the routed ones
    # what the router scores: "ffn": the tensor the experts compute on (the
    # stream after the mixer, normed); "layer": the layer's input stream,
    # before its first norm, so the choice is made before the mixer runs
    router_input: str = "ffn"
    # the latent kinds: the five sizes of latent attention (`q_lora_rank`
    # 0: the queries are one projection, no bottleneck and no norm: "mla"
    # alone), and whether the normed bottlenecks are scaled by
    # sqrt(d_model / rank)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mla_scale_lora: bool = False
    # 1.0 / None emit nothing: x = embedding_multiplier * E[token]; scores
    # q k^T * attention_multiplier (None: head_dim ** -0.5); x + Mix * r and
    # x + FFN * r with r = residual_multiplier; logits / logits_scaling
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # the residual path (manifold-constrained hyper-connections,
    # arXiv:2512.24880): n > 1 streams [B,T,n,D] in the residual's place.
    # Round EVERY sublayer, from the normalised 4D-wide token through its own
    # `phi` [nD, n*n + 2n]: the sublayer reads sum_j H_pre[j] x[j], and
    # x+[i] = sum_j H_res[i,j] x[j] + H_post[i] y, with H_pre = sigmoid(.),
    # H_post = 2 sigmoid(.) and H_res = `hc_sinkhorn_iters` rounds of column
    # then row normalisation (`hc_eps` in both denominators) of
    # exp(clip(., *hc_res_clamp)). The embedding is copied into every stream
    # and the streams are summed before the final norm. 1: x + y, and
    # nothing is emitted
    hc_streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # multi-token prediction blocks in the LOSS (DeepSeek-V3 2.2; 0 or 1):
    # a projection of [norm(h) ; norm(E[next token])] (h the stream before
    # the final norm), ONE more expert layer with parameters of its own, a
    # final norm of its own and the SHARED head, scored against the token
    # after next and weighted `mtp_weight` into the loss. A train step's
    # alone: the serve path never reads `params["mtp"]`
    mtp_depth: int = 0
    mtp_weight: float = 0.1
    # the latent kinds' rotary lanes under yarn: (factor, original max
    # positions, beta_fast, beta_slow, mscale, mscale_all_dim) (ops/rope.py
    # `yarn_inv_freq`, `yarn_mscale`); the scores are scaled by the square of
    # yarn_mscale(factor, mscale_all_dim). None: theta alone
    rope_yarn: Optional[Tuple[float, int, float, float, float, float]] = None

    def __post_init__(self) -> None:
        kinds = tuple(self.layer_kinds)
        object.__setattr__(self, "layer_kinds", kinds)
        if self.norm_place not in ("pre", "post", "both"):
            raise ValueError(f"unknown norm_place {self.norm_place!r}")
        if self.post_norm and self.norm_place == "both":
            raise ValueError('`post_norm` is `norm_place="post"`; the model '
                             'says "both"')
        if self.post_norm:
            object.__setattr__(self, "norm_place", "post")
        object.__setattr__(self, "post_norm", self.norm_place == "post")
        bad = sorted(set(kinds) - set(LAYER_KINDS))
        if bad or len(kinds) != self.n_layers:
            raise ValueError(
                f"layer_kinds must name one of {LAYER_KINDS} for each of "
                f"{self.n_layers} layers; got {len(kinds)} with {bad}")
        for kind, needs in (("gmu", "mamba"), ("cross", "full")):
            if kind in kinds and needs not in kinds[:kinds.index(kind)]:
                raise ValueError(f"a {kind!r} layer needs a {needs!r} "
                                 "layer before it")
        if {"window", "swa"} & set(kinds) and self.window <= 0:
            raise ValueError("window layers need `window` > 0")
        if set(kinds) & set(_DIFFERENTIAL) and (
                self.n_heads % 2 or self.kv_heads % 2
                or (self.n_heads // 2) % (self.kv_heads // 2)):
            raise ValueError("differential attention pairs heads: n_heads "
                             "and kv_heads even, q pairs a multiple of kv "
                             "pairs")
        if "gdn" in kinds and not (self.gdn_heads and self.gdn_key_dim
                                   and self.gdn_value_dim):
            raise ValueError("gdn layers need `gdn_heads`, `gdn_key_dim` "
                             "and `gdn_value_dim`")
        if (self.gdn_channel_rank or self.gdn_gate_rank) and "gdn" not in kinds:
            raise ValueError("`gdn_channel_rank` and `gdn_gate_rank` are the "
                             "gdn kind's: no gdn layer in `layer_kinds`")
        if self.attn_gate and not {"attn", "swa"} & set(kinds):
            raise ValueError("`attn_gate` gates the attn and swa kinds' "
                             "heads: neither is in `layer_kinds`")
        if "ssd" in kinds and not (
                self.ssm_heads and self.ssm_groups and self.ssm_inner
                and self.ssm_inner % self.ssm_heads == 0
                and self.ssm_heads % self.ssm_groups == 0):
            raise ValueError("ssd layers need `ssm_heads` that divide "
                             "`ssm_inner` and `ssm_groups` that divide them")
        # ONE pool and one array of conv tails: their rows are one shape
        # ("attn" beside "swa" is two page spaces of ONE row shape)
        for a, b, what in (("attn", "full", "layers that cache keys"),
                           ("swa", "full", "layers that cache keys"),
                           ("swa", "window", "window layers' keys"),
                           ("conv", "mamba", "convolution tails"),
                           ("conv", "gdn", "convolution tails"),
                           ("mamba", "gdn", "convolution tails"),
                           ("conv", "ssd", "convolution tails"),
                           ("mamba", "ssd", "convolution tails"),
                           ("gdn", "ssd", "convolution tails")):
            if a in kinds and b in kinds:
                raise ValueError(f"{a!r} and {b!r} layers in one stack: "
                                 f"two shapes of {what}")
        if self.router not in ("softmax", "sigmoid", "softmax_all"):
            raise ValueError(f"unknown router {self.router!r}")
        if self.router_input not in ("ffn", "layer"):
            raise ValueError(f"unknown router_input {self.router_input!r}")
        if self.router_input == "layer" and (
                "mla2" in kinds or self.norm_place != "pre"
                or self.n_dense_layers
                or self.d_ff_shared):
            raise ValueError(
                'router_input="layer" is written for layers of one mixer and '
                "one expert half, norms first")
        latent = sorted(set(kinds) & set(LATENT_KINDS))
        if latent:
            if len(set(kinds)) > 1:
                raise ValueError(f"{latent[0]!r} layers beside other kinds in "
                                 "one stack: two shapes of pool rows")
            if not (self.kv_lora_rank and self.qk_nope_dim
                    and self.qk_rope_dim and self.v_head_dim
                    ) or self.qk_rope_dim % 2:
                raise ValueError(
                    "latent attention needs `kv_lora_rank`, `qk_nope_dim`, "
                    "an even `qk_rope_dim` and `v_head_dim`")
        if "mla2" in kinds:
            if not self.q_lora_rank:
                raise ValueError("mla2 layers need `q_lora_rank`")
            if not self.is_moe or self.n_dense_layers or self.d_ff_shared:
                raise ValueError("an mla2 layer carries its experts: "
                                 "`num_experts` > 0, no leading dense "
                                 "layers and no shared experts")
        if self.d_ff_shared and not self.is_moe:
            raise ValueError("shared experts stand beside routed ones: "
                             "`d_ff_shared` needs `num_experts` > 0")
        if self.hc_streams < 1 or self.hc_sinkhorn_iters < 1:
            raise ValueError("`hc_streams` and `hc_sinkhorn_iters` are "
                             "counts: 1 or more")
        if self.hc_streams > 1 and ("mla2" in kinds
                                    or self.router_input == "layer"):
            raise ValueError(
                "`hc_streams` > 1 is written round a layer's two sublayers "
                "(a mixer, then its second half): not round an mla2 double "
                'block, nor where the router reads the layer\'s input '
                '(router_input="layer")')
        if self.mtp_depth not in (0, 1):
            raise ValueError("`mtp_depth` is 0 or 1: one multi-token "
                             f"prediction block is written, not {self.mtp_depth}")
        if self.mtp_depth and (kinds[-1:] == ("mla2",) or not self.is_moe):
            raise ValueError(
                "`mtp_depth`: the prediction block is one more layer of the "
                "stack's last kind with an expert second half: it needs "
                "`num_experts` > 0 and a last layer that is not mla2")
        if self.rope_yarn is not None:
            object.__setattr__(self, "rope_yarn", tuple(self.rope_yarn))
            if set(kinds) != {"mla"} or len(self.rope_yarn) != 6:
                raise ValueError(
                    "`rope_yarn` (factor, original max positions, beta_fast, "
                    "beta_slow, mscale, mscale_all_dim) scales the rotary "
                    'lanes of "mla" layers; the other kinds\' tables take '
                    "theta alone")
        if self.experts_first + self.num_experts > self.experts_routed:
            raise ValueError(
                f"experts {self.experts_first}.. of {self.num_experts} held "
                f"lie past the {self.experts_routed} routed ones")
        if self.counts_choices and (self.capacity_factor
                                    * self.num_selected_experts
                                    < self.num_experts - 1e-6):
            raise ValueError(
                "a layer that holds a share of the experts, or identity "
                "experts, has the dropless form alone: capacity_factor >= "
                "num_experts / num_selected_experts")
        if self.counts_choices and self.router == "softmax":
            raise ValueError(
                'router="softmax" renormalises over the chosen experts, '
                "which a layer that holds a share of them cannot: "
                '"softmax_all" or "sigmoid"')

    is_stack = True

    @property
    def untrainable(self) -> str:
        """Why this stack cannot be trained ("": it can): its kinds that
        have no backward yet, each with the op that lacks one."""
        bad = sorted(set(self.layer_kinds) - set(TRAINABLE_KINDS))
        if not bad:
            return ""
        return (f"{self.name!r}, a stack of unlike layers, cannot be trained "
                "yet: a stack trains where every layer is one of "
                f"{TRAINABLE_KINDS} (with a dense or an expert second half); "
                "there is no backward through "
                + ", ".join(f"{k!r} ({_NO_BACKWARD[k]})" for k in bad))

    @property
    def has_state(self) -> bool:
        """Some layer keeps per-sequence state that is not keys and values
        in THE pool: conv tails, scan state, a delta-rule or state-space
        state matrix, a window layer's ring."""
        return bool({"mamba", "conv", "window", "gdn", "swa", "ssd"}
                    & set(self.layer_kinds))

    @property
    def window_paged(self) -> bool:
        """The window layers' keys live in a second page space that the
        engine's allocator serves (the "swa" kind), and not in a ring a
        decode slot owns (the "window" kind)."""
        return "swa" in self.layer_kinds

    @property
    def window_cache_dims(self) -> Tuple[int, int, int]:
        """(layers, kv heads, head size) of the window page space: the
        "swa" layers' own heads, THE pool's row shape."""
        return self.count("swa"), self.kv_heads, self.hdim

    @property
    def expert_ff(self) -> int:
        return self.d_ff_expert or self.d_ff

    @property
    def experts_routed(self) -> int:
        return self.n_routed_experts or self.num_experts

    @property
    def latent_cache(self) -> bool:
        """The pool is ONE array of latent rows (there is no pool of
        values: a value is its row's leading `kv_lora_rank` lanes)."""
        return bool(set(LATENT_KINDS) & set(self.layer_kinds))

    @property
    def latent_row(self) -> int:
        """Lanes of a token's row in the latent pool."""
        used = self.kv_lora_rank + self.qk_rope_dim
        return -(-used // _LANES) * _LANES

    @property
    def conv_tail(self) -> Tuple[int, int, int]:
        """(layers, rows, width) of the convolution tails a sequence keeps:
        the last taps - 1 inputs of each mamba, conv, gdn or ssd layer's
        convolution (a gdn layer's runs over its q, k and v channels, an
        ssd layer's over x and every group's B and C)."""
        if "conv" in self.layer_kinds:
            return self.count("conv"), self.conv_taps - 1, self.d_model
        if "ssd" in self.layer_kinds:
            _, _, _, N, G = self.ssd_dims
            return (self.count("ssd"), self.ssm_conv - 1,
                    self.ssm_inner + 2 * G * N)
        if "gdn" in self.layer_kinds:
            _, H, dk, dv = self.gdn_dims
            return self.count("gdn"), self.conv_taps - 1, H * (2 * dk + dv)
        return self.count("mamba"), self.ssm_conv - 1, self.ssm_inner

    @property
    def gdn_dims(self) -> Tuple[int, int, int, int]:
        """(layers, heads, key size, value size) of the delta-rule state a
        sequence keeps: a float32 [key, value] matrix a head and gdn
        layer (ops/gdn.py `state_shape` lays them out)."""
        return (self.count("gdn"), self.gdn_heads, self.gdn_key_dim,
                self.gdn_value_dim)

    @property
    def ssd_dims(self) -> Tuple[int, int, int, int, int]:
        """(layers, heads, head size, state size, groups) of the
        state-space state a sequence keeps: a float32 [state, head] matrix
        a head and ssd layer (ops/ssd.py `state_shape` lays them out)."""
        return (self.count("ssd"), self.ssm_heads,
                self.ssm_inner // max(self.ssm_heads, 1), self.ssm_state,
                self.ssm_groups)

    @property
    def pool_heads(self) -> int:
        """KV heads as a cache holds them: a differential pair is one row."""
        return self.kv_heads // 2

    @property
    def pool_dim(self) -> int:
        return self.hdim * 2

    @property
    def cache_dims(self) -> Tuple[int, int, int]:
        """The layers that cache keys alone: the "attn" layers' own heads,
        or the "full" layers', a differential pair a head."""
        if "attn" in self.layer_kinds:
            return self.count("attn"), self.kv_heads, self.hdim
        if self.latent_cache:  # one row a token and attention
            return (sum(n * self.count(k) for k, n in LATENT_KINDS.items()),
                    1, self.latent_row)
        return self.count("full"), self.pool_heads, self.pool_dim

    def _mixer_params(self, kind: str) -> int:
        D, H, KVH, hd = self.d_model, self.n_heads, self.kv_heads, self.hdim
        Di, N, R = self.ssm_inner, self.ssm_state, self.ssm_dt_rank
        # q and o with biases, four lambda vectors, the pair norm's weight
        q_o = 2 * D * H * hd + H * hd + D + 4 * hd + 2 * hd
        if kind in ("attn", "swa"):
            qk = ((H + KVH) * hd if self.qk_norm_whole
                  else 2 * hd if self.qk_norm else 0)
            gate = D * H * hd if self.attn_gate else 0
            return 2 * D * H * hd + 2 * D * KVH * hd + qk + gate
        if kind == "gdn":
            _, Hg, dk, dv = self.gdn_dims
            rc, rg = self.gdn_channel_rank, self.gdn_gate_rank
            # a and b and a decay's A_log and dt_bias a head, or the
            # low-rank pair, b, A_log a head and dt_bias a key lane
            decay = (rc * (D + Hg * dk) + D * Hg + Hg + Hg * dk if rc
                     else 2 * D * Hg + 2 * Hg)
            # the full gate, or its low-rank pair and bias
            gate = rg * (D + Hg * dv) + Hg * dv if rg else D * Hg * dv
            # q, k, v in one projection and their taps; the out-projection;
            # the output norm
            return ((D + self.conv_taps) * Hg * (2 * dk + dv)
                    + D * Hg * dv + decay + gate + dv)
        if kind == "conv":
            return D * 3 * D + self.conv_taps * D + D * D
        if kind == "ssd":
            _, Hs, _, _, G = self.ssd_dims
            conv = Di + 2 * G * N
            # z, x, B, C and dt in; taps and their bias; dt_bias, A_log, D;
            # the gated norm; the out-projection
            return (D * (Di + conv + Hs) + (self.ssm_conv + 1) * conv
                    + 3 * Hs + Di + Di * D)
        if kind in LATENT_KINDS:
            ql, kl = self.q_lora_rank, self.kv_lora_rank
            qk = self.qk_nope_dim + self.qk_rope_dim
            # the queries through a normed bottleneck, or one projection
            q = D * ql + ql + ql * H * qk if ql else D * H * qk
            mla = (q + D * (kl + self.qk_rope_dim)
                   + kl + kl * H * (self.qk_nope_dim + self.v_head_dim)
                   + H * self.v_head_dim * D)
            if kind == "mla":
                return mla
            # two attentions and two dense FFNs with a norm before each;
            # `param_count` adds the experts and the layer's two norms
            return 2 * (mla + 3 * D * self.d_ff + D)
        if kind == "mamba":
            return (D * 2 * Di + Di * (self.ssm_conv + 1) + Di * (R + 2 * N)
                    + R * Di + Di + N * Di + Di + Di * D)
        if kind == "gmu":
            return 2 * D * Di
        if kind == "cross":
            return q_o
        return q_o + 2 * D * KVH * hd + 2 * KVH * hd

    def param_count(self) -> int:
        """Parameter count of the mixed stack (the tied table once)."""
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        E, Fe = self.num_experts, self.expert_ff
        norm = D * (2 if self.norm == "layernorm" else 1)
        W = self.router_width
        half = {"ffn": 3 * D * F,
                "moe": E * 3 * D * Fe + D * W
                + (W if self.router != "softmax" else 0)
                + 3 * D * self.d_ff_shared}
        norms = (4 if self.norm_place == "both" else 2) * norm
        n = self.hc_streams
        if n > 1:  # the residual path round both sublayers: phi, b, 3 scalars
            norms += 2 * ((n * D + 1) * (n * n + 2 * n) + 3)
        # the prediction block: a last layer over again with experts, the
        # projection of two normed halves, its own final norm
        mtp = self.mtp_depth * (self._mixer_params(self.layer_kinds[-1])
                                + half["moe"] + norms + 2 * D * D + 3 * norm)
        return (sum(self._mixer_params(k) for k in self.layer_kinds)
                + sum(half[h] + norms for h in self.second_halves) + mtp
                + V * D * (1 if self.tie_embeddings else 2) + norm)


_REGISTRY = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def list_configs():
    return sorted(_REGISTRY)


# --- BASELINE.md workload configs -----------------------------------------

register(ModelConfig(
    name="gpt2-125m",
    vocab_size=50257,
    d_model=768, n_layers=12, n_heads=12, d_ff=3072,
    max_seq_len=1024,
    norm="layernorm", activation="gelu", positional="learned",
    tie_embeddings=True,
))

register(ModelConfig(
    name="llama3-8b",
    vocab_size=128256,
    d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
    max_seq_len=8192,
    norm="rmsnorm", activation="swiglu", positional="rope",
    rope_theta=500000.0, norm_eps=1e-5,
))

register(ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32000,
    d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
    max_seq_len=8192,
    norm="rmsnorm", activation="swiglu", positional="rope",
    rope_theta=1000000.0,
    num_experts=8, num_selected_experts=2,
))

register(ModelConfig(
    name="llama-600m",
    # Llama-3 family member sized so f32 master params + Adam moments fit a
    # single 16GB v5e chip — the single-chip flagship-entry config.
    vocab_size=32000,
    d_model=1536, n_layers=16, n_heads=12, n_kv_heads=4,
    head_dim=128, d_ff=6144,
    max_seq_len=4096,
    norm="rmsnorm", activation="swiglu", positional="rope",
    rope_theta=500000.0,
))

register(ModelConfig(
    name="llama-2b",
    # ~2B Llama-3 family member: the single-chip scale stepping stone
    # toward llama3-8b (BASELINE.md workload #2). remat (on by default;
    # what it keeps is a rule on shapes and memory: `ModelConfig.remat`)
    # plus a FACTORED optimizer (train.lm.make_optimizer(factored=True),
    # adafactor second moments) is what fits f32 master state + grads in
    # one 16GB v5e chip — adamw moments alone would be 2x params.
    vocab_size=32000,
    d_model=2560, n_layers=24, n_heads=20, n_kv_heads=5,
    head_dim=128, d_ff=6912,
    max_seq_len=4096,
    norm="rmsnorm", activation="swiglu", positional="rope",
    rope_theta=500000.0,
))

# tiny variants for tests / CPU-mesh dry runs
register(ModelConfig(
    name="tiny-llama",
    vocab_size=512,
    d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=128, dtype="float32", remat=False,
))

register(ModelConfig(
    name="tiny-gpt2",
    vocab_size=512,
    d_model=64, n_layers=2, n_heads=4, d_ff=128,
    max_seq_len=128,
    norm="layernorm", activation="gelu", positional="learned",
    tie_embeddings=True, dtype="float32", remat=False,
))

register(ModelConfig(
    name="tiny-moe",
    vocab_size=512,
    d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=128,
    max_seq_len=128,
    num_experts=4, num_selected_experts=2, dtype="float32", remat=False,
))


def _sambay_kinds(n_layers: int) -> Tuple[str, ...]:
    """SambaY's order: mamba / window pairs up to the middle, then one mamba
    and THE full-attention layer, then gmu / cross pairs."""
    half = n_layers // 2
    return tuple(
        ("mamba" if l % 2 == 0 else "window") if l < half
        else "mamba" if l == half else "full" if l == half + 1
        else ("gmu" if l % 2 == 0 else "cross")
        for l in range(n_layers))


register(StackConfig(
    name="phi4-mini-flash",
    # microsoft/Phi-4-mini-flash-reasoning (arXiv:2507.06607): 3.85 B
    # parameters, no positional encoding, one KV cache read by 8 layers
    vocab_size=200064,
    d_model=2560, n_layers=32, n_heads=40, n_kv_heads=20, head_dim=64,
    d_ff=10240, max_seq_len=262144,
    norm="layernorm", activation="swiglu", positional="none",
    tie_embeddings=True, norm_eps=1e-5,
    layer_kinds=_sambay_kinds(32), window=512, ssm_inner=5120, ssm_state=16, ssm_conv=4,
    ssm_dt_rank=160,
))

def _lfm2_kinds(n_layers: int) -> Tuple[str, ...]:
    """LFM2-8B-A1B's published `layer_types`, cut to its first layers."""
    attn = (2, 6, 10, 14, 18, 21)
    return tuple("attn" if l in attn else "conv" for l in range(n_layers))


register(StackConfig(
    name="lfm2-8b-a1b",
    # LiquidAI/LFM2-8B-A1B: 8.3 B parameters, 1.5 B active a token: 18
    # gated short convolutions and 6 GQA layers (heads of 64, queries and
    # keys normalised), two dense layers and then 32 experts of 1792, 4 a
    # token by sigmoid score + bias
    vocab_size=65536,
    d_model=2048, n_layers=24, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=7168, max_seq_len=128000,
    norm="rmsnorm", activation="swiglu", positional="rope",
    rope_theta=1000000.0, tie_embeddings=True, norm_eps=1e-5,
    num_experts=32, num_selected_experts=4, capacity_factor=8.0,
    layer_kinds=_lfm2_kinds(24), conv_taps=3, qk_norm=True,
    n_dense_layers=2, d_ff_expert=1792, router="sigmoid",
))

register(StackConfig(
    name="tiny-lfm2",
    # the same stack's shape at toy widths: two dense conv layers, then
    # two attn / conv / conv / conv periods of expert layers
    vocab_size=512,
    d_model=64, n_layers=10, n_heads=8, n_kv_heads=4, head_dim=8, d_ff=128,
    max_seq_len=128, dtype="float32", remat=False,
    norm="rmsnorm", activation="swiglu", positional="rope",
    rope_theta=10000.0, tie_embeddings=True, norm_eps=1e-5,
    num_experts=8, num_selected_experts=2, capacity_factor=4.0,
    layer_kinds=_lfm2_kinds(10), conv_taps=3, qk_norm=True,
    n_dense_layers=2, d_ff_expert=32, router="sigmoid",
))

def _olmo_hybrid_kinds(n_layers: int) -> Tuple[str, ...]:
    """Three gated delta-rule layers, then one of full attention."""
    return tuple("attn" if l % 4 == 3 else "gdn" for l in range(n_layers))


register(StackConfig(
    name="olmo-hybrid-7b",
    # allenai/Olmo-Hybrid-7B: 7.4 B parameters; 24 gated delta-rule layers
    # (30 heads, a 96 x 192 state matrix each) and 8 of full attention with
    # no positional encoding (30 heads of 128, no GQA), norms after their
    # sublayers, untied head
    vocab_size=100352,
    d_model=3840, n_layers=32, n_heads=30, n_kv_heads=30, head_dim=128,
    d_ff=11008, max_seq_len=65536,
    norm="rmsnorm", activation="swiglu", positional="none",
    tie_embeddings=False, norm_eps=1e-6,
    layer_kinds=_olmo_hybrid_kinds(32), conv_taps=4, qk_norm=True,
    qk_norm_whole=True, post_norm=True, gdn_heads=30, gdn_key_dim=96,
    gdn_value_dim=192, gdn_neg_eigval=True,
))

register(StackConfig(
    name="tiny-olmo-hybrid",
    # the same stack's shape at toy widths: two gdn / gdn / gdn / attn
    # periods
    vocab_size=512,
    d_model=64, n_layers=8, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
    max_seq_len=128, dtype="float32", remat=False,
    norm="rmsnorm", activation="swiglu", positional="none",
    tie_embeddings=False, norm_eps=1e-6,
    layer_kinds=_olmo_hybrid_kinds(8), conv_taps=4, qk_norm=True,
    qk_norm_whole=True, post_norm=True, gdn_heads=4, gdn_key_dim=8,
    gdn_value_dim=16, gdn_neg_eigval=True,
))

def _window_full_kinds(n_layers: int) -> Tuple[str, ...]:
    """One layer of full attention, then three over a window."""
    return tuple("attn" if l % 4 == 0 else "swa" for l in range(n_layers))


register(StackConfig(
    name="smallthinker-21b-a3b",
    # PowerInfer/SmallThinker-21BA3B-Instruct: 21.5 B parameters, 3 B active
    # a token: 13 layers of full attention that encode no position and 39
    # rotary layers over a window of 4096 (28 heads of 128 over 4 KV heads,
    # each layer its own keys), and in every layer 64 ReGLU experts of 768,
    # 6 a token, chosen from the layer's input before the attention runs
    vocab_size=151936,
    d_model=2560, n_layers=52, n_heads=28, n_kv_heads=4, head_dim=128,
    d_ff=768, max_seq_len=16384,
    norm="rmsnorm", activation="reglu", positional="none",
    rope_theta=1500000.0, tie_embeddings=False, norm_eps=1e-6,
    num_experts=64, num_selected_experts=6, capacity_factor=64 / 6,
    layer_kinds=_window_full_kinds(52), window=4096, router_input="layer",
))

register(StackConfig(
    name="tiny-smallthinker",
    # the same stack's shape at toy widths: two attn / swa / swa / swa
    # periods over a window of 16, 8 experts top 3
    vocab_size=512,
    d_model=64, n_layers=8, n_heads=8, n_kv_heads=2, head_dim=8, d_ff=32,
    max_seq_len=128, dtype="float32", remat=False,
    norm="rmsnorm", activation="reglu", positional="none",
    rope_theta=10000.0, tie_embeddings=False, norm_eps=1e-6,
    num_experts=8, num_selected_experts=3, capacity_factor=8 / 3,
    layer_kinds=_window_full_kinds(8), window=16, router_input="layer",
))

def _granite_hybrid_kinds(n_layers: int) -> Tuple[str, ...]:
    """granite-4.0-h-micro's published `layer_types`, cut to its first
    layers: attention at 5, 15, 25, 35."""
    return tuple("attn" if l % 10 == 5 else "ssd" for l in range(n_layers))


register(StackConfig(
    name="granite-4.0-h-micro",
    # ibm-granite/granite-4.0-h-micro: 3.19 B parameters; 36 Mamba-2 layers
    # (64 heads of 64, one scalar decay each, a 128 x 64 state matrix a
    # head, B and C shared by all heads) and 4 of GQA (32 / 8 heads of 64)
    # with no positional encoding, a dense SwiGLU of 8192 in every layer,
    # tied table, and Granite's four scalars
    vocab_size=100352,
    d_model=2048, n_layers=40, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=8192, max_seq_len=131072,
    norm="rmsnorm", activation="swiglu", positional="none",
    tie_embeddings=True, norm_eps=1e-5,
    layer_kinds=_granite_hybrid_kinds(40), ssm_inner=4096, ssm_state=128,
    ssm_conv=4, ssm_heads=64, ssm_groups=1,
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=8.0,
))

register(StackConfig(
    name="tiny-granite-hybrid",
    # the same stack's shape at toy widths: attention at 5, two groups
    vocab_size=512,
    d_model=64, n_layers=8, n_heads=8, n_kv_heads=2, head_dim=8, d_ff=128,
    max_seq_len=128, dtype="float32", remat=False,
    norm="rmsnorm", activation="swiglu", positional="none",
    tie_embeddings=True, norm_eps=1e-5,
    layer_kinds=_granite_hybrid_kinds(8), ssm_inner=128, ssm_state=16,
    ssm_conv=4, ssm_heads=8, ssm_groups=2,
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=8.0,
))

register(StackConfig(
    name="tiny-sambay",
    # the same stack's shape at toy widths: three mamba / window pairs,
    # the one-off mamba and full layers at 6 and 7, two gmu / cross pairs
    vocab_size=512,
    d_model=64, n_layers=12, n_heads=8, n_kv_heads=4, head_dim=8, d_ff=128,
    max_seq_len=128, dtype="float32", remat=False,
    norm="layernorm", activation="swiglu", positional="none",
    tie_embeddings=True, norm_eps=1e-5,
    layer_kinds=_sambay_kinds(12), window=8, ssm_inner=128, ssm_state=4, ssm_conv=4, ssm_dt_rank=4,
))

register(StackConfig(
    name="longcat-flash",
    # meituan-longcat/LongCat-Flash-Omni's language model (560 B, 27 B
    # active): 28 double layers of latent attention (64 heads, a 512 + 64
    # latent row a token), dense FFNs of 12288, and a shortcut expert layer
    # of 512 experts of 2048 beside 256 identity experts, 12 a token by
    # softmax score + bias, the chosen scores times 6 and not renormalised.
    # No chip holds a layer: a deployment's chip holds a share of the
    # experts (`num_experts` of `n_routed_experts`, from `experts_first`)
    vocab_size=131072,
    d_model=6144, n_layers=28, n_heads=64, d_ff=12288, max_seq_len=131072,
    norm="rmsnorm", activation="swiglu", positional="none",
    rope_theta=10000000.0, tie_embeddings=False, norm_eps=1e-5,
    num_experts=16, num_selected_experts=12, capacity_factor=16 / 12,
    layer_kinds=("mla2",) * 28, d_ff_expert=2048, router="softmax_all",
    norm_topk=False, routed_scale=6.0, n_routed_experts=512,
    experts_zero=256, q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
    qk_rope_dim=64, v_head_dim=128, mla_scale_lora=True,
))

register(StackConfig(
    name="tiny-longcat-flash",
    # the same stack's shape at toy widths: two double layers, 4 of 8
    # routed experts held beside 4 identity experts, 3 a token
    vocab_size=512,
    d_model=64, n_layers=2, n_heads=4, d_ff=128, max_seq_len=128,
    dtype="float32", remat=False,
    norm="rmsnorm", activation="swiglu", positional="none",
    rope_theta=10000.0, tie_embeddings=False, norm_eps=1e-5,
    num_experts=4, num_selected_experts=3, capacity_factor=4 / 3,
    layer_kinds=("mla2",) * 2, d_ff_expert=32, router="softmax_all",
    norm_topk=False, routed_scale=6.0, n_routed_experts=8, experts_first=0,
    experts_zero=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=16, mla_scale_lora=True,
))

register(StackConfig(
    name="kanana-2-30b-a3b",
    # kakaocorp/kanana-2-30b-a3b-instruct-2601 (the DeepSeek-V3 block): 30.7 B
    # parameters, 3 B active a token: 48 layers of latent attention (32
    # heads, queries projected directly, a 512 + 64 latent row a token), one
    # leading dense SwiGLU of 6144 and then 128 experts of 768, 6 a token by
    # sigmoid score + bias, renormalised, times 2.448, beside two shared
    # experts (one SwiGLU of 1536) that every token passes through
    vocab_size=128256,
    d_model=2048, n_layers=48, n_heads=32, d_ff=6144, max_seq_len=32768,
    norm="rmsnorm", activation="swiglu", positional="none",
    rope_theta=1000000.0, tie_embeddings=False, norm_eps=1e-6,
    num_experts=128, num_selected_experts=6, capacity_factor=128 / 6,
    layer_kinds=("mla",) * 48, n_dense_layers=1, d_ff_expert=768,
    d_ff_shared=1536, router="sigmoid", norm_topk=True, routed_scale=2.448,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
))

register(StackConfig(
    name="tiny-kanana",
    # the same stack's shape at toy widths: one dense layer, then three
    # expert layers of 8 experts top 3 beside a shared pair
    vocab_size=512,
    d_model=64, n_layers=4, n_heads=4, d_ff=128, max_seq_len=128,
    dtype="float32", remat=False,
    norm="rmsnorm", activation="swiglu", positional="none",
    rope_theta=10000.0, tie_embeddings=False, norm_eps=1e-6,
    num_experts=8, num_selected_experts=3, capacity_factor=8 / 3,
    layer_kinds=("mla",) * 4, n_dense_layers=1, d_ff_expert=32,
    d_ff_shared=64, router="sigmoid", norm_topk=True, routed_scale=2.448,
    kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
))


def _trinity_kinds(n_layers: int) -> Tuple[str, ...]:
    """Three layers over a window, then one of full attention."""
    return tuple("attn" if l % 4 == 3 else "swa" for l in range(n_layers))


register(StackConfig(
    name="trinity-mini",
    # arcee-ai/Trinity-Mini (`afmoe`): 26 B parameters, about 3 B active a
    # token: 32 layers of gated GQA (32 heads of 128 over 4 KV heads, queries
    # and keys normalised per head, the heads' outputs times sigmoid(x W_g)),
    # three rotary ones over a window of 2048 then one over every key with
    # NO positions; a norm on BOTH sides of every sublayer; two dense layers
    # (6144), then 128 experts of 1024, 8 a token by sigmoid score + bias,
    # renormalised, times 2.826, beside one shared expert; the embedding
    # times sqrt(2048). The bias moves towards an even load in training
    vocab_size=200192,
    d_model=2048, n_layers=32, n_heads=32, n_kv_heads=4, head_dim=128,
    d_ff=6144, max_seq_len=131072,
    norm="rmsnorm", activation="swiglu", positional="none",
    rope_theta=10000.0, tie_embeddings=False, norm_eps=1e-5,
    num_experts=128, num_selected_experts=8, capacity_factor=128 / 8,
    router_aux_coef=0.0,
    layer_kinds=_trinity_kinds(32), window=2048, qk_norm=True,
    attn_gate=True, norm_place="both", n_dense_layers=2, d_ff_expert=1024,
    d_ff_shared=1024, router="sigmoid", norm_topk=True, routed_scale=2.826,
    router_bias_rate=0.001, embedding_multiplier=2048 ** 0.5,
))

register(StackConfig(
    name="tiny-trinity",
    # the same stack's shape at toy widths: two swa / swa / swa / attn
    # periods over a window of 16, two dense layers, then 8 experts top 2
    # beside a shared one
    vocab_size=512,
    d_model=128, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
    max_seq_len=512, dtype="float32", remat=False,
    norm="rmsnorm", activation="swiglu", positional="none",
    rope_theta=10000.0, tie_embeddings=False, norm_eps=1e-5,
    num_experts=8, num_selected_experts=2, capacity_factor=8 / 2,
    router_aux_coef=0.0,
    layer_kinds=_trinity_kinds(8), window=16, qk_norm=True,
    attn_gate=True, norm_place="both", n_dense_layers=2, d_ff_expert=128,
    d_ff_shared=128, router="sigmoid", norm_topk=True, routed_scale=2.826,
    router_bias_rate=0.001, embedding_multiplier=128 ** 0.5,
))


register(StackConfig(
    name="xing4.0-29b-a4b",
    # XingChen-AGI/Xing4.0-29B-A4B (`xing4_0`): 29 B parameters, about 4 B
    # active a token: the DeepSeek-V3 block (40 layers of latent attention,
    # 32 heads of 128 + 64 against values of 128, queries through a
    # bottleneck of 768, a 512 + 64 latent row; two dense layers of 9216,
    # then 64 experts of 1024, 4 a token by sigmoid score + bias,
    # renormalised, times 2, beside one shared expert) inside FOUR residual
    # streams mixed round every sublayer (mHC: 20 Sinkhorn rounds), yarn
    # rotary lanes (x 64 over 4096) and one multi-token prediction block
    vocab_size=131072,
    d_model=3584, n_layers=40, n_heads=32, d_ff=9216, max_seq_len=262144,
    norm="rmsnorm", activation="swiglu", positional="none",
    rope_theta=10000.0, tie_embeddings=False, norm_eps=1e-6,
    num_experts=64, num_selected_experts=4, capacity_factor=64 / 4,
    router_aux_coef=0.0,
    layer_kinds=("mla",) * 40, n_dense_layers=2, d_ff_expert=1024,
    d_ff_shared=1024, router="sigmoid", norm_topk=True, routed_scale=2.0,
    router_bias_rate=0.001, q_lora_rank=768, kv_lora_rank=512,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    hc_streams=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    hc_res_clamp=(-30.0, 30.0), mtp_depth=1, mtp_weight=0.1,
    rope_yarn=(64.0, 4096, 32.0, 1.0, 1.0, 1.0),
))

register(StackConfig(
    name="tiny-xing4",
    # the same stack's shape at toy widths: one dense layer, then three
    # expert layers of 8 experts top 2 beside a shared one, four streams,
    # yarn over 32 positions stretched 8 times, a prediction block
    vocab_size=512,
    d_model=128, n_layers=4, n_heads=4, d_ff=256, max_seq_len=512,
    dtype="float32", remat=False,
    norm="rmsnorm", activation="swiglu", positional="none",
    rope_theta=10000.0, tie_embeddings=False, norm_eps=1e-6,
    num_experts=8, num_selected_experts=2, capacity_factor=8 / 2,
    router_aux_coef=0.0,
    layer_kinds=("mla",) * 4, n_dense_layers=1, d_ff_expert=128,
    d_ff_shared=128, router="sigmoid", norm_topk=True, routed_scale=2.0,
    router_bias_rate=0.001, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    hc_streams=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    hc_res_clamp=(-30.0, 30.0), mtp_depth=1, mtp_weight=0.1,
    rope_yarn=(8.0, 32, 32.0, 1.0, 1.0, 1.0),
))
