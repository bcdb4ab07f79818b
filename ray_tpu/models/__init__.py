"""ray_tpu.models — built-in decoder-only transformer families.

The reference ships no models of its own (its Train/Serve/RLlib examples
pull torch models from HF/DeepSpeed/vLLM); a TPU-native framework must own
the model zoo, so these are first-class: GPT-2, Llama-3, Mixtral configs
over one sharded JAX transformer, and on the serve path stacks of unlike
layers (`StackConfig`, models/stack.py): `phi4-mini-flash` (Mamba, window
and cross differential attention, gated memory units) and `lfm2-8b-a1b`
(the `"conv"` kind, a gated short convolution, beside `"attn"` layers with
normalised queries and keys; two dense layers, then experts routed by
sigmoid score + bias), each with a tiny twin for the CPU (`tiny-sambay`,
`tiny-lfm2`), and eight more families since (models/config.py registers
them; models/stack.py's header lists the kinds). A stack whose kinds are
all `"attn"`, `"swa"` or `"mla"` (trained: `config.TRAINABLE_KINDS`) goes
through `forward` / `loss_fn` / `param_axes` / `make_train_step` like the
one-block models: `trinity-mini` / `tiny-trinity` (window and full gated
GQA, a norm on both sides of every sublayer, `norm_place="both"` where
`post_norm` is the older word for `"post"`; sigmoid-routed experts beside
a shared one) and `xing4.0-29b-a4b` / `tiny-xing4` (latent attention under
yarn inside four residual streams mixed round every sublayer,
`hc_streams`, with a multi-token prediction block in the loss,
`mtp_depth`; trained only: the engine refuses the streams by name). The
other kinds are served only, and refused by name. The benchmark reaches
them through benchmark/families/ (`mistral.py`, `sambay.py`,
`shortconv_moe.py`, .., `trinity_afmoe.py`, `xing4_mhc.py`).
"""

from .config import (  # noqa: F401
    ModelConfig,
    StackConfig,
    get_config,
    list_configs,
    register,
)
from .generate import generate, sample_token  # noqa: F401
from .transformer import (  # noqa: F401
    decode_step,
    forward,
    init_kv_cache,
    init_params,
    loss_fn,
    loss_from_logits,
    param_axes,
    prefill,
)
