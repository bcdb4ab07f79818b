"""ray_tpu.models — built-in decoder-only transformer families.

The reference ships no models of its own (its Train/Serve/RLlib examples
pull torch models from HF/DeepSpeed/vLLM); a TPU-native framework must own
the model zoo, so these are first-class: GPT-2, Llama-3, Mixtral configs
over one sharded JAX transformer.
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
