"""ray_tpu.ops — TPU Pallas kernels for the hot ops, with XLA fallbacks.

The reference delegates its hot math to cuBLAS/cutlass/flash-attn CUDA
kernels inside the frameworks it orchestrates; here the compute path is
owned by this package: Pallas kernels tuned for the MXU/VMEM hierarchy on
TPU, and pure-XLA blockwise fallbacks that run anywhere (CPU tests, and
shapes the kernels don't cover).

Dispatch policy: selection happens per *lowering platform* inside each op
(`dispatch.platform_dispatch`): the Pallas kernel when compiling for TPU
and shapes satisfy kernel tiling constraints, the XLA fallback on every
other platform. One process can therefore mix a real TPU and a virtual
CPU mesh. Set RAY_TPU_FORCE_PALLAS=0/1 to override globally.
"""

from .attention import flash_attention, mha_reference  # noqa: F401
from .gdn import gdn_chunk, gdn_step  # noqa: F401
from .mla_attention import (  # noqa: F401
    latent_attention_chunk,
    latent_attention_decode,
    write_latent_then_attend,
)
from .moe import (  # noqa: F401
    expert_groups,
    expert_step,
    gather_rows,
    group_rows,
    grouped_ffn,
    grouped_fits,
    grouped_rows_bound,
    grouped_tile,
    groups_fit,
    groups_rows_bound,
)
from .norm import layer_norm, rms_norm, rms_norm_reference  # noqa: F401
from .rope import apply_rope, rope_frequencies  # noqa: F401
from .paged_attention import (  # noqa: F401
    gather_pages,
    paged_attention_chunk,
    paged_attention_decode,
    paged_attention_verify,
    pool_shape,
    scatter_pages,
    write_then_attend,
)
