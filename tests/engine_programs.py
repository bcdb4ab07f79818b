"""What the tests that pin a program's lowered text share: each family's tiny
engine WITHOUT arrays (`InferenceEngine.abstract`) and the digest of a program
as `InferenceEngine.programs` describes it, which is as the engine calls it.
One process lowers a (family, program) once, whichever files ask."""

import functools
import hashlib

import jax
import pytest

from ray_tpu.models import get_config, init_params, stack
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

PAGE = 4
LOWERED_WITH_JAX = "0.9.0"
# the short names the digest tables go by -> the programs' own
NAMES = {"decode": "decode_span", "chunk": "chunk_prefill_16",
         "bucket": "prefill_bucket_16x1", "verify": "verify_3"}


# sha256 of the StableHLO text of each accepted family's programs, one tiny
# model a family the benchmark holds: the decode program of the plain sampler,
# `chunk_prefill_16`, the bucket program (16 x 1) and, where the family may
# speculate, `verify_3`, lowered for the CPU at `highest` matmul precision
# with jax as pinned above. A PR that adds a family or a form must leave each
# the text it was; one that MEANS to change a program re-pins it here, once
# (until PR 56 three files each held a table, the same value up to three
# times), and says so. Whose text each is: the decode programs PR 53's (the
# span's steps an argument, the layers an inner jit; PR 42's visit in the
# families with experts); the bucket programs the parent's of PR 41, but
# tiny-lfm2's, tiny-longcat-flash's and tiny-smallthinker's PR 43's (each
# expert over the rows that chose it) and tiny-sambay's PR 54's (the window
# through the flash kernels); verify PR 42's parent's; the chunk programs PR
# 56's in the letter and PR 51's in substance: until PR 56 the files lowered a
# chunk program WITHOUT the sampling arguments the engine has handed every
# chunk since PR 51, now it is lowered as it is called, and the parent of PR
# 56 (7f203e2) lowers these very texts through its private builders with the
# engine's full argument lists (30 of 30, both samplers: CHANGES.md, PR 56).
PINNED = {
    ("tiny-llama", "decode"):
        "1429c5d1a5199a98ce7766b3669606a0006fef0510cd95bdd4a8f5c340234831",
    ("tiny-llama", "chunk"):
        "002384587bf312a37cd8c35fe68e542d1f12e84c946f68f969617f33334f32db",
    ("tiny-llama", "bucket"):
        "89e6eca808b25dc8185be474bb000e1754b5f6711e8b35f7c565ac8b23458ea4",
    ("tiny-llama", "verify"):
        "c6b1cc0a49ce4ea71c6f3014aeae0654b4d858206b738123ac99babed7ff9135",
    ("tiny-moe", "decode"):
        "f0880d7635d59adcdb4f2808d0f76d6fe7bd58545d6cfe5a2d42a6901c26aa89",
    ("tiny-moe", "chunk"):
        "75ad464e0b0c439b1a25cae730e638415914624ead604266390ba80eab6d34f6",
    ("tiny-moe", "bucket"):
        "a8d171fe1e3f6467256a9cae983b142d438b55d121b1e140760c6c65e13814a8",
    ("tiny-moe", "verify"):
        "d3757c5f5b5cfb8b9197aa6ddaec8e90f1acc1251c8a48276541d071d5c42e14",
    ("tiny-lfm2", "decode"):
        "81f95d4cf89d01dd776458be381de23baefc0dcf4f347f04ffae631cddfc8d6b",
    ("tiny-lfm2", "chunk"):
        "4422ee96dacd619221e16774168b9c16e03265ae818dc228065cbeaa5f8f7679",
    ("tiny-lfm2", "bucket"):
        "d3a78cc2c6fd2cd4a8f57388f1ad147180733c227c6187a7d0fdc837722b5a31",
    ("tiny-olmo-hybrid", "decode"):
        "6c8b0085e41c557a010de66a11f7dfca080bdcfb838785aa5578b4da8628d532",
    ("tiny-olmo-hybrid", "chunk"):
        "b93dca6d2b0f5c78d0ab36d8f1b6bb9f2d9caec0962bdad8fdc0070cea50455b",
    ("tiny-olmo-hybrid", "bucket"):
        "c9e003a5a9dcef0baebf4c2e4cae0be7a651aeb41769d73009ed6d2eca7b317e",
    ("tiny-sambay", "decode"):
        "c74594983bf6d369b488a7ee13726586c7907ffae2a3459092d48867ad1ab0b0",
    ("tiny-sambay", "chunk"):
        "f5eb99aecb21b8ba691983d01aeba2834ca4ac2e72a2bc6804b97c91081baa18",
    ("tiny-sambay", "bucket"):
        "7ae5286ce38bd935816614228c1e23ae5f6f757d07bfbe443411142401834a6e",
    ("tiny-longcat-flash", "decode"):
        "316f8553de71700017a500817d535080a08455ec449a0488d2e41f78842f8100",
    ("tiny-longcat-flash", "chunk"):
        "8d0be2d77698d6fbc43fde86030f85c41b80d1e8e6c2d00772d2451bc897f76e",
    ("tiny-longcat-flash", "bucket"):
        "bd8fd2bc1a51d7aef7f731156f94709dc1112598c675a314cc5ac19ce0ae55d7",
    ("tiny-smallthinker", "decode"):
        "0fdf84add6bc9b97ee0122983b552421a63ef37bc24a834fef9cc3fcfe12e0cd",
    ("tiny-smallthinker", "chunk"):
        "2d235a0b0349af95be578e3c196a64b50004001f49d1ea72205e7ad84e5e961d",
    ("tiny-smallthinker", "bucket"):
        "38bf8b4999763ffac2fbee832fc945d37579290b491336df115e7c7b071a92cc",
}


def abstract_engine(name, **engine):
    """-> (an engine of the registered config `name` that holds no array, the
    shapes of its weights): 2 slots, pages of 4, a chunk of 16."""
    cfg = get_config(name)
    if cfg.window_paged:
        engine = dict(max_window_pages=40, prefill_buckets=(8, 16), **engine)
    ecfg = EngineConfig(**{**dict(
        max_batch_size=2, page_size=PAGE, max_pages=16, max_seq_len=32,
        prefill_chunk=16, cache_dtype="float32"), **engine})
    params = jax.eval_shape(
        lambda k: (stack.init_params if cfg.is_stack else init_params)(cfg, k),
        jax.random.PRNGKey(0))
    return InferenceEngine.abstract(cfg, ecfg), params


def lowered(name, program, **engine):
    """`program` (a key of NAMES) of `abstract_engine(name)`, lowered for the
    CPU at the tests' matmul precision. `verify` asks for speculation, which
    the stacks of unlike layers refuse."""
    if program == "verify":
        engine["speculation"] = {"mode": "ngram", "num_speculative_tokens": 3}
    eng, params = abstract_engine(name, **engine)
    with jax.default_matmul_precision("highest"):
        return eng.programs(params, buckets=(16,), batch_sizes=(1,))[
            NAMES[program]].lower()


@functools.lru_cache(maxsize=None)
def digest(name, program):
    """sha256 of the program's StableHLO text; skips the asking test under
    another jax than the one PINNED was taken with."""
    if jax.__version__ != LOWERED_WITH_JAX:
        pytest.skip(f"digests were taken with jax {LOWERED_WITH_JAX}")
    return hashlib.sha256(
        lowered(name, program).as_text().encode()).hexdigest()
