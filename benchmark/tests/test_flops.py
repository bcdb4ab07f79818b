"""The family's counts (benchmark/families/mistral.py, under the conventions
of benchmark/flops.py) against counts made by hand from the published sizes,
reached as the readers reach them: through `common.family(spec)`."""

from benchmark import common, flops


def test_mistral_matmul_parameters_by_hand():
    spec = common.load_json("configs", "mistral-7b.json")
    # per layer: q 4096x4096, o 4096x4096, k and v 4096x1024 each
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2
    ffn = 3 * 4096 * 14336
    head = 4096 * 32768
    assert attn == 41_943_040 and ffn == 176_160_768
    assert common.family(spec).active_matmul_params(spec) == 8 * (attn + ffn) + head
    assert common.family(spec).active_matmul_params(spec) == 1_879_048_192  # 1.88 B at 8 layers
    # no embedding lookup: the table (32768 x 4096) is in no term above


def test_mixtral_counts_two_experts_of_eight():
    spec = common.load_json("configs", "mixtral-8x7b.json")
    attn = 41_943_040
    ffn = 2 * 3 * 4096 * 14336 + 4096 * 8  # two experts + the router
    head = 4096 * 32000
    assert common.family(spec).active_matmul_params(spec) == 3 * (attn + ffn) + head
    all_experts = 3 * (attn + 8 * 3 * 4096 * 14336 + 4096 * 8) + head
    assert all_experts / common.family(spec).active_matmul_params(spec) > 3.0  # bench.py's error


def test_train_flops_per_token_by_hand():
    spec = common.load_json("configs", "mistral-7b.json")
    # attention forward per layer: 2 products x 2 ops x 32 heads x 128 x
    # T(T+1)/2 pairs; per token at T = 8192: 4 * 4096 * 4096.5
    attn_per_token = 8 * 4 * 4096 * (8192 + 1) / 2
    want = 3 * (2 * 1_879_048_192 + attn_per_token)
    got = common.family(spec).train_flops_per_token(spec, 8192)
    assert abs(got - want) / want < 1e-12
    assert 12.8e9 < got < 13.0e9  # the issue's 12.9 GFLOP a token


def test_kernel_work_and_bounds():
    spec = common.load_json("configs", "mistral-7b.json")
    peaks = common.load_json("peaks.json")["TPU v5 lite"]
    fwd = common.family(spec).flash_forward(spec, 1, 8192)
    assert fwd["flops"] == 4 * 32 * 128 * 8192 * 8193 / 2
    assert fwd["bytes"] == 8192 * 128 * (64 + 16) * 2
    assert flops.roofline_seconds(fwd, peaks)["bound"] == "compute"
    bwd = common.family(spec).flash_backward(spec, 1, 8192)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    dec = common.family(spec).paged_decode(spec, 1000)
    assert dec["bytes"] == 2 * 8 * 128 * 2 * 1000
    assert flops.roofline_seconds(dec, peaks)["bound"] == "memory"
