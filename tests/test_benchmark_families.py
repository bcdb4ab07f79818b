"""The seam through which the benchmark reaches a model, inside the
driver's test floor: the fast cases of benchmark/tests/ (the families'
digests, grep, resolver and merge tests, the references against the
program and their controls, the operation counts), imported so that
tier-1 counts them, and the same questions asked of the second family of
the benchmark proper (SambaY, benchmark/families/sambay.py). The CPU
rehearsal of every cell stays in benchmark/tests/test_rehearsal.py."""

import json
import os

import jax
import pytest

from benchmark import common, trace_reduce
from benchmark.tests.test_families import (  # noqa: F401
    gpt2,
    reference_file,
    test_a_family_file_that_lacks_part_of_the_contract_is_refused,
    test_a_train_cell_asks_its_family_for_the_train_parts,
    test_an_unknown_family_fails_with_the_families_found,
    test_kernel_groups_go_by_the_pallas_name_not_by_shapes,
    test_model_config_is_the_parents_field_by_field,
    test_nothing_outside_the_families_reaches_around_the_seam,
    test_readers_reach_the_counts_through_the_family,
    test_the_second_familys_reference_is_the_programs_forward,
    test_weights_are_bit_identical_to_the_parents,
)
from benchmark.tests.test_flops import (  # noqa: F401
    test_kernel_work_and_bounds,
    test_mistral_matmul_parameters_by_hand,
    test_mixtral_counts_two_experts_of_eight,
    test_train_flops_per_token_by_hand,
)
from benchmark.tests.test_reference import (  # noqa: F401
    test_control_is_told_apart_serve,
    test_control_is_told_apart_train,
    test_program_probe_agrees_with_reference_gradients,
    test_reference_agrees_with_the_programs_forward,
    test_sparse_reference_is_dropless_and_top2,
)
from benchmark.tests.tiny import tiny_spec

SAMBAY = "phi-4-mini-flash"
CATALOG_ROW = {  # the published config, key for key
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def test_sambay_configuration_is_the_published_one_uncut():
    spec = common.load_json("configs", SAMBAY + ".json")
    assert {k: spec[k] for k in CATALOG_ROW} == CATALOG_ROW
    assert spec["reduced"] == {}
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == SAMBAY)
    assert entry["reduced"] == [] and sorted(entry) == [
        "file", "name", "reduced", "source", "why"]
    cfg = common.family(spec).model_config(spec)
    assert round(cfg.param_count() / 1e9, 2) == 3.85
    assert (cfg.count("mamba"), cfg.count("window"), cfg.count("full"),
            cfg.count("gmu"), cfg.count("cross")) == (9, 8, 1, 7, 7)
    assert cfg.layer_kinds[16:18] == ("mamba", "full")


def test_sambay_readers_reach_the_counts_through_the_family():
    spec = common.load_json("configs", SAMBAY + ".json")
    family = common.family(spec)
    assert reference_file(family) == spec["reference"]
    assert set(family.modes) >= {"int8", "fp8"}
    assert family.calls_per_pass(spec, "paged_decode") == 16
    assert family.calls_per_pass(spec, "paged_decode_window") == 8
    assert family.calls_per_pass(spec, "ssm_step") == 9
    # a cached token is 10 KV rows of 128 bfloat16, keys and values
    assert family.work["paged_decode"](spec, 1000)["bytes"] == 5120 * 1000
    # 8 reads of min(context, 512) and 8 of context
    assert family.decode_attention_tokens(spec, 300) == {
        "paged_decode_window": 8 * 300, "paged_decode": 8 * 300}
    assert family.decode_attention_tokens(spec, 2000) == {
        "paged_decode_window": 8 * 512, "paged_decode": 8 * 2000}
    # a slot's state: 16 x 5120 float32, read and written
    step = family.work["ssm_step"](spec, 64)
    assert step["bytes"] == 64 * (2 * 16 * 5120 + 3 * 5120 + 32) * 4
    scan = family.work["ssm_scan"](spec, 256)
    assert scan["flops"] == 6 * 16 * 5120 * 256
    assert set(family.work) == {"paged_decode", "paged_decode_window",
                                "ssm_scan", "ssm_step"}


def test_sambay_weights_are_seeded_and_bfloat16():
    from benchmark import weights

    spec = tiny_spec(SAMBAY)
    a, b, c = (weights.make_weights(spec, s) for s in (7, 7, 2**31 + 11))
    leaves = jax.tree.leaves(a)
    assert all(leaf.dtype == jax.numpy.bfloat16 for leaf in leaves)
    assert all((x == y).all() for x, y in zip(leaves, jax.tree.leaves(b)))
    assert any((x != y).any() for x, y in zip(leaves, jax.tree.leaves(c)))
    # the program's layout: segments of stacked periods
    cfg = common.family(spec).model_config(spec)
    assert [len(seg) for seg in a["layers"]] == [
        len(kinds) for _, kinds, _ in cfg.segments()]


def test_a_names_file_in_the_tree_adds_and_removes_nothing():
    """benchmark/trace_names/sambay.json against trace_names.json alone:
    every group of the base file keeps its entries, first and in order."""
    with open(os.path.join(common.HERE, "trace_names.json")) as f:
        base = json.load(f)["groups"]
    merged = trace_reduce.load_names()["groups"]
    for group, entries in base.items():
        assert merged[group][:len(entries)] == entries
    assert {"ssm_scan", "ssm_step", "paged_decode_window"} <= set(merged)
    window = ("%paged_decode_window.3 = bf16[64,10,4,128]{3,2,1,0} "
              "custom-call(s32[2112]{0} %a)")
    trace = {"ops": {
        window: [2.0, 8],
        "%paged_decode.1 = bf16[64,10,4,128]{3,2,1,0} custom-call(s32[9]{0} %a)": [3.0, 8],
        "%ssm_step.2 = (f32[64,5120]{1,0}, f32[9,64,16,5120]{3,2,1,0}) custom-call(s32[1]{0} %l)": [1.0, 9],
    }, "modules": {"jit_decode_span_16(1)": [7.0, 1]},
        "module_ops": {"jit_decode_span_16(1)": [window]}}
    assert trace_reduce.group_seconds(trace, "paged_decode") == (5.0, 16.0)
    assert trace_reduce.group_seconds(trace, "paged_decode_window") == (2.0, 8.0)
    assert trace_reduce.group_seconds(trace, "ssm_step") == (1.0, 9.0)


@pytest.mark.parametrize("metric", [
    "ssm_scan_roofline", "ssm_step_device_share",
    "hybrid_decode_attn_roofline", "window_pages_held_share"])
def test_a_new_reader_returns_nothing_where_the_program_has_nothing(metric):
    """On the parent (no such kernel, no such counter) a new reader reads
    nothing and does not raise: the result line leaves its metric out."""
    spec = common.load_json("configs", SAMBAY + ".json")
    cell = common.load_cell(SAMBAY + ".serve-reason")
    ctx = {"cell": cell, "spec": spec, "family": common.family(spec),
           "chips": 1, "peaks": common.peaks_for("TPU v5 lite"),
           "run": {"requests": [], "records": [], "traced_from_s": 1.0,
                   "traced_to_s": 6.0},
           "trace": {"busy_s": 4.0, "ops": {
               "%paged_decode.1 = bf16[1] custom-call(s32[1] %a)": [1.0, 4]},
               "modules": {}, "module_ops": {}},
           "counters": ({}, {})}
    assert common.load_reader(metric)(ctx) is None
