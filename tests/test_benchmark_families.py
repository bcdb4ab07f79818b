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
from benchmark.tests import test_token_ledger as ledger_tests
from benchmark.tests import test_decode_span_ahead_share as ahead_tests
from benchmark.tests.test_decode_span_ahead_share import (  # noqa: F401
    test_a_tree_without_the_series_reads_nothing,
    test_a_window_shorter_than_the_busy_time_is_that_busy_time,
    test_the_share_of_a_known_split,
)
from benchmark.tests.test_token_ledger import (  # noqa: F401
    test_ledger_readers_on_snapshots_records_and_a_recorded_trace,
    test_the_recorded_trace_splits_put_from_call_under_their_phases,
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


def test_the_eight_are_entries_of_the_five_serve_cells_and_no_train_cell(
        monkeypatch):
    """The benchmark's own test, run as it is. It asks for the manifest's
    LAST eight per-layer entries, and a later PR's entry goes to the end of
    the list (PR 38's `decode_span_ahead_share`; an entry in the middle
    reads as an edit of the accepted ones, and so does an edit of that
    test's file): it is asked of the list up to its own eighth."""
    manifest = as_of_five_serve_cells()
    names = [m["name"] for m in manifest["per_layer"]]
    upto = names.index("decode_live_slots.traced") + 1
    monkeypatch.setattr(common, "load_manifest", lambda: {
        **manifest, "per_layer": manifest["per_layer"][:upto]})
    ledger_tests.test_the_eight_are_entries_of_the_five_serve_cells_and_no_train_cell()


LATER_CELLS = ("longcat-flash-omni.serve-docs",  # PR 39
               "smallthinker-21b-a3b.serve-mixedlen",  # PR 41
               "granite-4.0-h-micro.serve-chat-burst",  # PR 45
               "kanana-2-30b-a3b.serve-agent",  # PR 48
               "solar-open2-250b.serve-mixedlen",  # PR 52
               "trinity-mini.train-packed-x4",  # PR 54: the second TRAIN cell
               "xing4.0-29b-a4b.train-packed-x2")  # PR 58: the third


# metrics that later PRs appended for cells that were there already
LATER_READERS = ("moe_experts_skipped_share",)  # PR 42
# ... and for ONE cell that was there already: the chunk kernel's share of
# the device's time, PR 52's, lists Olmo's cell beside PR 52's own
LATER_READER_OF_OLMO = "gdn_chunk_device_share"

# the parts of `setup_s` (PR 50), appended for every serve cell at once:
# they end the list whatever cells a test leaves out
SETUP_READERS = ("setup_replica_ready_s", "setup_params_s",
                 "setup_engine_init_s", "setup_warmup_s",
                 "setup_trace_lower_s", "setup_compile_s",
                 "setup_cache_hit_share")


def manifest_without(cells):
    """The manifest without `cells` that later PRs appended: their entries
    of `workloads`, their names in each metric's own list, the
    configurations no cell is left for and the metrics no cell is left
    in; and without the LATER_READERS."""
    manifest = common.load_manifest()
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] not in (*LATER_READERS,
                                                  LATER_READER_OF_OLMO)]

    def without(entry):
        if "workloads" not in entry:
            return entry
        return {**entry, "workloads": [w for w in entry["workloads"]
                                       if w not in cells]}

    workloads = [w for w in manifest["workloads"] if w["name"] not in cells]
    return {**manifest,
            "configs": [c for c in manifest["configs"]
                        if any(w["config"] == c["name"] for w in workloads)],
            "workloads": workloads,
            "end_to_end": [without(m) for m in manifest["end_to_end"]],
            "per_layer": [m for m in map(without, manifest["per_layer"])
                          if m.get("workloads", True)]}


def as_of_five_serve_cells():
    """The manifest without the cells that later PRs appended (to
    `workloads` and to each entry's own list): those two tests of the
    benchmark's pin the serve cells at five, and an edit of their files
    reads as a change to the accepted benchmark. That the later cells are
    listed where they should be is `test_longcat_*`'s and
    `test_smallthinker_model.py`'s to hold."""
    return manifest_without(LATER_CELLS)


def test_it_is_an_entry_of_the_five_serve_cells_and_no_train_cell(monkeypatch):
    """The benchmark's own test of `decode_span_ahead_share`, run as it is,
    of the manifest as PR 38 left it."""
    manifest = as_of_five_serve_cells()
    monkeypatch.setattr(common, "load_manifest", lambda: manifest)
    ahead_tests.test_it_is_an_entry_of_the_five_serve_cells_and_no_train_cell()


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
    "hybrid_decode_attn_roofline", "window_pages_held_share",
    "moe_rows_padding_factor", "short_conv_device_share",
    "moe_ffn_device_share.tpot", "gdn_step_roofline", "gdn_step_device_share",
    "gdn_chunk_roofline", "recurrent_state_live_share"])
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


# -- the LFM2 family (PR 32) -------------------------------------------------

LFM2 = "lfm2-8b-a1b"
LFM2_ROW = {  # the published config's numbers, key for key, but the depth
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
LFM2_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
              "full_attention", "conv", "conv", "conv", "full_attention",
              "conv", "conv", "conv", "full_attention", "conv", "conv",
              "conv", "full_attention", "conv", "conv", "full_attention",
              "conv", "conv"]


def test_lfm2_configuration_is_the_published_one_cut_in_depth_alone():
    spec = common.load_json("configs", LFM2 + ".json")
    assert {k: spec[k] for k in LFM2_ROW} == LFM2_ROW
    assert spec["num_hidden_layers"] == 14
    assert spec["layer_types"] == LFM2_TYPES[:14]
    assert spec["published"]["layer_types"] == LFM2_TYPES
    assert sorted(spec["reduced"]) == ["layer_types", "num_hidden_layers"]
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == LFM2)
    assert sorted(entry["reduced"]) == sorted(spec["reduced"])
    assert sorted(entry) == ["file", "name", "reduced", "source", "why"]
    cfg = common.family(spec).model_config(spec)
    # both dense layers, then three whole periods of sparse layers
    assert cfg.segments() == ((0, ("conv",), 2),
                              (2, ("attn", "conv", "conv", "conv"), 3))
    assert cfg.second_halves == ("ffn",) * 2 + ("moe",) * 12
    assert round(cfg.param_count() / 1e6) == 4667      # the issue's count
    assert cfg.cache_dims == (3, 8, 64) and cfg.conv_tail == (11, 2, 2048)
    assert (cfg.router, cfg.qk_norm, cfg.capacity_factor) == ("sigmoid", True, 8.0)


def test_lfm2_readers_reach_the_counts_through_the_family():
    spec = common.load_json("configs", LFM2 + ".json")
    family = common.family(spec)
    assert reference_file(family) == spec["reference"]
    assert set(family.modes) == {"int8", "fp8"}
    # 3 of the 14 layers attend and hold a cache
    assert family.calls_per_pass(spec, "paged_decode") == 3
    # a cached token is 8 KV heads of 64 bfloat16, keys and values
    work = family.work["paged_decode"](spec, 1000)
    assert work["bytes"] == 2 * 8 * 64 * 2 * 1000
    assert work["flops"] == 2 * 2 * 32 * 64 * 1000
    cell = common.load_cell(LFM2 + ".serve-chat")
    assert cell["engine"] == {"max_seq_len": 4096, "max_batch_size": 64,
                              "max_pages": 16385}
    assert {m["name"] for m in cell["per_layer"]} >= {
        "moe_rows_padding_factor", "short_conv_device_share",
        "moe_ffn_device_share.tpot", "paged_decode_roofline"}
    # its answers outlast the window: the time per token is what is judged
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_mean_ms", "setup_s"]
    assert {m["moves"] for m in cell["per_layer"]} == {"tpot_mean_ms", "setup_s"}
    mixtral = common.load_cell("mixtral-8x7b.serve-chat")
    assert "moe_rows_padding_factor" in {m["name"] for m in mixtral["per_layer"]}


def test_lfm2_weights_are_seeded_bfloat16_and_in_the_programs_layout():
    from benchmark import weights

    spec = tiny_spec(LFM2)
    a, b, c = (weights.make_weights(spec, s) for s in (7, 7, 2**31 + 11))
    leaves = jax.tree.leaves(a)
    assert all(leaf.dtype == jax.numpy.bfloat16 for leaf in leaves)
    assert all((x == y).all() for x, y in zip(leaves, jax.tree.leaves(b)))
    assert any((x != y).any() for x, y in zip(leaves, jax.tree.leaves(c)))
    cfg = common.family(spec).model_config(spec)
    assert [len(seg) for seg in a["layers"]] == [
        len(kinds) for _, kinds, _ in cfg.segments()] == [1, 4]
    sparse = a["layers"][1][0]
    # the bias is drawn nonzero, so that the choice and the weights differ
    assert float(abs(sparse["router_bias"].astype("float32")).max()) > 0.01
    assert "final_norm_b" not in a


def test_the_padding_factor_reads_the_two_counters():
    read = common.load_reader("moe_rows_padding_factor")
    before = {("serve_moe_rows_routed", ()): 10.0,
              ("serve_moe_rows_computed", ()): 100.0}
    after = {("serve_moe_rows_routed", ()): 110.0,
             ("serve_moe_rows_computed", ()): 900.0}
    assert read({"counters": (before, after)}) == 8.0
    assert read({"counters": None}) is None


# -- the Olmo Hybrid family (PR 34) ------------------------------------------

OLMO = "olmo-hybrid-7b"
OLMO_ROW = {  # the published config's numbers, key for key, but the depth
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
OLMO_TYPES = ["linear_attention"] * 3 + ["full_attention"]


def test_olmo_hybrid_configuration_is_the_published_one_cut_in_depth_alone():
    spec = common.load_json("configs", OLMO + ".json")
    assert {k: spec[k] for k in OLMO_ROW} == OLMO_ROW
    assert spec["num_hidden_layers"] == 16
    assert spec["layer_types"] == OLMO_TYPES * 4
    assert spec["published"]["layer_types"] == OLMO_TYPES * 8
    assert spec["published"]["num_hidden_layers"] == 32
    assert sorted(spec["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert {"norm_placement", "qk_norm", "positions", "convolution", "decay",
            "decay_init", "weights"} <= set(spec["assumed"])
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == OLMO)
    assert sorted(entry["reduced"]) == sorted(spec["reduced"])
    assert sorted(entry) == ["file", "name", "reduced", "source", "why"]
    cfg = common.family(spec).model_config(spec)
    # four whole periods, ONE scan
    assert cfg.segments() == ((0, ("gdn", "gdn", "gdn", "attn"), 4),)
    assert round(cfg.param_count() / 1e7) == 410        # the issue's 4.10 B
    assert cfg.cache_dims == (4, 30, 128)
    assert cfg.conv_tail == (12, 3, 11520) and cfg.gdn_dims == (12, 30, 96, 192)
    assert (cfg.positional, cfg.post_norm, cfg.qk_norm_whole,
            cfg.gdn_neg_eigval, cfg.tie_embeddings) == (
                "none", True, True, True, False)


def test_olmo_hybrid_readers_reach_the_counts_through_the_family():
    spec = common.load_json("configs", OLMO + ".json")
    family = common.family(spec)
    assert reference_file(family) == spec["reference"]
    assert set(family.modes) == {"int8", "fp8", "state-bf16"}
    assert set(family.work) == {"paged_decode", "gdn_chunk", "gdn_step"}
    # 4 of the 16 layers attend and hold a cache, 12 run the recurrence
    assert family.calls_per_pass(spec, "paged_decode") == 4
    assert family.calls_per_pass(spec, "gdn_step") == 12
    assert family.calls_per_pass(spec, "gdn_chunk") == 12
    # a cached token is 30 KV heads of 128 bfloat16, keys and values
    work = family.work["paged_decode"](spec, 1000)
    assert work["bytes"] == 2 * 30 * 128 * 2 * 1000 == 15360 * 1000
    assert work["flops"] == 2 * 2 * 30 * 128 * 1000
    cell = common.load_cell(OLMO + ".serve-reason")
    assert cell["engine"] == {"max_seq_len": 4096, "max_batch_size": 64,
                              "max_pages": 3073}
    assert cell["traffic_name"] == "serve-reason"
    assert {m["name"] for m in cell["per_layer"]} == {
        "engine_host_ms_per_step", "engine_loop_host_ms_per_iter",
        "engine_idle_gap_attributed_share", "pool_copy_device_share",
        "decode_device_ms_per_step", "prefill_device_ms_per_ktok",
        "paged_decode_roofline", "gdn_step_roofline", "gdn_step_device_share",
        "gdn_chunk_roofline", "recurrent_state_live_share",
        LATER_READER_OF_OLMO,
        # the token ledger's eight (PR 36), in every serve cell
        "tpot_device_wait_ms", "tpot_host_ms", "tpot_ready_ms",
        "tpot_unaccounted_ms", "decode_step_wall_ms.clean",
        "decode_step_wall_ms.shared", "interleaved_prefill_tokens_per_token",
        "decode_live_slots.traced",
        # how often the loop stayed a span ahead (PR 38), likewise
        "decode_span_ahead_share",
        # where the set-up's seconds went (PR 50), likewise
        *SETUP_READERS}
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_mean_ms", "setup_s"]
    assert {m["moves"] for m in cell["per_layer"]} == {"tpot_mean_ms", "setup_s"}


def test_the_delta_rules_work_is_a_hand_count_at_one_small_shape():
    """2 heads of a [4, 8] state: per head, token and state element one
    product for the decay and a product and a sum each for S'^T k, the
    correction and S^T q; q, k, v, o, g, beta in float32; the state once a
    call (prefill) or once a LIVE slot (decode)."""
    spec = {"linear_num_key_heads": 2, "linear_num_value_heads": 2,
            "linear_key_head_dim": 4, "linear_value_head_dim": 8}
    family = common.family(common.load_json("configs", OLMO + ".json"))
    state = 2 * 4 * 8                       # elements
    operands = 2 * (4 + 4 + 8 + 8 + 1 + 1)  # q, k, v, o, g, beta a token
    chunk = family.work["gdn_chunk"](spec, 100)
    assert chunk["flops"] == 7 * state * 100
    assert chunk["bytes"] == 4 * (operands * 100 + 2 * state)
    step = family.work["gdn_step"](spec, 5)
    assert step["flops"] == 7 * state * 5
    assert step["bytes"] == 4 * 5 * (operands + 2 * state)
    assert family.work["gdn_step"](spec, 0) == {"flops": 0, "bytes": 0}
    # at the published sizes a live slot's state is 2.21 MB, read and written
    big = common.load_json("configs", OLMO + ".json")
    assert family.work["gdn_step"](big, 1)["bytes"] == 4 * (
        2 * 30 * 96 * 192 + 30 * (96 + 96 + 192 + 192 + 2))


def test_olmo_hybrid_weights_are_seeded_bfloat16_and_in_the_programs_layout():
    from benchmark import weights

    spec = tiny_spec(OLMO)
    a, b, c = (weights.make_weights(spec, s) for s in (7, 7, 2**31 + 11))
    leaves = jax.tree.leaves(a)
    assert all(leaf.dtype == jax.numpy.bfloat16 for leaf in leaves)
    assert all((x == y).all() for x, y in zip(leaves, jax.tree.leaves(b)))
    assert any((x != y).any() for x, y in zip(leaves, jax.tree.leaves(c)))
    cfg = common.family(spec).model_config(spec)
    assert [len(seg) for seg in a["layers"]] == [
        len(kinds) for _, kinds, _ in cfg.segments()] == [4]
    linear = a["layers"][0][0]
    # the decay as its authors draw it: A in (0, 16], a step in [0.001, 0.1]
    assert float(linear["d_A_log"].astype("float32").max()) <= 2.78
    step = jax.nn.softplus(linear["d_dt_b"].astype("float32"))
    assert 5e-4 < float(step.min()) and float(step.max()) < 0.11
    assert a["lm_head"].shape == (64, 256) and "final_norm_b" not in a


def test_the_olmo_names_file_adds_its_groups_and_removes_nothing():
    with open(os.path.join(common.HERE, "trace_names.json")) as f:
        base = json.load(f)["groups"]
    merged = trace_reduce.load_names()["groups"]
    for group, entries in base.items():
        assert merged[group][:len(entries)] == entries
    step = ("%gdn_step.2 = (f32[64,1,5760]{2,1,0}, f32[12,64,96,5760]{3,2,1,0}) "
            "custom-call(s32[64]{0} %a)")
    write = ("%fusion.9 = bf16[4,3073,16,3840]{3,2,1,0} "
             "fusion(bf16[4,3073,16,3840]{3,2,1,0} %p, bf16[64,3840]{1,0} %k)")
    trace = {"busy_s": 10.0, "ops": {
        step: [2.0, 12],
        "%gdn_chunk.1 = (f32[1,30,256,192]{3,2,1,0}) custom-call(f32[1] %a)": [1.0, 3],
        "%paged_decode.1 = bf16[64,30,128]{2,1,0} custom-call(s32[9]{0} %a)": [3.0, 4],
        write: [0.5, 8]},
        "modules": {"jit_decode_span_16(1)": [7.0, 1]},
        "module_ops": {"jit_decode_span_16(1)": [step]}}
    assert trace_reduce.group_seconds(trace, "gdn_step") == (2.0, 12.0)
    assert trace_reduce.group_seconds(trace, "gdn_chunk") == (1.0, 3.0)
    assert trace_reduce.group_seconds(trace, "pool_copy") == (0.5, 8.0)
    assert common.load_reader("gdn_step_device_share")({"trace": trace}) == 20.0


def test_the_step_roofline_counts_live_slots_alone():
    """12 calls in the trace, 10 slots live by the engine's own polls over
    the traced part: the bytes are 12 x 10 live slots' state and operands,
    whatever `max_batch_size` holds."""
    spec = common.load_json("configs", OLMO + ".json")
    family = common.family(spec)
    peaks = common.peaks_for("TPU v5 lite")
    step = ("%gdn_step.2 = (f32[64,1,5760]{2,1,0}, f32[12,64,96,5760]{3,2,1,0}) "
            "custom-call(s32[64]{0} %a)")
    ctx = {"spec": spec, "family": family, "peaks": peaks,
           "trace": {"busy_s": 1.0, "ops": {step: [0.002, 12]},
                     "modules": {}, "module_ops": {}},
           "run": {"t0": 100.0, "traced_from_s": 1.0, "traced_to_s": 6.0,
                   "polls": [{"t": 100.5, "active": 50},
                             {"t": 102.0, "active": 8},
                             {"t": 104.0, "active": 12},
                             {"t": 107.0, "active": 60}]}}
    got = common.load_reader("gdn_step_roofline")(ctx)
    bytes_moved = family.work["gdn_step"](spec, 12 * 10)["bytes"]
    assert got == pytest.approx(
        100.0 * bytes_moved / peaks["hbm_bytes_per_s"] / 0.002)
    assert 0 < got < 100
    ctx["run"]["polls"] = []
    assert common.load_reader("gdn_step_roofline")(ctx) is None


def test_the_live_share_reads_the_counter_pair():
    read = common.load_reader("recurrent_state_live_share")
    name = "serve_recurrent_state_slot_steps"
    before = {(name, (("state", "live"),)): 10.0,
              (name, (("state", "held"),)): 64.0}
    after = {(name, (("state", "live"),)): 170.0,
             (name, (("state", "held"),)): 704.0}
    assert read({"counters": (before, after)}) == 25.0
    assert read({"counters": None}) is None


# -- the LongCat-Flash family (PR 39) ----------------------------------------

LONGCAT = "longcat-flash-omni"
LONGCAT_CELL = LONGCAT + ".serve-docs"
LONGCAT_ROW = {  # the catalog's config, key for key, but the three cut
    "attention_bias": False, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}
NEW_READERS = ("mla_decode_roofline", "mla_prefill_roofline",
               "mla_attn_device_share", "prefix_hit_token_share",
               "moe_zero_choice_share", "moe_held_choice_share")


def test_longcat_configuration_is_the_published_one_with_three_cuts():
    spec = common.load_json("configs", LONGCAT + ".json")
    assert {k: spec[k] for k in LONGCAT_ROW} == LONGCAT_ROW
    assert (spec["num_layers"], spec["n_routed_experts"],
            spec["vocab_size"]) == (4, 16, 16384)
    assert {k: spec["published"][k] for k in
            ("num_layers", "n_routed_experts", "vocab_size")} == {
                "num_layers": 28, "n_routed_experts": 512,
                "vocab_size": 131072}
    # the router is not cut: 512 + 256 outputs, top 12
    assert spec["n_routed_experts_total"] == 512
    assert spec["held_experts_first"] == 0
    assert sorted(spec["reduced"]) == ["n_routed_experts", "num_layers",
                                       "vocab_size"]
    assert {"norm_topk_prob", "router", "mla_scale", "rotary", "activation",
            "biases", "head", "order", "weights"} <= set(spec["assumed"])
    assert "32 chips share each layer" in spec["deployment"]
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == LONGCAT)
    assert sorted(entry["reduced"]) == sorted(spec["reduced"])
    assert sorted(entry) == ["file", "name", "reduced", "source", "why"]
    assert entry["source"] == spec["source"]
    cfg = common.family(spec).model_config(spec)
    assert cfg.segments() == ((0, ("mla2",), 4),)       # ONE scan
    assert round(cfg.param_count() / 1e6, 1) == 5172.7  # the issue's 5172.9 M
    assert cfg.cache_dims == (8, 1, 640) and cfg.latent_cache
    assert (cfg.num_experts, cfg.experts_routed, cfg.experts_zero,
            cfg.router_width, cfg.num_selected_experts) == (16, 512, 256, 768, 12)
    assert (cfg.router, cfg.norm_topk, cfg.routed_scale, cfg.positional,
            cfg.tie_embeddings, cfg.has_state) == (
                "softmax_all", False, 6.0, "none", False, False)


def test_longcat_readers_reach_the_counts_through_the_family():
    spec = common.load_json("configs", LONGCAT + ".json")
    family = common.family(spec)
    assert reference_file(family) == spec["reference"]
    assert set(family.modes) == {"int8", "fp8", "router-bf16"}
    assert set(family.work) == {"mla_decode", "mla_chunk"}
    # two attentions a double layer; the decode kernel counts a span's steps
    for group in ("mla_decode", "mla_chunk", "paged_decode"):
        assert family.calls_per_pass(spec, group) == 8
    # a cached token: 2 x 64 x (576 + 512) operations, its 640-lane row once
    work = family.work["mla_decode"](spec, 1000, 3)
    assert work["flops"] == 2 * 64 * (576 + 512) * 1000
    assert work["bytes"] == 1280 * 1000 + 3 * 64 * (640 + 512) * 2
    assert round(work["flops"] / (1280 * 1000)) == 109  # under the ridge, 240
    # a chunk of 256 at 8192: row r sees 8192 + r + 1 rows, read once a call
    chunk = family.work["mla_chunk"](spec, 8192, 256)
    assert chunk["flops"] == 2 * 64 * 1088 * (256 * 8192 + 256 * 257 / 2)
    assert chunk["bytes"] == 1280 * 8448 + 256 * 64 * 1152 * 2
    cell = common.load_cell(LONGCAT_CELL)
    assert cell["engine"] == {"max_seq_len": 9216, "max_batch_size": 64,
                              "max_pages": 24577}
    assert cell["traffic_name"] == "serve-docs"
    assert cell["traffic"]["shared_prefix"] == {"count": 12, "len": 8192,
                                                "zipf_s": 0.7}
    assert {m["name"] for m in cell["per_layer"]} == set(NEW_READERS) | {
        "engine_host_ms_per_step", "engine_loop_host_ms_per_iter",
        "engine_idle_gap_attributed_share", "pool_copy_device_share",
        "decode_device_ms_per_step", "prefill_device_ms_per_ktok",
        "moe_rows_padding_factor", "moe_ffn_device_share.tpot",
        "tpot_device_wait_ms", "tpot_host_ms", "tpot_ready_ms",
        "tpot_unaccounted_ms", "decode_step_wall_ms.clean",
        "decode_step_wall_ms.shared", "interleaved_prefill_tokens_per_token",
        "decode_live_slots.traced", "decode_span_ahead_share",
        *LATER_READERS, *SETUP_READERS}
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_mean_ms", "setup_s"]
    assert {m["moves"] for m in cell["per_layer"]} == {"tpot_mean_ms", "setup_s"}
    # appended, never inserted: the new entries ended their lists, before
    # later PRs appended theirs
    manifest = manifest_without(LATER_CELLS[1:])
    assert manifest["configs"][-1]["name"] == LONGCAT
    assert manifest["workloads"][-1]["name"] == LONGCAT_CELL
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_READERS[0])
    assert tuple(names[at:at + 6]) == NEW_READERS
    assert names[at + 6:] == [*SETUP_READERS, "setup_trainer_start_s"]
    assert all(m["workloads"][-1] == LONGCAT_CELL for m in
               manifest["per_layer"] if LONGCAT_CELL in m["workloads"])


def test_longcat_weights_are_seeded_bfloat16_and_in_the_programs_layout():
    from benchmark import weights

    spec = tiny_spec(LONGCAT)
    a = weights.make_weights(spec, 2**31 + 11)
    leaves = jax.tree.leaves(a)
    assert all(leaf.dtype == jax.numpy.bfloat16 for leaf in leaves)
    cfg = common.family(spec).model_config(spec)
    assert [len(seg) for seg in a["layers"]] == [1]
    layer = a["layers"][0][0]
    assert layer["router"].shape == (2, 64, 12)      # every output, held or not
    assert layer["w_in"].shape == (2, 4, 64, 32)     # the held experts alone
    assert float(abs(layer["router_bias"].astype("float32")).max()) > 0
    assert a["lm_head"].shape == (64, 256)
    assert sum(x.size for x in leaves) == cfg.param_count()


def test_the_longcat_names_file_adds_its_groups_and_removes_nothing():
    with open(os.path.join(common.HERE, "trace_names.json")) as f:
        base = json.load(f)["groups"]
    merged = trace_reduce.load_names()["groups"]
    for group, entries in base.items():
        assert merged[group][:len(entries)] == entries
    decode = ("%mla_decode.3 = bf16[64,64,512]{2,1,0} custom-call(s32[36864]{0} %a)")
    chunk = "%mla_chunk.1 = bf16[16384,512]{1,0} custom-call(s32[576]{0} %a)"
    write = ("%fusion.9 = bf16[8,24577,16,640]{3,2,1,0} "
             "fusion(bf16[8,24577,16,640]{3,2,1,0} %p, bf16[64,640]{1,0} %k)")
    experts = "%fusion.7 = bf16[16,64,2048]{2,1,0} fusion(bf16[64,6144]{1,0} %x)"
    trace = {"busy_s": 10.0, "ops": {
        decode: [2.0, 16], chunk: [1.0, 8], write: [0.5, 8], experts: [1.5, 4],
        "%paged_decode.1 = bf16[64,32,128]{2,1,0} custom-call(s32[9]{0} %a)": [3.0, 4]},
        "modules": {"jit_decode_span_8(1)": [7.0, 1],
                    "jit_chunk_prefill_256(2)": [2.0, 1]},
        "module_ops": {"jit_decode_span_8(1)": [decode],
                       "jit_chunk_prefill_256(2)": [chunk]}}
    assert trace_reduce.group_seconds(trace, "mla_decode") == (2.0, 16.0)
    assert trace_reduce.group_seconds(trace, "mla_chunk") == (1.0, 8.0)
    # the decode kernel counts steps for the accepted readers too
    assert trace_reduce.group_seconds(trace, "paged_decode") == (5.0, 20.0)
    assert trace_reduce.group_seconds(trace, "decode_span") == (7.0, 1.0)
    assert trace_reduce.group_seconds(trace, "prefill") == (2.0, 1.0)
    assert trace_reduce.group_seconds(trace, "pool_copy") == (0.5, 8.0)
    assert trace_reduce.group_seconds(trace, "moe_ffn") == (1.5, 4.0)
    assert common.load_reader("mla_attn_device_share")({"trace": trace}) == 30.0


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_longcat_reader_returns_nothing_where_the_program_has_nothing(metric):
    """On the parent (no such kernel, no such counter, no such attribute) a
    new reader reads nothing and does not raise."""
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


def test_the_decode_roofline_counts_rows_once_and_the_choice_shares_their_counters():
    spec = common.load_json("configs", LONGCAT + ".json")
    family = common.family(spec)
    peaks = common.peaks_for("TPU v5 lite")
    decode = "%mla_decode.3 = bf16[64,64,512]{2,1,0} custom-call(s32[36864]{0} %a)"
    # one request of 8200 prompt tokens; tokens 1 and 2 fall in the traced part
    ctx = {"spec": spec, "family": family, "peaks": peaks,
           "trace": {"busy_s": 1.0, "ops": {decode: [0.001, 16]},
                     "modules": {}, "module_ops": {}},
           "run": {"traced_from_s": 1.0, "traced_to_s": 6.0,
                   "requests": [{"prompt_len": 8200, "due_s": 0.5}],
                   "records": [{"token_s": [0.9, 1.5, 2.0, 7.0]}]},
           "counters": None}
    work = family.work["mla_decode"](spec, 8201 + 8202, 2)
    want = 100.0 * 8 * work["bytes"] / peaks["hbm_bytes_per_s"] / 0.001
    assert common.load_reader("mla_decode_roofline")(ctx) == pytest.approx(want)
    assert 8 * work["flops"] / peaks["bf16_flops"] \
        < 8 * work["bytes"] / peaks["hbm_bytes_per_s"]   # memory-bound
    name = "serve_moe_choices"
    before = {(name, (("kind", k),)): 0.0 for k in ("all", "zero", "held")}
    after = {(name, (("kind", "all"),)): 1200.0,
             (name, (("kind", "zero"),)): 400.0,
             (name, (("kind", "held"),)): 24.0,
             ("serve_prefix_cache_hit_tokens", ()): 8192.0}
    ctx["counters"] = (before, after)
    assert common.load_reader("moe_zero_choice_share")(ctx) == pytest.approx(100 / 3)
    assert common.load_reader("moe_held_choice_share")(ctx) == 2.0
    ctx["run"]["requests"] = [{"prompt_len": 8292}, {"prompt_len": 8092}]
    assert common.load_reader("prefix_hit_token_share")(ctx) == 50.0


@pytest.mark.parametrize("kernel, operands", [
    ("moe_step", "bf16[64,2560]{1,0} %x, bf16[3,64,2560,768]{3,2,1,0} %w"),
    ("moe_groups", "bf16[256,2560]{1,0} %x, bf16[3,64,2560,768]{3,2,1,0} %w"),
    ("moe_groups", "bf16[256,4096]{1,0} %x, bf16[8,8,4096,14336]{3,2,1,0} %w"),
])
def test_the_expert_kernels_names_files_join_the_moe_ffn_group(
        kernel, operands):
    """benchmark/trace_names/moe_step.json and moe_groups.json: a step's
    and a chunk's expert kernels are counted in `moe_ffn`, once each,
    whatever else in the group matches their operands' shapes; nothing the
    accepted file holds is removed."""
    with open(os.path.join(common.HERE, "trace_names.json")) as f:
        base = json.load(f)["groups"]
    merged = trace_reduce.load_names()["groups"]
    for group, entries in base.items():
        assert merged[group][:len(entries)] == entries
    call = f"%{kernel}.12 = f32[256,2560]{{1,0}} custom-call({operands})"
    trace = {"busy_s": 10.0, "ops": {
        call: [2.5, 48],
        "%paged_chunk.1 = bf16[256,28,128]{2,1,0} custom-call(s32[9]{0} %a)":
            [1.0, 12]},
        "modules": {}, "module_ops": {}}
    assert trace_reduce.group_seconds(trace, "moe_ffn") == (2.5, 48.0)
    assert trace_reduce.group_share(trace, "moe_ffn") == 25.0


# -- the Solar Open 2 family (PR 52) -----------------------------------------

SOLAR = "solar-open2-250b"
SOLAR_ROW = {  # the published config, key for key, but the four cut keys
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}
SOLAR_CUTS = {"num_hidden_layers": (48, 8),
              "gqa_layers": (list(range(0, 48, 4)), [0, 4]),
              "n_routed_experts": (320, 20), "vocab_size": (196608, 24576)}


def test_solar_open2_configuration_is_the_published_one_with_four_cuts():
    spec = common.load_json("configs", SOLAR + ".json")
    assert {k: spec[k] for k in SOLAR_ROW} == SOLAR_ROW
    for key, (published, held) in SOLAR_CUTS.items():
        assert spec[key] == held and spec["published"][key] == published
    assert sorted(spec["reduced"]) == sorted(SOLAR_CUTS)
    assert (spec["n_routed_experts_total"], spec["held_experts_first"]) == (320, 0)
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == SOLAR)
    assert sorted(entry["reduced"]) == sorted(spec["reduced"])
    assert sorted(entry) == ["file", "name", "reduced", "source", "why"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    # every published width, pinned field by field
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hdim, cfg.d_ff) == (
        4096, 64, 8, 128, 10240)
    assert cfg.gdn_dims == (6, 64, 128, 128) and cfg.conv_taps == 4
    assert (cfg.gdn_channel_rank, cfg.gdn_gate_rank) == (128, 128)
    assert (cfg.num_experts, cfg.experts_routed, cfg.router_width,
            cfg.experts_first, cfg.num_selected_experts) == (20, 320, 320, 0, 8)
    assert (cfg.expert_ff, cfg.d_ff_shared, cfg.n_dense_layers) == (1280, 1280, 0)
    assert (cfg.router, cfg.norm_topk, cfg.routed_scale) == ("sigmoid", True, 1.0)
    assert (cfg.positional, cfg.post_norm, cfg.attn_gate, cfg.gdn_neg_eigval,
            cfg.tie_embeddings, cfg.qk_norm) == (
                "none", False, True, True, False, False)
    assert cfg.capacity_factor * 8 >= 20  # dropless over the held experts
    assert cfg.segments() == ((0, ("attn", "gdn", "gdn", "gdn"), 2),)
    assert round(cfg.param_count() / 1e7) == 390         # the issue's 3.90 B
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps) == (
        24576, 1048576, 1e-5)


@pytest.mark.parametrize("key,value,says", [
    ("n_group", 4, "n_group 4"), ("topk_group", 2, "topk_group 2"),
    ("kda_use_full_proj", True, "kda_use_full_proj True"),
    ("use_rope", True, "use_rope True")])
def test_solar_open2_refuses_what_it_does_not_write(key, value, says):
    spec = dict(common.load_json("configs", SOLAR + ".json"), **{key: value})
    with pytest.raises(ValueError, match=says):
        common.family(spec).model_config(spec)


def test_solar_open2_takes_an_absent_group_or_one_and_whole_key_heads_alone():
    spec = common.load_json("configs", SOLAR + ".json")
    family = common.family(spec)
    assert "n_group" not in spec
    family.model_config({**spec, "n_group": 1, "topk_group": 1})
    shared_keys = {**spec, "linear_attn_config": {
        **spec["linear_attn_config"], "num_kv_heads": 16}}
    with pytest.raises(ValueError, match="key heads shared"):
        family.model_config(shared_keys)


def test_solar_open2_readers_reach_the_counts_through_the_family():
    spec = common.load_json("configs", SOLAR + ".json")
    family = common.family(spec)
    assert reference_file(family) == spec["reference"]
    assert set(family.modes) == {"int8", "fp8", "state-bf16", "router-bf16"}
    assert set(family.work) == {"paged_decode", "paged_chunk", "gdn_chunk",
                                "gdn_step"}
    assert family.calls_per_pass(spec, "paged_decode") == 2
    assert family.calls_per_pass(spec, "paged_chunk") == 2
    assert family.calls_per_pass(spec, "gdn_step") == 6
    assert family.calls_per_pass(spec, "gdn_chunk") == 6
    # a cached token is 8 KV heads of 128 bfloat16, keys and values
    work = family.work["paged_decode"](spec, 1000)
    assert work["bytes"] == 2 * 8 * 128 * 2 * 1000 == 4096 * 1000
    assert work["flops"] == 2 * 2 * 64 * 128 * 1000
    # a chunk of 256 from position 512 in both GQA layers: every key up to
    # each row's own scored, every key read once
    chunk = family.chunk_attention_work(spec, 512, 256)
    assert chunk["bytes"] == 2 * 4096 * 768
    assert chunk["flops"] == 2 * 2 * 2 * 64 * 128 * (256 * 512 + 256 * 257 // 2)


def test_the_channel_decays_work_is_a_hand_count_at_one_small_shape():
    """2 heads of a [4, 4] state whose decay is a key channel's: per head,
    token and state element the scalar form's 7 operations, and one
    exponential a key lane; q, k, v, o, the decay's 4 lanes and beta in
    float32; the state once a call (prefill) or once a LIVE slot (decode)."""
    spec = {"linear_attn_config": {"num_heads": 2, "head_dim": 4,
                                   "num_kv_heads": None}}
    family = common.family(common.load_json("configs", SOLAR + ".json"))
    state = 2 * 4 * 4                        # elements
    operands = 2 * (4 + 4 + 4 + 4 + 4 + 1)   # q, k, v, o, g, beta a token
    chunk = family.work["gdn_chunk"](spec, 100)
    assert chunk["flops"] == (7 * state + 2 * 4) * 100
    assert chunk["bytes"] == 4 * (operands * 100 + 2 * state)
    step = family.work["gdn_step"](spec, 5)
    assert step["flops"] == (7 * state + 2 * 4) * 5
    assert step["bytes"] == 4 * 5 * (operands + 2 * state)
    assert family.work["gdn_step"](spec, 0) == {"flops": 0, "bytes": 0}
    # at the published sizes a live slot's state is 4.19 MB, read and written
    big = common.load_json("configs", SOLAR + ".json")
    assert family.work["gdn_step"](big, 1)["bytes"] == 4 * (
        2 * 64 * 128 * 128 + 64 * (5 * 128 + 1))


def test_solar_open2_weights_are_seeded_bfloat16_and_in_the_programs_layout():
    from benchmark import weights

    spec = tiny_spec(SOLAR)
    a, b, c = (weights.make_weights(spec, s) for s in (7, 7, 2**31 + 11))
    leaves = jax.tree.leaves(a)
    assert all(leaf.dtype == jax.numpy.bfloat16 for leaf in leaves)
    assert all((x == y).all() for x, y in zip(leaves, jax.tree.leaves(b)))
    assert any((x != y).any() for x, y in zip(leaves, jax.tree.leaves(c)))
    cfg = common.family(spec).model_config(spec)
    assert [len(seg) for seg in a["layers"]] == [4]
    gqa, linear = a["layers"][0][0], a["layers"][0][1]
    assert gqa["wg"].shape == (2, 64, 4, 16)
    assert linear["router"].shape == (2, 64, 16)    # every output, held or not
    assert linear["w_in"].shape == (2, 4, 64, 32)   # the held experts alone
    assert linear["sh_in"].shape == (2, 64, 32)
    assert float(abs(linear["router_bias"].astype("float32")).max()) > 0
    assert not linear["d_gb_b"].astype("float32").any()
    # the decay as Kimi Linear draws it: A in (1, 16) a head, a step in
    # [0.001, 0.1] a LANE
    assert linear["d_dt_b"].shape == (2, 32) and linear["d_A_log"].shape == (2, 4)
    A_log = linear["d_A_log"].astype("float32")
    assert float(A_log.min()) >= 0 and float(A_log.max()) <= 2.78
    step = jax.nn.softplus(linear["d_dt_b"].astype("float32"))
    assert 5e-4 < float(step.min()) and float(step.max()) < 0.11
    assert sum(x.size for x in leaves) == cfg.param_count()


def test_the_solar_names_file_adds_the_shared_experts_width_and_removes_nothing():
    with open(os.path.join(common.HERE, "trace_names.json")) as f:
        base = json.load(f)["groups"]
    merged = trace_reduce.load_names()["groups"]
    for group, entries in base.items():
        assert merged[group][:len(entries)] == entries
    up = ("%fusion.31 = bf16[256,1280]{1,0} fusion(bf16[256,4096]{1,0} %x, "
          "bf16[2,4096,1280]{2,1,0} %w)")
    down = ("%fusion.32 = bf16[256,4096]{1,0} fusion(bf16[256,1280]{1,0} %h, "
            "bf16[2,1280,4096]{2,1,0} %w)")
    routed = ("%moe_groups.4 = bf16[256,4096]{1,0} custom-call(bf16[256,4096]{1,0} "
              "%x, bf16[2,20,4096,1280]{3,2,1,0} %w)")
    loop = "%while.3 = (bf16[256,1280]{1,0}, s32[]) while(%tuple.1)"
    chunk = "%gdn_chunk.2 = (f32[1,64,256,128]{3,2,1,0}) custom-call(f32[1] %a)"
    trace = {"busy_s": 10.0, "ops": {up: [1.0, 8], down: [0.5, 8],
                                     routed: [3.0, 8], loop: [9.0, 1],
                                     chunk: [2.0, 6]},
             "modules": {}, "module_ops": {}}
    assert trace_reduce.group_seconds(trace, "shared_experts") == (1.5, 16.0)
    assert trace_reduce.group_seconds(trace, "moe_ffn") == (3.0, 8.0)
    assert common.load_reader("shared_expert_device_share")({"trace": trace}) == 15.0
    assert common.load_reader("gdn_chunk_device_share")({"trace": trace}) == 20.0


# -- the afmoe family (PR 54): the second TRAIN cell -------------------------

TRINITY = "trinity-mini"
TRINITY_CELL = TRINITY + ".train-packed-x4"
TRINITY_READERS = ["flash_window_fwd_roofline.train",
                   "flash_window_bwd_roofline.train",
                   "moe_grouped_roofline.train", "moe_ffn_device_share.train",
                   "flash_attn_device_share.train",
                   "moe_rows_max_over_mean.train",
                   "moe_row_buffer_fill_share.train"]


def _trinity_trace():
    """A traced step's operations as the chip names them (chip, PR 54)."""
    q = "bf16[4,32,8192,128]{3,2,1,0}"
    return {"busy_s": 10.0, "window_s": 10.5, "modules": {}, "module_ops": {},
            "ops": {
                f"%flash_fwd_window.12 = ({q}, f32[4,32,8192,128]) custom-call({q} %a)": [0.3, 24],
                f"%flash_fwd.1 = ({q}, f32[4,32,8192,128]) custom-call({q} %a)": [0.16, 8],
                f"%flash_bwd_window_dq.14 = {q} custom-call({q} %a)": [0.28, 24],
                f"%flash_bwd_window_dkv.14 = (f32[4,32,8192,128]) custom-call({q} %a)": [0.44, 24],
                f"%flash_bwd_dq.1 = {q} custom-call({q} %a)": [0.18, 8],
                "%moe_gmm.52 = bf16[73728,2048]{1,0} custom-call(s32[144] %t)": [0.1, 96],
                "%moe_gmm_dx.43 = bf16[73728,2048]{1,0} custom-call(s32[144] %t)": [0.1, 96],
                "%moe_gmm_dw.44 = bf16[16,1024,2048]{2,1,0} custom-call(s32[144] %t)": [0.1, 96],
                "%fusion.1407 = f32[32768,2048]{1,0} fusion(s32[73728]{0} %i, f32[73728,2048]{1,0} %y)": [0.15, 24],
                "%fusion.1404 = f32[262144]{0} fusion(f32[4,8192,128]{2,1,0} %s, s32[262144]{0} %i)": [0.06, 24],
                "%fusion.1408 = bf16[4,8192,1024]{2,1,0} fusion(bf16[4,8192,2048]{2,1,0} %h, bf16[2048,1024]{1,0} %w)": [0.02, 24],
                "%fusion.667 = bf16[4,8192,6144]{2,1,0} fusion(bf16[4,8192,2048]{2,1,0} %h, bf16[2048,6144]{1,0} %w)": [0.03, 8],
                "%while.7 = (bf16[73728,2048]{1,0}, s32[]) while(%tuple.1)": [9.0, 8],
            }}


def test_the_afmoe_names_file_adds_its_groups_and_removes_nothing():
    with open(os.path.join(common.HERE, "trace_names.json")) as f:
        base = json.load(f)["groups"]
    merged = trace_reduce.load_names()["groups"]
    for group, entries in base.items():
        assert merged[group][:len(entries)] == entries
    trace = _trinity_trace()
    seconds = lambda group: trace_reduce.group_seconds(trace, group)  # noqa: E731
    # the full layer's calls and the window layers' are two rooflines
    assert seconds("flash_fwd") == (0.16, 8.0)
    assert seconds("flash_window_fwd") == (0.3, 24.0)
    assert seconds("flash_bwd") == (0.18, 8.0)
    assert seconds("flash_window_bwd") == (pytest.approx(0.72), 48.0)
    assert seconds("flash_window_bwd_count") == (0.28, 24.0)
    assert seconds("moe_grouped") == (pytest.approx(0.3), 288.0)
    assert seconds("flash_attn_train")[0] == pytest.approx(1.36)
    # the kernels, the sorted rows' gather and scatter, the router's scores
    # and choices, the shared expert; not the dense layer, not the loop
    assert seconds("moe_ffn_train")[0] == pytest.approx(0.3 + 0.15 + 0.06 + 0.02)


def test_the_afmoe_readers_reach_the_counts_through_the_family():
    spec = common.load_json("configs", TRINITY + ".json")
    cell = common.load_cell(TRINITY_CELL)
    family = common.family(spec)
    peaks = common.peaks_for("TPU v5 lite")
    steps = 8
    # the step's own metrics on the first batch, as the driver's loop read
    # them (the expert layers' numbers leave the step nowhere else)
    first = {"ce_loss": 10.5, "moe_choices_held": 4 * 32768.0,
             "moe_rows_max": 4 * 4096.0, "moe_rows_bound": 4 * 73728.0,
             "moe_bias_moved": 250.0, "moe_rows_short": 0.0}
    ctx = {"cell": cell, "spec": spec, "family": family, "chips": 1,
           "peaks": peaks, "trace": _trinity_trace(),
           "run": {"traced_steps": steps, "tokens_per_s": 32768.0,
                   "first_metrics": first}}
    read = {name: common.load_reader(name)(ctx) for name in TRINITY_READERS}
    work = family.work["flash_window_fwd"](spec, 4, 8192)
    ideal = max(work["flops"] / peaks["bf16_flops"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    assert read["flash_window_fwd_roofline.train"] == pytest.approx(
        100 * ideal * 24 / 0.3)
    assert 1 < read["flash_window_bwd_roofline.train"] < 100
    grouped = family.work["moe_grouped"](spec, 32768)
    ideal = max(grouped["flops"] / peaks["bf16_flops"],
                grouped["bytes"] / peaks["hbm_bytes_per_s"])
    assert read["moe_grouped_roofline.train"] == pytest.approx(
        100 * ideal * 4 * steps / 0.3)
    assert read["moe_ffn_device_share.train"] == pytest.approx(5.3)
    assert read["flash_attn_device_share.train"] == pytest.approx(13.6)
    assert read["moe_rows_max_over_mean.train"] == pytest.approx(2.0)
    assert read["moe_row_buffer_fill_share.train"] == pytest.approx(
        100 * 32768 / 73728)
    mfu = common.load_reader("train_mfu")(ctx)
    assert mfu == pytest.approx(100 * family.train_flops_per_token(spec, 8192)
                                * 32768 / peaks["bf16_flops"])


@pytest.mark.parametrize("metric", TRINITY_READERS)
def test_an_afmoe_reader_returns_nothing_where_the_program_has_nothing(
        metric, monkeypatch):
    """On the parent (no such kernel, no such counter) and in a cell of
    another family a new reader reads nothing and does not raise."""
    monkeypatch.setattr(common, "counters", lambda: {})
    spec = common.load_json("configs", "mistral-7b.json")
    cell = common.load_cell("mistral-7b.train-packed")
    ctx = {"cell": cell, "spec": spec, "family": common.family(spec),
           "chips": 1, "peaks": common.peaks_for("TPU v5 lite"),
           "run": {"traced_steps": 4, "tokens_per_s": 8000.0,
                   "first_metrics": {"loss": 9.0, "ce_loss": 9.0}},
           "trace": {"busy_s": 4.0, "ops": {
               "%paged_decode.1 = bf16[1] custom-call(s32[1] %a)": [1.0, 4]},
               "modules": {}, "module_ops": {}}}
    assert common.load_reader(metric)(ctx) is None


def test_afmoe_weights_are_seeded_and_in_the_programs_layout():
    import jax.numpy as jnp

    spec = tiny_spec(TRINITY)
    family = common.family(spec)
    make = jax.jit(lambda k: family.init_weights(spec, k))
    a, b = make(jax.random.PRNGKey(1)), make(jax.random.PRNGKey(1))
    c = make(jax.random.PRNGKey(2))
    leaves = jax.tree.leaves(a)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(leaves, jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["embed"], c["embed"]))
    # every leaf bfloat16 but the router's bias, a float32 buffer
    for segment in a["layers"]:
        for lp in segment:
            for name, leaf in lp.items():
                assert leaf.dtype == (jnp.float32 if name == "router_bias"
                                      else jnp.bfloat16), name
    cfg = family.model_config(spec)
    from ray_tpu.models import stack

    shapes = jax.eval_shape(lambda k: stack.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: x.shape, a) == jax.tree.map(
        lambda x: x.shape, shapes)
    bias = a["layers"][1][0]["router_bias"]
    assert float(jnp.abs(bias).max()) > 0 and abs(float(bias.mean())) < 1e-3
    with pytest.raises(ValueError, match="n_group"):
        family.model_config({**spec, "n_group": 2})
