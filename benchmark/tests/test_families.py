"""The seam through which the benchmark reaches a model (benchmark/families/):
the first family is the parent's code moved and not rewritten (weights and
`ModelConfig` pinned on the parent, commit 79faa56, before the move); the
resolver fails loudly; kernel names merge from a directory; and a second
family that lives only under benchmark/tests/data/ goes through the same
drivers and comparison, with teeth."""

import argparse
import dataclasses
import hashlib
import inspect
import json
import os
import re
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common, drive, trace_reduce, weights
from benchmark.tests.tiny import tiny_cell, tiny_spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIGS = ["mistral-7b", "mixtral-8x7b"]


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        for part in (jax.tree_util.keystr(path), leaf.dtype, leaf.shape):
            h.update(str(part).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def reference_file(family) -> str:
    """Where the function that decides `correct` is written down."""
    return os.path.relpath(inspect.getsourcefile(family.logits_at), common.ROOT)


# -- the first family is the parent's code -----------------------------------

PARENT_WEIGHTS = {  # make_weights(tiny cut, seed) on the parent, on the CPU
    ("mistral-7b", 7):
        "c4c2dd422a3967cabd1a905e5caa6d37a742841d6df4aecb26f51c07c9b092ea",
    ("mistral-7b", 2**31 + 11):
        "47787ea8b15c5f1b65df61528bd882ff8741a5bc85af524f4c49bcf4c85fa8a1",
    ("mixtral-8x7b", 7):
        "7d9a6141779e4808dc9d7d848d9b501172e9d6de0128558a0774acca8533d4bb",
    ("mixtral-8x7b", 2**31 + 11):
        "1129801700d375e1fea1aa4c8f0a5e88578a3ca438ea6db256a9cae7c46ce6f0",
}


@pytest.mark.parametrize("config,seed", sorted(PARENT_WEIGHTS))
def test_weights_are_bit_identical_to_the_parents(config, seed):
    tree = weights.make_weights(tiny_spec(config), seed)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(tree))
    assert digest(tree) == PARENT_WEIGHTS[config, seed]


PARENT_CONFIG = dict(  # what the parent's weights.model_config built
    activation="swiglu", attn_impl="flash", dtype="bfloat16",
    logits_softcap=None, norm="rmsnorm", norm_eps=1e-05,
    num_selected_experts=2, positional="rope", remat=True,
    rope_theta=1000000.0, tie_embeddings=False)
PARENT_SIZES = {
    "tiny": dict(d_ff=128, d_model=64, head_dim=16, max_seq_len=512,
                 n_heads=4, n_kv_heads=2, n_layers=2, vocab_size=256),
    "published": dict(d_ff=14336, d_model=4096, head_dim=128,
                      max_seq_len=32768, n_heads=32, n_kv_heads=8),
}
PARENT_OWN = {
    "mistral-7b": dict(name="mistral", capacity_factor=1.25, num_experts=0,
                       router_aux_coef=0.0),
    "mixtral-8x7b": dict(name="mixtral", capacity_factor=4.0, num_experts=8,
                         router_aux_coef=0.02),
}
PUBLISHED_OWN = {"mistral-7b": dict(n_layers=8, vocab_size=32768),
                 "mixtral-8x7b": dict(n_layers=3, vocab_size=32000)}


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("cut", ["tiny", "published"])
def test_model_config_is_the_parents_field_by_field(config, cut):
    spec = tiny_spec(config) if cut == "tiny" \
        else common.load_json("configs", config + ".json")
    want = {**PARENT_CONFIG, **PARENT_SIZES[cut], **PARENT_OWN[config]}
    if cut == "published":
        want.update(PUBLISHED_OWN[config])
    got = dataclasses.asdict(common.family(spec).model_config(spec))
    assert got == want


@pytest.mark.parametrize("config", CONFIGS)
def test_readers_reach_the_counts_through_the_family(config):
    spec = common.load_json("configs", config + ".json")
    family = common.family(spec)
    assert set(family.work) == {"flash_fwd", "flash_bwd", "paged_decode"}
    assert family.work["paged_decode"](spec, 1000)["bytes"] == 2 * 8 * 128 * 2 * 1000
    layers = {"mistral-7b": 8, "mixtral-8x7b": 3}[config]
    assert all(family.calls_per_pass(spec, g) == layers for g in family.work)
    assert family.matmul_params(spec)["layers"] == layers
    assert reference_file(family) == spec["reference"]


# -- the resolver ------------------------------------------------------------


def test_an_unknown_family_fails_with_the_families_found():
    with pytest.raises(common.BenchFailure,
                       match=r"no family file benchmark/families/rwkv.py; "
                             r"benchmark/families/ has \['mistral.py'"):
        common.family({"family": "benchmark/families/rwkv.py"})
    with pytest.raises(common.BenchFailure, match="names no \"family\""):
        common.family({"hidden_size": 64})


def test_a_family_file_that_lacks_part_of_the_contract_is_refused(tmp_path):
    (tmp_path / "half.py").write_text("PAD_TO = 1\nmodes = ()\n")
    with pytest.raises(common.BenchFailure, match="lacks.*'init_weights'"):
        common.family({"family": str(tmp_path / "half.py")})


def test_a_train_cell_asks_its_family_for_the_train_parts(gpt2):
    """A family that only serves holds no `nll_and_norm_grads`: a train
    cell on it is refused when it is loaded, a serve cell is not."""
    _, family = gpt2
    assert not hasattr(family, "nll_and_norm_grads")
    with pytest.raises(common.BenchFailure, match="lacks.*'program_probe'"):
        common._holds(family, common.FAMILY_HOLDS_TO_TRAIN)
    common._holds(common.family(tiny_spec("mistral-7b")),
                  common.FAMILY_HOLDS_TO_TRAIN)


SEAM = re.compile(
    r"reference\.model|reference import model|ModelConfig\(|"
    r"""["'](head_dim|num_hidden_layers|intermediate_size|hidden_size|"""
    r"""num_attention_heads|num_key_value_heads|num_local_experts|"""
    r"""num_experts_per_tok)["']""")


def test_nothing_outside_the_families_reaches_around_the_seam():
    """No driver, check, tool or reader names the reference, builds a
    ModelConfig or reads an architecture key of `spec`. (tools/record_*.py
    build toy models of their own for the recorded test traces.)"""
    skip = ("families", "reference", "tests")
    found = []
    for folder, dirs, files in os.walk(common.HERE):
        dirs[:] = [d for d in dirs if d not in skip and d != "__pycache__"]
        for name in files:
            if name.endswith(".py") and not name.startswith("record_tiny_"):
                with open(os.path.join(folder, name)) as f:
                    for i, line in enumerate(f, 1):
                        if SEAM.search(line):
                            found.append(f"{name}:{i}: {line.strip()}")
    assert found == []


# -- kernel and program names from a directory -------------------------------


def test_a_second_names_file_adds_a_group_and_an_entry_and_removes_nothing(tmp_path):
    shutil.copy(os.path.join(common.HERE, "trace_names.json"), tmp_path)
    shutil.copytree(os.path.join(DATA, "trace_names"), tmp_path / "trace_names")
    before = trace_reduce.load_names()
    after = trace_reduce.load_names(str(tmp_path))
    assert set(after["groups"]) - set(before["groups"]) == {"layer_norm"}
    for group, entries in before["groups"].items():
        assert after["groups"][group][: len(entries)] == entries
    assert len(after["groups"]["prefill"]) == len(before["groups"]["prefill"]) + 1
    assert after["host_waiting"] == before["host_waiting"]


def test_kernel_groups_go_by_the_pallas_name_not_by_shapes():
    """Another head size, batch or pool lands in the same group; another
    kernel with operands of the same shapes does not."""
    trace = {"ops": {
        "%paged_decode.5 = bf16[64,8,4,128]{3,2,1,0} custom-call(s32[65]{0} %a, s32[3]{0} %b, bf16[8,8,8193,16,128]{4,3,2,1,0} %k)": [2.0, 4],
        "%paged_decode = bf16[4,20,2,64]{3,2,1,0} custom-call(s32[5]{0} %a)": [1.0, 2],
        "%paged_verify.2 = bf16[64,8,4,128]{3,2,1,0} custom-call(s32[65]{0} %a, s32[3]{0} %b, bf16[8,8,8193,16,128]{4,3,2,1,0} %k)": [8.0, 1],
        "%fusion.7 = bf16[64,8,4,128]{3,2,1,0} fusion(bf16[1] %paged_decode.5)": [16.0, 1],
    }, "modules": {}, "module_ops": {}}
    assert trace_reduce.group_seconds(trace, "paged_decode") == (3.0, 6.0)


# -- a second family, from test data alone -----------------------------------


@pytest.fixture(scope="module")
def gpt2():
    with open(os.path.join(DATA, "manifest.json")) as f:
        manifest = json.load(f)
    cell = tiny_cell("gpt2-tiny.chat", manifest, DATA)
    return cell, common.family(cell["config"])


def test_the_second_familys_reference_is_the_programs_forward(gpt2):
    from ray_tpu.models import forward

    cell, family = gpt2
    spec = cell["config"]
    assert os.path.dirname(family.__file__) == os.path.join(DATA, "families")
    assert reference_file(family) == spec["reference"]
    cfg = family.model_config(spec, dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make_weights(spec, 2**31 + 3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, 64), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, tokens[None], cfg)
    got = family.logits_at(params, tokens, jnp.arange(64), spec)
    assert float(jnp.max(jnp.abs(got))) > 1.0
    assert float(jnp.max(jnp.abs(logits[0] - got))) < 1e-4


@pytest.fixture(scope="module")
def runtime():
    import ray_tpu

    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    yield common.CompileWatch()
    ray_tpu.shutdown()


@pytest.mark.parametrize("fault", [None, "fp8 reference", "program's eps"])
def test_the_second_family_runs_as_a_cell_and_a_fault_fails_it(
        fault, gpt2, runtime, monkeypatch, capsys):
    """As test_rehearsal.py runs the manifest's cells. With the family's
    own reference rounding to fp8 (its `modes`), the same run is not
    correct: the comparison has teeth through the seam too. So it is with
    the timed path broken underneath: the program given another LayerNorm
    epsilon than the configuration states serves other log-probabilities."""
    cell, family = gpt2
    assert "fp8" in family.modes
    if fault == "fp8 reference":
        plain = family.logits_at
        monkeypatch.setattr(family, "logits_at",
                            lambda *a: plain(*a[:4], "fp8"))
    elif fault:
        sound = family.model_config
        monkeypatch.setattr(family, "model_config",
                            lambda spec: sound(spec, norm_eps=0.05))
    args = argparse.Namespace(seed=2**31 + 21, seconds=2.0, trace=0, sweep="")
    out = drive.measure(cell, args, {"platform": "cpu"}, runtime,
                        time.perf_counter())
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["correct"] == (fault is None)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    checks = {line["check"]: line for line in lines if "check" in line}
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["logprob_rms_err"]["ok"] == (fault is None)
    assert '"correct"' not in json.dumps(lines)  # no result line
