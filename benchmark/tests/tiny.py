"""Tiny sizes for the CPU: the published configurations with every size
shrunk. Tests only; no cell of the benchmark may use these."""

import copy

from benchmark import common

SHRINK = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              vocab_size=256, max_position_embeddings=512)


def tiny_spec(config_name: str):
    spec = dict(common.load_json("configs", config_name + ".json"))
    spec.update(SHRINK)
    return spec


def tiny_cell(name: str):
    cell = copy.deepcopy(common.load_cell(name))
    cell["config"].update(SHRINK)
    if cell["kind"] == "train":
        mix = cell["traffic"]
        mix.update(row_tokens=128, docs_in_pool=64)
        mix["doc_len"].update(median=40, max=128)
        cell["corpus_rows"] = 16
    else:
        mix = cell["traffic"]  # tests that bring a mix of their own replace it
        mix["prompt_len"].update(median=24, min=4, max=120)
        mix["output_len"].update(median=6, min=2, max=12)
        cell["engine"] = {"max_seq_len": 160, "max_batch_size": 4,
                          "max_pages": 64, "prefill_buckets": (16, 32),
                          "prefill_chunk": 32}
        cell["rate_rps"] = 4.0
        cell["drain_cap_s"] = 60
    return cell
