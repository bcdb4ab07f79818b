"""Tiny sizes for the CPU: the published configurations with every size
shrunk, by the cut their family gives (`tiny(spec)`), so a cell of a new
family is rehearsed by its entry in the manifest alone. Tests only; no cell
of the benchmark may use these."""

import copy

from benchmark import common


def tiny_spec(config_name: str):
    spec = common.load_json("configs", config_name + ".json")
    return common.family(spec).tiny(spec)


def tiny_cell(name: str, manifest=None, tree: str = common.HERE):
    cell = copy.deepcopy(common.load_cell(name, manifest, tree))
    cell["config"] = common.family(cell["config"]).tiny(cell["config"])
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
