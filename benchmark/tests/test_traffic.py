import json
import os

import numpy as np
import pytest

from benchmark import common, traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "traffic")


def _chat():
    return common.load_json("traffic", "serve-chat.json")


def _test_mix(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


def test_requests_are_deterministic_in_seed():
    a = traffic.requests(_chat(), 2**31 + 5, 4.0, 20, 32768)
    b = traffic.requests(_chat(), 2**31 + 5, 4.0, 20, 32768)
    c = traffic.requests(_chat(), 7, 4.0, 20, 32768)
    assert a == b
    assert [r["prompt_ids"] for r in a] != [r["prompt_ids"] for r in c]


def test_every_seed_gets_the_same_sizes_and_instants():
    def schedule(seed):
        return [(r["due_s"], len(r["prompt_ids"]), r["max_tokens"])
                for r in traffic.requests(_chat(), seed, 4.0, 40, 32768)]

    a, b = schedule(1), schedule(2)
    assert a == b            # the work is the mix's; the seed draws the tokens
    assert len(a) == 160     # rate x seconds, whatever the seed
    other = dict(_chat(), schedule_seed=7)
    c = [(r["due_s"], len(r["prompt_ids"]), r["max_tokens"])
         for r in traffic.requests(other, 1, 4.0, 40, 32768)]
    assert c != a and sorted(x[1] for x in c) == sorted(x[1] for x in a)


def test_requests_follow_the_mix_and_hit_both_prefill_paths():
    mix = _chat()
    reqs = traffic.requests(mix, 3, 4.0, 40, 32768)
    prompts = np.array([len(r["prompt_ids"]) for r in reqs])
    outputs = np.array([r["max_tokens"] for r in reqs])
    assert prompts.min() >= mix["prompt_len"]["min"]
    assert prompts.max() <= mix["prompt_len"]["max"]
    assert abs(np.median(prompts) - mix["prompt_len"]["median"]) < 20
    assert abs(np.median(outputs) - mix["output_len"]["median"]) < 10
    # the engine's default prefill_chunk is 256: bucketed at or under it,
    # chunked above; both carry a good share of the traffic
    assert (prompts <= 256).sum() >= 30 and (prompts > 256).sum() >= 30
    due = np.array([r["due_s"] for r in reqs])
    assert (np.diff(due) >= 0).all() and 0 <= due[0] and due[-1] < 40
    ids = np.concatenate([r["prompt_ids"] for r in reqs])
    assert ids.min() >= 3 and ids.max() < 32768


def test_arrival_gaps_have_mean_one_and_the_named_shape():
    poisson = traffic.arrival_gaps({"process": "poisson"}, 1000)
    bursty = traffic.arrival_gaps({"process": "gamma", "cv": 3.0}, 1000)
    assert abs(poisson.mean() - 1) < 1e-9 and abs(bursty.mean() - 1) < 1e-9
    assert 0.9 < poisson.std() < 1.1
    assert bursty.std() > 2.0


@pytest.mark.parametrize("name", ["sessions", "longprompt", "burst"])
def test_other_shapes_of_traffic_are_data_files_of_the_one_generator(name):
    """Mixes that no cell uses yet (PERF.md section 7) need no new code:
    each is a data file, deterministic in the seed, with every seed's sizes
    and instants the same."""
    mix = _test_mix(name)
    a = traffic.requests(mix, 2**31 + 9, 4.0, 10, 256)
    assert a == traffic.requests(mix, 2**31 + 9, 4.0, 10, 256)
    b = traffic.requests(mix, 8, 4.0, 10, 256)

    def shape(reqs):
        return [(r["due_s"], len(r["prompt_ids"]), r["max_tokens"]) for r in reqs]

    assert shape(a) == shape(b)
    assert [r["prompt_ids"] for r in a] != [r["prompt_ids"] for r in b]
    due = [r["due_s"] for r in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 10


def test_a_mixture_keeps_each_class_to_its_own_sizes():
    reqs = traffic.requests(_test_mix("longprompt"), 1, 4.0, 10, 256)
    long = [r for r in reqs if len(r["prompt_ids"]) >= 80]
    assert len(reqs) == 40 and len(long) == 12        # 30% of rate x seconds
    assert {r["max_tokens"] for r in long} == {4}     # the long class's answers
    assert all(len(r["prompt_ids"]) <= 40 for r in reqs if r not in long)


def test_sessions_grow_over_shared_prefixes():
    mix = _test_mix("sessions")
    reqs = traffic.requests(mix, 1, 2.0, 10, 256)
    heads = [tuple(r["prompt_ids"][:32]) for r in reqs]
    counts = sorted((heads.count(h) for h in set(heads)), reverse=True)
    assert len(counts) == 3 and counts[0] > counts[-1]  # 3 prefixes, Zipf
    # a later turn's prompt is an earlier turn's prompt, that turn's
    # stand-in answer, and new tokens
    grown = 0
    for r in reqs:
        for q in reqs:
            n = len(q["prompt_ids"])
            if (q["due_s"] < r["due_s"]
                    and len(r["prompt_ids"]) >= n + q["max_tokens"] + 4
                    and r["prompt_ids"][:n] == q["prompt_ids"]):
                assert 0.2 <= r["due_s"] - q["due_s"] <= 0.5 * 2 + 1e-9
                grown += 1
                break
    # 20 sessions of 2 or 3 turns: 30 later turns, less those due past the end
    assert 20 <= grown <= 30 and len(reqs) >= 40


def test_unknown_names_raise():
    with pytest.raises(ValueError):
        traffic.quantile_sizes({"dist": "pareto"}, 4)
    with pytest.raises(ValueError):
        traffic.arrival_gaps({"process": "weibull"}, 4)


def test_packed_rows_are_deterministic_full_and_separated():
    mix = common.load_json("traffic", "train-packed.json")
    a = traffic.packed_rows(mix, 2**31 + 1, 6, 32768)
    b = traffic.packed_rows(mix, 2**31 + 1, 6, 32768)
    c = traffic.packed_rows(mix, 9, 6, 32768)
    assert a.shape == (6, mix["row_tokens"] + 1) and a.dtype == np.int32
    assert (a == b).all() and not (a == c).all()
    seps = (a == mix["separator_id"]).sum()
    # documents of median 600 in 49k tokens: some tens of separators
    assert 20 <= seps <= 200
    assert a.max() < 32768 and a.min() >= 2
