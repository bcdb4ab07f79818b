"""Speculative decoding (serve/spec_decode.py): config validation, the
n-gram and draft proposers, the span verify op, and engine-level
correctness — greedy speculation must be token-for-token identical to
speculation-off decoding, through stop sequences and cancellation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import get_config, init_params
from ray_tpu.ops import (
    paged_attention_decode,
    paged_attention_verify,
    pool_shape,
)
from ray_tpu.ops.paged_attention import _verify_reference
from ray_tpu.serve import EngineConfig, InferenceEngine, SpeculationConfig
from ray_tpu.serve.spec_decode import (
    NGramProposer,
    _batch_ngram_lookup,
    _ngram_lookup,
)


@pytest.fixture(params=["xla", "pallas"])
def kernel_mode(request, monkeypatch):
    monkeypatch.setenv(
        "RAY_TPU_FORCE_PALLAS", "1" if request.param == "pallas" else "0"
    )
    return request.param


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


class TestSpeculationConfig:
    def test_defaults_off(self):
        assert not SpeculationConfig().enabled
        assert SpeculationConfig(mode="ngram").enabled

    def test_bad_mode(self):
        with pytest.raises(ValueError, match="mode"):
            SpeculationConfig(mode="medusa")

    def test_bad_k(self):
        with pytest.raises(ValueError, match="num_speculative_tokens"):
            SpeculationConfig(mode="ngram", num_speculative_tokens=0)
        with pytest.raises(ValueError, match="num_speculative_tokens"):
            SpeculationConfig(mode="ngram", num_speculative_tokens=65)

    def test_bad_ngram_bounds(self):
        with pytest.raises(ValueError, match="ngram_min"):
            SpeculationConfig(mode="ngram", ngram_min=3, ngram_max=2)

    def test_draft_model_requires_draft_mode(self):
        with pytest.raises(ValueError, match="draft_model"):
            SpeculationConfig(mode="ngram", draft_model="tiny-llama")

    def test_parse_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="num_spec_tokens"):
            SpeculationConfig.parse({"mode": "ngram", "num_spec_tokens": 4})

    def test_parse_rejects_non_mapping(self):
        with pytest.raises(ValueError, match="mapping"):
            SpeculationConfig.parse("ngram")

    def test_parse_passthrough_and_dict(self):
        c = SpeculationConfig(mode="draft")
        assert SpeculationConfig.parse(c) is c
        d = SpeculationConfig.parse(
            {"mode": "ngram", "num_speculative_tokens": 2})
        assert d.num_speculative_tokens == 2


class TestNGramLookup:
    def test_repeat_continuation(self):
        # suffix [7, 8] seen earlier, continuation 9, 1, 2
        ctx = np.array([7, 8, 9, 1, 2, 5, 7, 8], np.int32)
        out = _ngram_lookup(ctx, nmin=1, nmax=3, k=3)
        assert out.tolist() == [9, 1, 2]

    def test_most_recent_match_wins(self):
        # suffix [3]: occurs at idx 1 (-> 4) and idx 4 (-> 6); recent wins
        ctx = np.array([1, 3, 4, 2, 3, 6, 5, 3], np.int32)
        out = _ngram_lookup(ctx, nmin=1, nmax=1, k=1)
        assert out.tolist() == [6]

    def test_longest_suffix_preferred(self):
        # 2-gram suffix [2, 3] matches idx 0 (-> 9); the 1-gram [3] also
        # matches later (-> 5) but longer n is tried first
        ctx = np.array([2, 3, 9, 3, 5, 2, 3], np.int32)
        out = _ngram_lookup(ctx, nmin=1, nmax=4, k=1)
        assert out.tolist() == [9]

    def test_no_match_empty(self):
        ctx = np.array([1, 2, 3, 4, 5], np.int32)
        assert _ngram_lookup(ctx, nmin=2, nmax=4, k=4).size == 0

    def test_short_context(self):
        assert _ngram_lookup(np.array([5], np.int32), 1, 4, 4).size == 0

    def test_truncated_at_context_end(self):
        # match lands 2 tokens before the suffix: only 2 continuation
        # tokens exist to draft
        ctx = np.array([1, 9, 9, 4, 4, 1], np.int32)
        out = _ngram_lookup(ctx, nmin=1, nmax=1, k=4)
        assert out.tolist() == [9, 9, 4, 4]


class TestBatchNGramLookup:
    def test_matches_scalar_lookup_randomized(self):
        # the vectorized batch lookup must agree row-for-row with the
        # unit-pinned scalar lookup across random small-vocab contexts
        # (small vocab => plenty of suffix collisions to exercise the
        # longest-n / most-recent / truncation tie-breaks)
        rng = np.random.default_rng(0)
        B, cap, k = 8, 48, 4
        for trial in range(6):
            ctx = np.zeros((B, cap), np.int32)
            lens = np.zeros((B,), np.int64)
            active = np.ones((B,), bool)
            active[trial % B] = False  # one inactive row per trial
            for i in range(B):
                L = int(rng.integers(2, cap + 1))
                ctx[i, :L] = rng.integers(0, 6, size=L)
                lens[i] = L
            drafts, n = _batch_ngram_lookup(ctx, lens, active, 1, 4, k)
            for i in range(B):
                if not active[i]:
                    assert n[i] == 0
                    continue
                ref = _ngram_lookup(ctx[i, : lens[i]], 1, 4, k)
                assert n[i] == ref.size, (trial, i)
                assert drafts[i, : n[i]].tolist() == ref.tolist(), (trial, i)

    def test_inactive_rows_never_draft(self):
        ctx = np.tile(np.array([5, 6, 5, 6, 5, 6], np.int32), (2, 1))
        lens = np.array([6, 6], np.int64)
        drafts, n = _batch_ngram_lookup(
            ctx, lens, np.array([True, False]), 1, 4, 4)
        assert n[0] > 0 and n[1] == 0
        assert not drafts[1].any()

    def test_no_match_rows_zero(self):
        ctx = np.array([[1, 2, 3, 4, 5, 0]], np.int32)
        _, n = _batch_ngram_lookup(
            ctx, np.array([5], np.int64), np.array([True]), 2, 4, 4)
        assert n[0] == 0


class _StubEngine:
    """The minimal engine surface NGramProposer touches: ecfg dims plus
    the slots list (objects with .request)."""

    class _Ecfg:
        max_batch_size = 4
        max_seq_len = 64

    class _Slot:
        def __init__(self):
            self.request = None

    class _Req:
        def __init__(self, rid, prompt):
            self.request_id = rid
            self.prompt = list(prompt)
            self.output = []

    def __init__(self):
        self.ecfg = self._Ecfg()
        self.slots = [self._Slot() for _ in range(4)]


class TestProposerHygiene:
    """A cancelled/evicted request's context must never influence a
    successor's proposals (the satellite regression for proposer state
    hygiene on eviction)."""

    REPETITIVE = [7, 8, 7, 8, 7, 8, 7]   # guaranteed ngram match
    BLAND = [1, 2, 3]                     # guaranteed no match

    def _tokens(self, eng):
        B = eng.ecfg.max_batch_size
        return np.zeros((B,), np.int32), np.zeros((B,), np.int32)

    def test_evicted_context_never_leaks_to_successor(self):
        prop = NGramProposer(SpeculationConfig(mode="ngram"))
        eng = _StubEngine()
        eng.slots[0].request = _StubEngine._Req("req-A", self.REPETITIVE)
        _, n = prop.propose(eng, *self._tokens(eng))
        assert n[0] > 0  # predecessor really was drafting
        prop.on_evict(eng, 0)
        eng.slots[0].request = _StubEngine._Req("req-B", self.BLAND)
        drafts, n = prop.propose(eng, *self._tokens(eng))
        assert n[0] == 0, "evicted request's context leaked into successor"
        assert not drafts[0].any()

    def test_slot_reuse_without_evict_reseeds_by_request_id(self):
        # even if the engine never called on_evict (crash path), the
        # request_id stamp must force a reseed for the new occupant
        prop = NGramProposer(SpeculationConfig(mode="ngram"))
        eng = _StubEngine()
        eng.slots[0].request = _StubEngine._Req("req-A", self.REPETITIVE)
        _, n = prop.propose(eng, *self._tokens(eng))
        assert n[0] > 0
        eng.slots[0].request = _StubEngine._Req("req-B", self.BLAND)
        _, n = prop.propose(eng, *self._tokens(eng))
        assert n[0] == 0

    def test_incremental_append_tracks_output(self):
        prop = NGramProposer(SpeculationConfig(mode="ngram"))
        eng = _StubEngine()
        req = _StubEngine._Req("req-A", self.BLAND)
        eng.slots[0].request = req
        _, n = prop.propose(eng, *self._tokens(eng))
        assert n[0] == 0
        # the OUTPUT develops a repeating motif: the incremental append
        # must pick it up without a reinstall
        req.output.extend([4, 5, 4, 5, 4])
        drafts, n = prop.propose(eng, *self._tokens(eng))
        assert n[0] > 0
        assert drafts[0, 0] == 5  # continuation after most recent [4]


class TestVerifyOp:
    def _setup(self, B=2, S=5, H=4, KVH=2, D=128, ps=16, pps=8):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(ks[0], (B, S, H, D))
        # a pool of one layer: the ops take the pool whole and a layer
        kp = _rand(ks[1], pool_shape(1, B * pps + 1, ps, KVH, D))
        vp = _rand(ks[2], pool_shape(1, B * pps + 1, ps, KVH, D))
        pt = (1 + jnp.arange(B * pps, dtype=jnp.int32)).reshape(B, pps)
        positions = jnp.array([10, 37], jnp.int32)[:B]
        return q, kp, vp, pt, positions

    def test_matches_reference(self, kernel_mode):
        q, kp, vp, pt, pos = self._setup()
        out = paged_attention_verify(q, kp, vp, pt, pos, layer=0)
        ref = _verify_reference(q, kp, vp, pt, pos, 0, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_row_equals_decode_at_that_length(self, kernel_mode):
        # row s of the span must equal a plain decode step with
        # length = positions + s + 1 (S=1 degenerates to decode exactly)
        q, kp, vp, pt, pos = self._setup(S=3)
        out = paged_attention_verify(q, kp, vp, pt, pos, layer=0)
        for s in range(3):
            dec = paged_attention_decode(q[:, s], kp, vp, pt, pos + s + 1,
                                         layer=0)
            np.testing.assert_allclose(out[:, s], dec, atol=2e-3, rtol=2e-3)

    def test_near_table_end(self, kernel_mode):
        # span launched near the last page: the kernel's page loop must
        # clamp to this sequence's table instead of walking past it
        q, kp, vp, pt, _ = self._setup(B=2, S=5, pps=4)
        pos = jnp.array([4 * 16 - 5, 7], jnp.int32)
        out = paged_attention_verify(q, kp, vp, pt, pos, layer=0)
        ref = _verify_reference(q, kp, vp, pt, pos, 0, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


SPEC_MODES = [
    pytest.param({"mode": "ngram", "num_speculative_tokens": 4}, id="ngram"),
    # self-speculation: draft shares the target weights (acceptance ~1)
    pytest.param({"mode": "draft", "num_speculative_tokens": 4},
                 id="draft-self"),
    # genuinely different draft (1 layer vs 2): drafts mostly reject —
    # committed tokens must STILL be exactly the target's greedy stream
    pytest.param({"mode": "draft", "num_speculative_tokens": 3,
                  "draft_model": "tiny-llama",
                  "draft_model_overrides": {"n_layers": 1}},
                 id="draft-distinct"),
]


class TestEngineSpeculation:
    def _engine(self, model="tiny-llama", spec=None, **kw):
        cfg = get_config(model)
        params = init_params(cfg, jax.random.PRNGKey(0))
        ecfg = EngineConfig(
            max_batch_size=4, page_size=8, max_pages=64, max_seq_len=64,
            prefill_buckets=(16, 32), speculation=spec, **kw,
        )
        return InferenceEngine(params, cfg, ecfg), cfg

    def _greedy(self, engine, prompts, max_tokens=24, **kw):
        outs = []
        for p in prompts:
            outs.append(engine.generate(p, max_tokens=max_tokens,
                                        timeout_s=120, **kw)["token_ids"])
        engine.stop()
        return outs

    PROMPTS = [[1, 2, 3, 4], [7, 5, 3], [2, 2, 9, 9, 4, 1]]

    @pytest.mark.parametrize("spec", SPEC_MODES)
    def test_greedy_on_equals_off(self, spec):
        base_eng, _ = self._engine()
        base = self._greedy(base_eng, self.PROMPTS)
        spec_eng, _ = self._engine(spec=spec)
        out = self._greedy(spec_eng, self.PROMPTS)
        assert out == base

    def test_greedy_equivalence_learned_positional(self):
        # tiny-gpt2: learned position embeddings exercise the pos_emb
        # branch of the verify forward (and the draft prefill/propose)
        base_eng, _ = self._engine(model="tiny-gpt2")
        base = self._greedy(base_eng, self.PROMPTS, max_tokens=16)
        spec_eng, _ = self._engine(
            model="tiny-gpt2",
            spec={"mode": "draft", "num_speculative_tokens": 3})
        out = self._greedy(spec_eng, self.PROMPTS, max_tokens=16)
        assert out == base

    def test_stop_sequence_mid_speculation(self):
        # pick a stop sequence from the plain greedy stream so it matches
        # mid-generation; the spec engine must stop at the same point and
        # strip the matched tail identically
        base_eng, _ = self._engine()
        ref = base_eng.generate(self.PROMPTS[0], max_tokens=24,
                                timeout_s=120)["token_ids"]
        base_eng.stop()
        stop = [ref[7:9]]  # 2-token stop hit mid-stream
        plain_eng, _ = self._engine()
        plain = plain_eng.generate(self.PROMPTS[0], max_tokens=24,
                                   timeout_s=120, stop=stop)
        plain_eng.stop()
        assert plain["finish_reason"] == "stop"
        spec_eng, _ = self._engine(
            spec={"mode": "draft", "num_speculative_tokens": 4})
        out = spec_eng.generate(self.PROMPTS[0], max_tokens=24,
                                timeout_s=120, stop=stop)
        spec_eng.stop()
        assert out["finish_reason"] == "stop"
        assert out["token_ids"] == plain["token_ids"]

    def test_cancellation_mid_speculation(self):
        import time as _time

        spec_eng, _ = self._engine(
            spec={"mode": "draft", "num_speculative_tokens": 4})
        req, gen = spec_eng.open_stream(self.PROMPTS[0], max_tokens=48,
                                        timeout_s=120)
        first = next(gen)
        assert isinstance(first, int)
        spec_eng.cancel(req.request_id)
        list(gen)  # drain to termination
        assert req.finish_reason == "cancelled"
        # the slot and its pages must free at the next step boundary
        deadline = _time.monotonic() + 10
        while _time.monotonic() < deadline:
            if spec_eng.stats()["active"] == 0:
                break
            _time.sleep(0.02)
        assert spec_eng.stats()["active"] == 0
        spec_eng.stop()

    def test_zero_draft_cap_falls_back_to_one_token(self):
        # max_tokens=2: after the prefill token the budget leaves room for
        # the bonus token only, so the round runs with zero drafts — the
        # clean 1-token fallback path — and must match plain decode
        base_eng, _ = self._engine()
        base = self._greedy(base_eng, self.PROMPTS, max_tokens=2)
        spec_eng, _ = self._engine(
            spec={"mode": "draft", "num_speculative_tokens": 4})
        out = self._greedy(spec_eng, self.PROMPTS, max_tokens=2)
        assert out == base

    def test_speculation_off_engine_has_no_spec(self):
        eng, _ = self._engine(spec={"mode": "off"})
        assert eng._spec is None
        st_keys = eng.stats().keys()
        assert "spec_acceptance_rate" not in st_keys
        eng.stop()

    def test_sampling_with_speculation_completes(self):
        spec_eng, _ = self._engine(
            spec={"mode": "ngram", "num_speculative_tokens": 4})
        r = spec_eng.generate(self.PROMPTS[2], max_tokens=20, timeout_s=120,
                              temperature=0.8, top_p=0.9, top_k=8)
        spec_eng.stop()
        assert len(r["token_ids"]) == 20
        assert r["finish_reason"] == "length"

    def test_self_spec_acceptance_and_tokens_per_step(self):
        # draft sharing the target's weights: acceptance must be high and
        # tokens/step well above the plain path's ceiling of 1.0
        spec_eng, _ = self._engine(
            spec={"mode": "draft", "num_speculative_tokens": 4})
        self._greedy(spec_eng, self.PROMPTS, max_tokens=24)
        st = spec_eng.stats()
        assert st["spec_mode"] == "draft"
        assert st["spec_proposed_tokens"] > 0
        assert st["spec_acceptance_rate"] > 0.5
        assert st["tokens_per_decode_step"] > 1.3

    def test_step_phase_metrics_observed(self):
        from ray_tpu.serve.engine import _m_step_phase

        phases = ("propose", "propose_wait", "propose_compute", "verify",
                  "sample", "cache_bookkeeping", "cancellation_check")
        before = {
            ph: _m_step_phase.count({"phase": ph, "mode": "spec"})
            for ph in phases
        }
        # draft-self speculation proposes k drafts EVERY round, so every
        # decode step is a spec round (an ngram engine may propose nothing
        # and legitimately fall back to the plain span, observed under
        # mode="plain" — no spec-mode verify/sample to count)
        spec_eng, _ = self._engine(
            spec={"mode": "draft", "num_speculative_tokens": 2})
        self._greedy(spec_eng, [self.PROMPTS[0]], max_tokens=8)
        for ph, n0 in before.items():
            assert _m_step_phase.count({"phase": ph, "mode": "spec"}) > n0, ph

    def test_zero_draft_round_falls_back_to_plain_span(self):
        from ray_tpu.serve.engine import _m_step_phase

        before = _m_step_phase.count({"phase": "verify", "mode": "plain"})
        spec_eng, _ = self._engine(
            spec={"mode": "ngram", "num_speculative_tokens": 4})
        plain_eng, _ = self._engine()
        # no repeated suffix anywhere: every round proposes zero drafts,
        # so the spec engine must decode entirely through plain spans —
        # and still match the plain engine token-for-token
        outs_s = self._greedy(spec_eng, [self.PROMPTS[0]], max_tokens=8)
        outs_p = self._greedy(plain_eng, [self.PROMPTS[0]], max_tokens=8)
        assert outs_s == outs_p
        assert _m_step_phase.count(
            {"phase": "verify", "mode": "plain"}) > before

    def test_draft_vocab_mismatch_rejected(self):
        with pytest.raises(ValueError, match="tokenizer"):
            self._engine(spec={
                "mode": "draft", "draft_model": "tiny-llama",
                "draft_model_overrides": {"vocab_size": 300},
            })

    def test_prefill_chunk_alignment_validated(self):
        with pytest.raises(ValueError, match="prefill_chunk"):
            EngineConfig(page_size=16, prefill_chunk=100)
        # alignment only matters when a chunk path can run
        cfg = EngineConfig(page_size=16, prefill_chunk=100,
                           chunked_prefill=False, prefix_caching=False)
        assert cfg.prefill_chunk == 100
