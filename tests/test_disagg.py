"""Disaggregated prefill/decode serving (serve/disagg.py).

Covers the KV migration contract (export -> import into a differently
sized page pool is token-exact vs an uninterrupted engine), the
streamed transport (multi-frame partial-blob import token-exact across
mismatched page sizes, prefix-aware role routing that skips migration,
chaos paths failing cleanly instead of hanging), the coordinator e2e
(concurrent mixed-length prompts through a real prefill+decode replica
pair match a colocated engine token-for-token, with migration metrics
emitted), KvInbox hygiene (cancel eviction + TTL sweep), the kv_dest
per-identity cache, the Pow2Router resize accounting fix, and the
channel-writer reconnect regression.
"""

import os
import queue
import threading
import time
import uuid

import numpy as np
import pytest

import jax

from ray_tpu.core.metrics import registry
from ray_tpu.models import get_config, init_params
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request

pytestmark = pytest.mark.disagg


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny-llama")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    defaults = dict(max_batch_size=4, page_size=8, max_pages=64,
                    max_seq_len=96, prefill_buckets=(16, 32))
    defaults.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**defaults))


def _mixed_prompts(cfg, lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, cfg.vocab_size, size=n)) for n in lengths]


# --------------------------------------------------------------------------
# KV round-trip: export -> import preserves exact greedy continuation
# --------------------------------------------------------------------------


class TestKvRoundTrip:
    def _roundtrip(self, src, dst, prompt, max_tokens=8):
        import uuid

        req = Request(request_id=uuid.uuid4().hex, prompt=list(prompt),
                      max_tokens=max_tokens, prefill_only=True)
        src.add_request(req)
        blob = src.export_kv_pages(req, timeout_s=120.0)
        dreq = Request(request_id=uuid.uuid4().hex, prompt=list(prompt),
                       max_tokens=max_tokens)
        dst.import_kv_pages(dreq, blob)
        assert dreq.done.wait(120.0)
        assert dreq.error is None, dreq.error
        return dreq

    def test_import_into_smaller_pages_token_exact(self, tiny):
        """page_size 8 -> 4 (different page count for the same tokens):
        the decode side repaginates and continues bit-identically."""
        cfg, params = tiny
        src = _engine(cfg, params, page_size=8)
        dst = _engine(cfg, params, page_size=4, max_pages=96)
        ref = _engine(cfg, params, page_size=8)
        try:
            for prompt in _mixed_prompts(cfg, (5, 13, 29)):
                want = ref.generate(prompt, max_tokens=8)["token_ids"]
                dreq = self._roundtrip(src, dst, prompt)
                assert list(dreq.output) == want
        finally:
            src.stop(), dst.stop(), ref.stop()

    @pytest.mark.parametrize("n,path", [(13, "bucket"), (40, "chunked")])
    def test_the_wire_holds_tokens_by_head_whatever_the_pool_holds(
            self, tiny, n, path):
        """The blob is [layers, true_len, kv_heads, head_dim] on both export
        paths, and it is what the pool's rows hold: a token's row is its
        heads side by side, so a page gathered is its tokens in order."""
        cfg, params = tiny
        kw = dict(page_size=8, prefill_buckets=(16,), prefill_chunk=16,
                  max_seq_len=96, max_pages=96, cache_dtype="float32")
        src, dst = _engine(cfg, params, **kw), _engine(cfg, params, **kw)
        ref = _engine(cfg, params, **kw)
        try:
            prompt = _mixed_prompts(cfg, (n,))[0]
            want = ref.generate(prompt, max_tokens=8)["token_ids"]
            req = Request(request_id=f"wire-{path}", prompt=list(prompt),
                          max_tokens=8, prefill_only=True)
            src.add_request(req)
            blob = src.export_kv_pages(req, timeout_s=120.0)
            L, KVH, hd = cfg.n_layers, cfg.kv_heads, cfg.hdim
            assert blob["k"].shape == blob["v"].shape == (L, n, KVH, hd)
            assert (blob["layers"], blob["kv_heads"], blob["head_dim"]) == (
                L, KVH, hd)
            dreq = Request(request_id=f"wire-{path}-dst", prompt=list(prompt),
                           max_tokens=8)
            dst.import_kv_pages(dreq, blob)
            assert dreq.done.wait(120.0) and dreq.error is None, dreq.error
            assert list(dreq.output) == want
            # the importing pool's rows ARE the blob's tokens: the ref
            # engine wrote the same prompt through its own programs
            pages = np.asarray(ref.k_pages)  # [L, 1, P, ps, KVH*hd]
            rows = pages.reshape(L, -1, KVH * hd)
            flat = np.asarray(blob["k"]).reshape(L, n, KVH * hd)
            for t in (0, n - 1):  # each token's row is somewhere in the pool
                hit = np.isclose(rows[0], flat[0, t], atol=1e-5).all(-1)
                assert hit.any(), (path, t)
        finally:
            src.stop(), dst.stop(), ref.stop()

    def test_chunked_prefill_export_token_exact(self, tiny):
        """Long prompt prefilled in chunks on the source: export gathers
        straight from the paged pools (the non-bucketed path)."""
        cfg, params = tiny
        src = _engine(cfg, params, page_size=8, prefill_buckets=(16,),
                      prefill_chunk=16, max_seq_len=96, max_pages=96)
        dst = _engine(cfg, params, page_size=4, max_pages=128)
        ref = _engine(cfg, params, page_size=8, prefill_buckets=(16,),
                      prefill_chunk=16, max_seq_len=96, max_pages=96)
        try:
            prompt = _mixed_prompts(cfg, (40,))[0]
            want = ref.generate(prompt, max_tokens=8)["token_ids"]
            dreq = self._roundtrip(src, dst, prompt)
            assert list(dreq.output) == want
        finally:
            src.stop(), dst.stop(), ref.stop()

    def test_prefix_cache_variant(self, tiny):
        """Prefill-only requests register their pages in the prefix cache
        (when enabled), and a shared-prefix re-export stays token-exact."""
        cfg, params = tiny
        src = _engine(cfg, params, page_size=8, prefix_caching=True,
                      prefill_chunk=16)
        dst = _engine(cfg, params, page_size=4, max_pages=96)
        ref = _engine(cfg, params, page_size=8)
        hits = registry.get("serve_prefix_cache_hit_tokens")
        try:
            rng = np.random.default_rng(3)
            shared = list(rng.integers(1, cfg.vocab_size, size=16))
            a = shared + list(rng.integers(1, cfg.vocab_size, size=5))
            b = shared + list(rng.integers(1, cfg.vocab_size, size=9))
            before = hits.get()
            for prompt in (a, b):
                want = ref.generate(prompt, max_tokens=8)["token_ids"]
                dreq = self._roundtrip(src, dst, prompt)
                assert list(dreq.output) == want
            # the second export reused the first's full pages
            assert hits.get() - before >= 16
        finally:
            src.stop(), dst.stop(), ref.stop()

    def test_import_rejects_mismatched_prompt(self, tiny):
        cfg, params = tiny
        src = _engine(cfg, params)
        dst = _engine(cfg, params)
        try:
            prompt = _mixed_prompts(cfg, (9,))[0]
            req = Request(request_id="exp-1", prompt=list(prompt),
                          max_tokens=4, prefill_only=True)
            src.add_request(req)
            blob = src.export_kv_pages(req, timeout_s=120.0)
            bad = Request(request_id="imp-1", prompt=list(prompt) + [1, 2],
                          max_tokens=4)
            dst.import_kv_pages(bad, blob)
            assert bad.done.wait(30.0)
            assert bad.error is not None
        finally:
            src.stop(), dst.stop()


# --------------------------------------------------------------------------
# coordinator e2e over in-process engine workers
# --------------------------------------------------------------------------


class TestDisaggCoordinator:
    @pytest.fixture(scope="class")
    def pair(self, tiny):
        from ray_tpu.serve.disagg import DisaggCoordinator, EngineWorker

        cfg, params = tiny
        pe = _engine(cfg, params, page_size=8)
        de = _engine(cfg, params, page_size=4, max_pages=96)
        ref = _engine(cfg, params, page_size=8)
        co = DisaggCoordinator([EngineWorker(pe, "p0")],
                               [EngineWorker(de, "d0")],
                               {"kv_transfer": "object",
                                "small_blob_bytes": 0})
        yield cfg, co, ref
        pe.stop(), de.stop(), ref.stop()

    def test_concurrent_mixed_lengths_token_identical(self, pair):
        """The acceptance e2e: >= 8 concurrent mixed-length prompts
        through prefill replica A + decode replica B are token-identical
        to a colocated engine, and migration metrics are emitted."""
        cfg, co, ref = pair
        prompts = _mixed_prompts(cfg, (5, 11, 17, 23, 29, 31, 8, 26))
        want = [ref.generate(p, max_tokens=8)["token_ids"] for p in prompts]
        mig_s = registry.get("serve_kv_migration_seconds")
        mig_b = registry.get("serve_kv_migration_bytes")
        tags = {"transport": "object"}
        n0, b0 = mig_s.count(tags), mig_b.get(tags)

        results = [None] * len(prompts)

        def run(i):
            results[i] = co.generate(prompts[i], max_tokens=8)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        [t.start() for t in threads]
        [t.join() for t in threads]

        for w, r in zip(want, results):
            assert r["token_ids"] == w
            assert r["kv_transport"] == "object"
            assert r["migration_bytes"] > 0
            assert r["ttft_s"] > 0
        assert mig_s.count(tags) - n0 >= len(prompts)
        assert mig_b.get(tags) - b0 > 0

    def test_channel_transport_token_identical(self, pair):
        from ray_tpu.serve.disagg import DisaggCoordinator

        cfg, co, ref = pair
        co2 = DisaggCoordinator(co._workers["prefill"],
                                co._workers["decode"],
                                {"kv_transfer": "channel"})
        prompt = _mixed_prompts(cfg, (12,))[0]
        want = ref.generate(prompt, max_tokens=8)["token_ids"]
        out = co2.generate(prompt, max_tokens=8)
        assert out["token_ids"] == want
        assert out["kv_transport"] == "channel"

    def test_stream_tokens_and_finish_reason(self, pair):
        cfg, co, ref = pair
        prompt = _mixed_prompts(cfg, (9,))[0]
        want = ref.generate(prompt, max_tokens=8)["token_ids"]
        ds = co.open_stream(prompt, max_tokens=8)
        assert list(ds.tokens()) == want
        assert ds.finish_reason == "length"
        assert ds.migration_bytes > 0

    def test_one_request_one_connected_trace(self, pair):
        """Tracing e2e: a single traced request through the disagg pipeline
        yields ONE trace — admit, queue-wait, prefill, KV export, the
        migration fetch, KV import, and decode all share the trace id and
        chain into a single connected tree under the client span."""
        from ray_tpu.util import tracing

        cfg, co, _ = pair
        prompt = _mixed_prompts(cfg, (9,))[0]
        tracing.clear()
        with tracing.start_span("client") as root:
            out = co.generate(prompt, max_tokens=6)
        assert out["token_ids"]
        spans = tracing.get_spans(root.trace_id)
        names = {s["name"] for s in spans}
        assert {"disagg.admit", "disagg.queue_wait", "disagg.prefill",
                "disagg.kv_export", "disagg.kv_migration",
                "disagg.kv_import", "disagg.decode"} <= names
        # connected: every span's parent is also in the trace
        by_id = {s["span_id"]: s for s in spans}
        for s in spans:
            if s["span_id"] != root.span_id:
                assert s["parent_id"] in by_id, s["name"]
        tree = tracing.get_trace(root.trace_id)
        assert len(tree) == 1 and tree[0]["name"] == "client"

    def test_untraced_request_records_nothing(self, pair):
        from ray_tpu.util import tracing

        cfg, co, _ = pair
        before = len(tracing.get_spans())
        co.generate(_mixed_prompts(cfg, (7,))[0], max_tokens=4)
        assert len(tracing.get_spans()) == before  # zero-overhead path


# --------------------------------------------------------------------------
# streamed KV migration (kv_transfer="stream") + prefix-aware routing
# --------------------------------------------------------------------------


class TestStreamedMigration:
    @pytest.fixture(scope="class")
    def spair(self, tiny):
        """Streamed-transport pair with mismatched page sizes (8 -> 4),
        tiny kv_window so every request spans several frames, and chunked
        prefill small enough that the 40-token prompt exercises the
        chunked (page-committed) streaming path."""
        from ray_tpu.serve.disagg import DisaggCoordinator, EngineWorker

        cfg, params = tiny
        pe = _engine(cfg, params, page_size=8, prefill_chunk=16)
        de = _engine(cfg, params, page_size=4, max_pages=96,
                     prefill_chunk=16)
        ref = _engine(cfg, params, page_size=8, prefill_chunk=16)
        co = DisaggCoordinator([EngineWorker(pe, "sp0")],
                               [EngineWorker(de, "sd0")],
                               {"kv_stream_tokens": 8,
                                "prefix_routing": False})
        yield cfg, co, ref, pe, de
        pe.stop(), de.stop(), ref.stop()

    def test_streamed_token_exact_mismatched_pages(self, spair):
        """Partial-blob (multi-frame) import is token-identical to the
        colocated engine across both prefill paths: bucketed (short
        prompts) and chunked (40 > prefill_chunk), into a 4-token-page
        pool fed from an 8-token-page source."""
        cfg, co, ref, _, _ = spair
        mig_s = registry.get("serve_kv_migration_seconds")
        tags = {"transport": "stream"}
        n0 = mig_s.count(tags)
        prompts = _mixed_prompts(cfg, (5, 13, 29, 40), seed=21)
        for prompt in prompts:
            want = ref.generate(prompt, max_tokens=8)["token_ids"]
            out = co.generate(prompt, max_tokens=8)
            assert out["token_ids"] == want
            assert out["kv_transport"] == "stream"
            assert out["migration_bytes"] > 0
        assert mig_s.count(tags) - n0 >= len(prompts)

    def test_open_stream_streamed(self, spair):
        cfg, co, ref, _, _ = spair
        prompt = _mixed_prompts(cfg, (23,), seed=22)[0]
        want = ref.generate(prompt, max_tokens=8)["token_ids"]
        ds = co.open_stream(prompt, max_tokens=8)
        assert list(ds.tokens()) == want
        assert ds.finish_reason == "length"
        assert ds.migration_bytes > 0

    def test_prefix_warm_destination_token_exact(self, spair):
        """Destination whose PrefixCache already holds the prompt's
        pages (from a prior import): re-importing the same prompt over
        the stream stays token-exact (routing disabled on this pair, so
        the second pass really is a second migration)."""
        cfg, co, ref, _, de = spair
        prompt = _mixed_prompts(cfg, (40,), seed=23)[0]
        want = ref.generate(prompt, max_tokens=8)["token_ids"]
        first = co.generate(prompt, max_tokens=8)
        assert first["token_ids"] == want
        assert de.prefix_digest()["hashes"]  # dest cache is now warm
        again = co.generate(prompt, max_tokens=8)
        assert again["token_ids"] == want
        assert again["kv_transport"] == "stream"

    def test_prefix_route_skips_migration(self, spair):
        """The tentpole routing win: a repeat prompt whose prefix is
        warm on the decode replica runs there directly — kv_transport
        'skipped', zero migration bytes, token-identical, for both the
        blocking and streaming APIs."""
        from ray_tpu.serve.disagg import DisaggCoordinator

        cfg, co, ref, _, _ = spair
        co2 = DisaggCoordinator(co._workers["prefill"],
                                co._workers["decode"],
                                {"kv_stream_tokens": 8,
                                 "prefix_gossip_s": 0.0})
        prompt = _mixed_prompts(cfg, (40,), seed=24)[0]
        want = ref.generate(prompt, max_tokens=8)["token_ids"]
        cold = co2.generate(prompt, max_tokens=8)
        assert cold["token_ids"] == want
        warm = co2.generate(prompt, max_tokens=8)
        assert warm["token_ids"] == want
        assert warm["kv_transport"] == "skipped"
        assert warm["migration_bytes"] == 0
        assert warm["prefix_warm_tokens"] >= 32
        ds = co2.open_stream(prompt, max_tokens=8)
        assert list(ds.tokens()) == want

    def test_streamed_smoke(self, spair):
        """Fast two-replica streamed-migration smoke for make check."""
        cfg, co, ref, _, _ = spair
        prompt = _mixed_prompts(cfg, (9,), seed=25)[0]
        out = co.generate(prompt, max_tokens=4)
        assert out["token_ids"] == ref.generate(
            prompt, max_tokens=4)["token_ids"]
        assert out["kv_transport"] == "stream"


class TestLayerMajorFraming:
    """Wire v2 (layer-major) streamed export: frames carry per-layer-group
    slabs so the stream starts during the first layers of the device->host
    pull; import must stay token-exact across mismatched page sizes, old
    token-major (v1) frames must keep importing, and anything newer than
    v2 is refused up front."""

    def _collect_frames(self, src, prompt, layout, max_tokens=8):
        frames = []
        req = Request(request_id=uuid.uuid4().hex, prompt=list(prompt),
                      max_tokens=max_tokens, prefill_only=True,
                      kv_sink=frames.append, kv_window=8,
                      kv_frame_layout=layout)
        src.add_request(req)
        assert req.done.wait(120.0)
        assert req.error is None, req.error
        return frames

    def _import_frames(self, dst, prompt, frames, max_tokens=8):
        meta = next(f for f in frames if f["seq"] == 0)
        last = next(f for f in frames if f["last"])
        dreq = Request(request_id=uuid.uuid4().hex, prompt=list(prompt),
                       max_tokens=max_tokens)
        assert dst.begin_kv_import(dreq, meta["true_len"], meta)
        for f in frames:
            dst.ingest_kv_chunk(dreq, f)
        dst.finish_kv_import(dreq, last["first_token"],
                             last.get("first_logprob"))
        assert dreq.done.wait(120.0)
        assert dreq.error is None, dreq.error
        return dreq

    @pytest.mark.parametrize("nlen,chunk", [(29, None), (40, 16)],
                             ids=["bucketed", "chunked"])
    def test_layer_major_token_exact_mismatched_pages(self, tiny, nlen,
                                                      chunk):
        """Layer-major streamed export -> 8->4 page repagination is
        token-identical to an uninterrupted engine, on both the bucketed
        and the chunked (page-committed) prefill paths."""
        cfg, params = tiny
        kw = {} if chunk is None else dict(prefill_chunk=chunk)
        src = _engine(cfg, params, page_size=8, **kw)
        dst = _engine(cfg, params, page_size=4, max_pages=96)
        ref = _engine(cfg, params, page_size=8, **kw)
        try:
            prompt = _mixed_prompts(cfg, (nlen,), seed=31)[0]
            want = ref.generate(prompt, max_tokens=8)["token_ids"]
            frames = self._collect_frames(src, prompt, "layer")
            # wire v2 on the frames: every frame is a layer slab, the
            # header stamps the version, and SOME frame starts at a
            # nonzero layer (tiny-llama's 2 layers split into 2 groups)
            meta = next(f for f in frames if f["seq"] == 0)
            assert meta["kv_wire"] == 2
            assert meta["layers"] == cfg.n_layers
            assert all("layer0" in f for f in frames)
            assert any(f["layer0"] > 0 for f in frames)
            assert all(f["k"].shape[0] < cfg.n_layers for f in frames)
            dreq = self._import_frames(dst, prompt, frames)
            assert list(dreq.output) == want
        finally:
            src.stop(), dst.stop(), ref.stop()

    def test_token_major_legacy_frames_still_import(self, tiny):
        """Wire v1 (token-major, kv_frame_layout='token'): frames carry
        the full layer stack, no version marker — and the importer keeps
        accepting them token-exactly (old senders stay compatible)."""
        cfg, params = tiny
        src = _engine(cfg, params, page_size=8)
        dst = _engine(cfg, params, page_size=4, max_pages=96)
        ref = _engine(cfg, params, page_size=8)
        try:
            prompt = _mixed_prompts(cfg, (29,), seed=32)[0]
            want = ref.generate(prompt, max_tokens=8)["token_ids"]
            frames = self._collect_frames(src, prompt, "token")
            meta = next(f for f in frames if f["seq"] == 0)
            assert "kv_wire" not in meta
            assert all("layer0" not in f for f in frames)
            assert all(f["k"].shape[0] == cfg.n_layers for f in frames)
            dreq = self._import_frames(dst, prompt, frames)
            assert list(dreq.output) == want
        finally:
            src.stop(), dst.stop(), ref.stop()

    def test_wire_version_guard_rejects_future_format(self, tiny):
        cfg, params = tiny
        dst = _engine(cfg, params)
        try:
            req = Request(request_id="v3-req", prompt=[1, 2, 3],
                          max_tokens=4)
            meta = {"layers": cfg.n_layers, "kv_heads": cfg.kv_heads,
                    "head_dim": cfg.hdim, "dtype": "float32",
                    "kv_wire": 3}
            assert not dst.begin_kv_import(req, 3, meta)
            assert req.done.is_set()
            assert "kv wire format v3" in req.error
        finally:
            dst.stop()

    def test_frame_outside_staged_layers_rejected(self, tiny):
        cfg, params = tiny
        dst = _engine(cfg, params)
        try:
            prompt = [1, 2, 3, 4, 5]
            req = Request(request_id="oob-req", prompt=list(prompt),
                          max_tokens=4)
            meta = {"layers": cfg.n_layers, "kv_heads": cfg.kv_heads,
                    "head_dim": cfg.hdim, "dtype": "float32",
                    "kv_wire": 2}
            assert dst.begin_kv_import(req, len(prompt), meta)
            bad = {"request_id": req.request_id, "seq": 0, "start": 0,
                   "layer0": cfg.n_layers,  # one past the last layer
                   "k": np.zeros((1, 5, cfg.kv_heads, cfg.hdim),
                                 np.float32),
                   "v": np.zeros((1, 5, cfg.kv_heads, cfg.hdim),
                                 np.float32),
                   "last": False}
            with pytest.raises(ValueError, match="layers"):
                dst.ingest_kv_chunk(req, bad)
            dst.abort_kv_import(req, error="bad frame")
            assert req.done.is_set() and req.error == "bad frame"
        finally:
            dst.stop()

    def test_abort_mid_layer_stream_frees_pages_both_sides(self, tiny):
        """A sink dying mid-layer-stream fails the prefill request and
        returns its pages; the decode side tearing down a half-staged
        layer-major import frees the staged pages too."""
        cfg, params = tiny
        src = _engine(cfg, params, prefill_chunk=16)
        dst = _engine(cfg, params, page_size=4, max_pages=96)
        try:
            prompt = _mixed_prompts(cfg, (40,), seed=33)[0]
            # source side: collect a healthy stream first (to replay a
            # partial prefix into the importer), then a dying sink
            frames = self._collect_frames(src, prompt, "layer")
            assert len(frames) >= 3
            src_free0 = src.stats()["free_pages"]
            calls = [0]

            def dying_sink(frame):
                calls[0] += 1
                if calls[0] > 2:
                    raise RuntimeError("decode replica died mid-slab")

            req = Request(request_id=uuid.uuid4().hex, prompt=list(prompt),
                          max_tokens=8, prefill_only=True,
                          kv_sink=dying_sink, kv_window=8,
                          kv_frame_layout="layer")
            src.add_request(req)
            assert req.done.wait(60.0), "prefill hung on dead sink"
            assert req.error and "kv stream failed" in req.error
            deadline = time.monotonic() + 10
            while (src.stats()["free_pages"] != src_free0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert src.stats()["free_pages"] == src_free0

            # decode side: stage the first two layer slabs, then abort
            dst_free0 = dst.stats()["free_pages"]
            meta = next(f for f in frames if f["seq"] == 0)
            dreq = Request(request_id=uuid.uuid4().hex,
                           prompt=list(prompt), max_tokens=8)
            assert dst.begin_kv_import(dreq, meta["true_len"], meta)
            assert dst.stats()["free_pages"] < dst_free0
            for f in frames[:2]:
                dst.ingest_kv_chunk(dreq, f)
            dst.abort_kv_import(dreq, error="prefill replica died")
            assert dreq.done.is_set()
            assert "prefill replica died" in dreq.error
            assert dst.stats()["free_pages"] == dst_free0
        finally:
            src.stop(), dst.stop()


class TestStreamChaos:
    """A dying replica mid-stream must FAIL the request cleanly (no
    hang) and release every page/blob it staged."""

    def test_decode_death_fails_prefill_cleanly(self, tiny):
        """kv_sink raising (the decode-side channel is gone) fails the
        prefill request — bucketed and chunked paths — and returns its
        pages to the allocator."""
        cfg, params = tiny
        src = _engine(cfg, params, prefill_chunk=16)
        try:
            free0 = src.stats()["free_pages"]
            for n in (24, 40):  # bucketed, chunked
                def sink(frame):
                    raise RuntimeError("decode replica died")

                req = Request(request_id=uuid.uuid4().hex,
                              prompt=_mixed_prompts(cfg, (n,))[0],
                              max_tokens=8, prefill_only=True,
                              kv_sink=sink, kv_window=8)
                src.add_request(req)
                assert req.done.wait(60.0), "prefill hung on dead sink"
                assert req.error and "kv stream failed" in req.error
            deadline = time.monotonic() + 10
            while (src.stats()["free_pages"] != free0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert src.stats()["free_pages"] == free0
        finally:
            src.stop()

    def test_prefill_death_mid_stream_raises(self, tiny):
        """An error frame mid-stream (prefill replica died after some
        frames) surfaces as KvMigrationError on the decode side, with
        staged pages freed and the inbox left empty."""
        from ray_tpu.serve import disagg
        from ray_tpu.serve.disagg import KvInbox, KvMigrationError

        cfg, params = tiny
        src = _engine(cfg, params, prefill_chunk=16)
        de = _engine(cfg, params, page_size=4, max_pages=96)
        try:
            frames = []
            prompt = _mixed_prompts(cfg, (40,))[0]
            req = Request(request_id="chaos-1", prompt=list(prompt),
                          max_tokens=8, prefill_only=True,
                          kv_sink=frames.append, kv_window=8)
            src.add_request(req)
            assert req.done.wait(60.0) and req.error is None
            assert len(frames) >= 3
            free0 = de.stats()["free_pages"]
            inbox = KvInbox()
            rid = "chaos-1"
            for f in frames[:2]:
                inbox.channel.put((rid, f))
            inbox.channel.put((rid, {"request_id": rid,
                                     "error": "prefill replica died"}))
            request = {"request_id": rid, "prompt_ids": list(prompt),
                       "max_tokens": 8, "kv": {"kind": "stream"},
                       "kv_stream_idle_s": 10.0}
            t0 = time.monotonic()
            with pytest.raises(KvMigrationError, match="prefill replica"):
                disagg._import_request(de, request, inbox)
            assert time.monotonic() - t0 < 10.0  # failed fast, no hang
            assert inbox.parked() == 0
            deadline = time.monotonic() + 10
            while (de.stats()["free_pages"] != free0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert de.stats()["free_pages"] == free0
        finally:
            src.stop(), de.stop()

    def test_stream_idle_timeout_raises(self, tiny):
        """A stream that never produces a frame aborts after the idle
        window instead of hanging forever."""
        from ray_tpu.serve import disagg
        from ray_tpu.serve.disagg import KvInbox, KvMigrationError

        cfg, params = tiny
        de = _engine(cfg, params)
        try:
            inbox = KvInbox()
            request = {"request_id": "ghost", "prompt_ids": [1, 2, 3],
                       "max_tokens": 4, "kv": {"kind": "stream"},
                       "kv_stream_idle_s": 0.5}
            t0 = time.monotonic()
            with pytest.raises(KvMigrationError):
                disagg._import_request(de, request, inbox)
            assert time.monotonic() - t0 < 5.0
        finally:
            de.stop()

    def test_e2e_prefill_reject_fails_fast(self, tiny):
        """Coordinator-level: a prefill-side rejection poisons the
        stream, so the concurrent decode leg fails within the idle
        window instead of hanging, and the root cause surfaces."""
        from ray_tpu.serve.disagg import (DisaggCoordinator, EngineWorker,
                                          KvMigrationError)

        cfg, params = tiny
        # 60-token prompt: the prefill replica rejects it at admission
        # (exceeds its largest bucket); the decode replica could fit it
        pe = _engine(cfg, params)
        de = _engine(cfg, params)
        try:
            co = DisaggCoordinator([EngineWorker(pe, "cp0")],
                                   [EngineWorker(de, "cd0")],
                                   {"kv_stream_idle_s": 20.0,
                                    "prefix_routing": False})
            free0 = de.stats()["free_pages"]
            prompt = _mixed_prompts(cfg, (60,))[0]
            t0 = time.monotonic()
            with pytest.raises((ValueError, KvMigrationError)):
                co.generate(prompt, max_tokens=8, timeout_s=60.0)
            assert time.monotonic() - t0 < 20.0
            assert de.stats()["free_pages"] == free0
        finally:
            pe.stop(), de.stop()


class TestKvInboxHygiene:
    """Regression: a request cancelled between prefill and decode ingest
    used to leak its parked blob in the inbox forever."""

    def test_cancel_evicts_parked_and_drops_late_frames(self):
        from ray_tpu.serve.disagg import KvInbox

        inbox = KvInbox(maxsize=8, ttl_s=60.0)
        inbox.channel.put(("r1", {"blob": 1}))
        with pytest.raises(TimeoutError):
            inbox.take("r2", timeout=0.6)  # drains, parking r1's blob
        assert inbox.parked() == 1
        inbox.cancel("r1")
        assert inbox.parked() == 0
        # the in-flight tail of the cancelled stream is dropped at park
        inbox.channel.put(("r1", {"blob": 2}))
        with pytest.raises(TimeoutError):
            inbox.take("r2", timeout=0.6)
        assert inbox.parked() == 0

    def test_ttl_sweep_evicts_unclaimed(self):
        from ray_tpu.serve.disagg import KvInbox

        inbox = KvInbox(maxsize=8, ttl_s=1.5)
        inbox.channel.put(("r1", {"blob": 1}))
        with pytest.raises(TimeoutError):
            inbox.take("rX", timeout=0.3)
        assert inbox.parked() == 1
        time.sleep(1.3)  # past ttl_s counting the drain above
        with pytest.raises(TimeoutError):
            inbox.take("rY", timeout=0.6)  # this drain pass sweeps
        assert inbox.parked() == 0

    def test_take_still_delivers(self):
        from ray_tpu.serve.disagg import KvInbox

        inbox = KvInbox(maxsize=8, ttl_s=60.0)
        inbox.channel.put(("r1", {"blob": 1}))
        assert inbox.take("r1", timeout=5.0) == {"blob": 1}
        assert inbox.parked() == 0


# --------------------------------------------------------------------------
# satellite: kv_dest cached per replica identity across _sync
# --------------------------------------------------------------------------


class _FakeController:
    def __init__(self, replicas):
        self.replicas = replicas  # deployment name -> [fake replicas]

    @property
    def get_replicas(self):
        outer = self

        class _M:
            def remote(self, name):
                return (outer.replicas[name], 1)

        return _M()


class TestKvDestCache:
    def test_kv_dest_resolved_once_per_replica_identity(self, tiny,
                                                        monkeypatch):
        """Regression: every 1s resync used to hand back worker objects
        whose kv_dest re-resolved per call site; the coordinator cache
        must resolve ONCE per replica identity and re-resolve only when
        the membership actually changes."""
        from ray_tpu.serve import disagg
        from ray_tpu.serve.disagg import DisaggCoordinator

        monkeypatch.setattr(disagg.api, "get",
                            lambda ref, timeout=None: ref)
        pa, da = _FakeReplica("pa"), _FakeReplica("da")
        ctrl = _FakeController({"P": [pa], "D": [da]})
        co = DisaggCoordinator([], [], {"prefix_routing": False})
        co._deployments = {"prefill": "P", "decode": "D"}
        co._controller = ctrl
        co._sync(force=True)
        w = co._workers["decode"][0]
        d1 = co._kv_dest_for(w)
        d2 = co._kv_dest_for(w)
        assert d1 is d2
        assert len(da.calls) == 1
        # resync with unchanged membership: same worker, cache intact
        co._last_sync = 0.0
        co._sync(force=True)
        w2 = co._workers["decode"][0]
        assert w2 is w
        co._kv_dest_for(w2)
        assert len(da.calls) == 1
        # replica replaced: cache invalidated, new identity re-resolves
        db = _FakeReplica("db")
        ctrl.replicas["D"] = [db]
        co._last_sync = 0.0
        co._sync(force=True)
        w3 = co._workers["decode"][0]
        assert w3 is not w
        co._kv_dest_for(w3)
        assert len(db.calls) == 1
        assert w.key not in co._kv_dest_cache


class TestKvDestConcurrency:
    """Regression: the deploy path minted one KV inbox PER concurrent
    first request. LLMServer.kv_ingest and ReplicaWorker.kv_dest both
    lazily initialised without a lock, so N racing cold requests got N
    distinct channels — the prefill senders then streamed frames into
    orphaned channels no drainer reads and every import idled out.
    (EngineWorker always had the lock, which is why the in-process
    tests never caught it.)"""

    def test_concurrent_kv_ingest_single_inbox(self, tiny):
        from ray_tpu.serve.llm import LLMServer

        cfg, params = tiny
        srv = LLMServer._target(  # the class under the @deployment wrapper
            params_fn=lambda: (params, cfg),
            engine_config=dict(max_batch_size=2, page_size=8,
                               max_pages=32, max_seq_len=64),
            role="decode",
        )
        try:
            n = 8
            bar = threading.Barrier(n)
            chans = [None] * n

            def grab(i):
                bar.wait()
                chans[i] = srv.kv_ingest({})

            ts = [threading.Thread(target=grab, args=(i,))
                  for i in range(n)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            ids = {c.chan_id for c in chans}
            assert len(ids) == 1, f"minted {len(ids)} inbox channels"
            # and the one everyone got is the one decode actually drains
            assert chans[0].chan_id == srv._kv_inbox.channel.chan_id
        finally:
            srv.engine.stop()

    def test_concurrent_kv_dest_single_fetch(self, monkeypatch):
        from ray_tpu.serve import disagg
        from ray_tpu.serve.disagg import ReplicaWorker

        monkeypatch.setattr(disagg.api, "get",
                            lambda ref, timeout=None: ref)

        class _SlowReplica(_FakeReplica):
            class _Method(_FakeReplica._Method):
                def remote(self, *a):
                    time.sleep(0.05)  # widen the race window
                    return super().remote(*a)

            @property
            def handle_request(self):
                return self._Method(self)

        rep = _SlowReplica("d0")
        w = ReplicaWorker(rep)
        n = 6
        bar = threading.Barrier(n)
        dests = [None] * n

        def grab(i):
            bar.wait()
            dests[i] = w.kv_dest()

        ts = [threading.Thread(target=grab, args=(i,)) for i in range(n)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert len(rep.calls) == 1, f"kv_ingest fetched {len(rep.calls)}x"
        assert all(d is dests[0] for d in dests)


# --------------------------------------------------------------------------
# serve deployment path (role replicas + coordinator-from-controller)
# --------------------------------------------------------------------------


class TestDisaggServe:
    @pytest.fixture
    def serve_session(self, ray_start_regular):
        from ray_tpu import serve

        yield
        serve.shutdown()

    def test_deploy_disagg_two_replica_roundtrip(self, tiny, serve_session):
        """deploy_disagg on one host: STRICT_SPREAD is infeasible, the
        soft-SPREAD fallback still yields two role replicas, and output
        stays token-identical to a colocated engine."""
        from ray_tpu.serve.disagg import deploy_disagg

        cfg, params = tiny
        ecfg = dict(max_batch_size=4, page_size=8, max_pages=64,
                    max_seq_len=96, prefill_buckets=(16, 32))
        co = deploy_disagg(
            "tiny-llama",
            {"prefill_replicas": 1, "decode_replicas": 1,
             "small_blob_bytes": 0},
            engine_config=ecfg,
        )
        ref = _engine(cfg, params)
        try:
            st = co.stats()
            assert st["prefill_replicas"] == 1
            assert st["decode_replicas"] == 1
            prompts = _mixed_prompts(cfg, (5, 13, 21, 29), seed=11)
            want = [ref.generate(p, max_tokens=6)["token_ids"]
                    for p in prompts]
            results = [None] * len(prompts)

            def run(i):
                results[i] = co.generate(prompts[i], max_tokens=6,
                                         timeout_s=120.0)

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(len(prompts))]
            [t.start() for t in threads]
            [t.join() for t in threads]
            for w, r in zip(want, results):
                assert r["token_ids"] == w
        finally:
            ref.stop()
            co.close()


@pytest.mark.slow
class TestDisaggCrossHost:
    """Prefill on host A, decode on host B: KV migrates over the object
    plane between real processes, placed host-disjoint by STRICT_SPREAD."""

    @pytest.fixture
    def disagg_cluster(self):
        import subprocess
        import sys
        import textwrap
        import time as _time

        import ray_tpu

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

        def worker_env():
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["RAY_TPU_WORKER_PROCESSES"] = "0"
            env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
            env["RAY_TPU_TELEMETRY_REPORT_PERIOD_S"] = "0.5"
            env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
            return env

        rt = ray_tpu.init(
            num_cpus=1, num_tpus=0,
            system_config={"control_plane_rpc_port": 0,
                           "worker_processes": 0},
        )
        code = textwrap.dedent(f"""
            import ray_tpu
            w = ray_tpu.init(address={rt._cp_server.address!r}, num_cpus=2,
                             num_tpus=0)
            w.wait(timeout=600)
        """)
        procs = [subprocess.Popen(
            [sys.executable, "-c", code], env=worker_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ) for _ in range(2)]
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline:
            if len(rt.control_plane.alive_nodes()) >= 3:
                break
            _time.sleep(0.1)
        try:
            yield rt
        finally:
            from ray_tpu import serve

            try:
                serve.shutdown()
            except Exception:
                pass
            ray_tpu.shutdown()
            for p in procs:
                if p.poll() is None:
                    p.kill()

    def test_cross_host_disagg_token_identical(self, tiny, disagg_cluster):
        from ray_tpu.serve.disagg import deploy_disagg

        cfg, params = tiny
        ecfg = dict(max_batch_size=4, page_size=8, max_pages=64,
                    max_seq_len=96, prefill_buckets=(16, 32))
        co = deploy_disagg(
            "tiny-llama",
            {"prefill_replicas": 1, "decode_replicas": 1,
             "small_blob_bytes": 0},
            engine_config=ecfg,
        )
        ref = _engine(cfg, params)
        try:
            # STRICT_SPREAD materialized: the two role bundles sit on
            # distinct hosts by construction
            assert co._pg is not None
            for prompt in _mixed_prompts(cfg, (7, 19, 27), seed=5):
                want = ref.generate(prompt, max_tokens=6)["token_ids"]
                out = co.generate(prompt, max_tokens=6, timeout_s=300.0)
                assert out["token_ids"] == want
                assert out["kv_transport"] == "stream"
        finally:
            ref.stop()
            co.close()

    def test_cross_host_trace_spans_multiple_processes(self, tiny,
                                                       disagg_cluster):
        """One traced request, prefill on host A / decode on host B: after
        telemetry federation the HEAD's buffer holds prefill, migration,
        and decode spans from at least two distinct pids, all under the
        client's trace id."""
        import time as _time

        from ray_tpu.serve.disagg import deploy_disagg
        from ray_tpu.util import tracing

        cfg, params = tiny
        ecfg = dict(max_batch_size=4, page_size=8, max_pages=64,
                    max_seq_len=96, prefill_buckets=(16, 32))
        co = deploy_disagg(
            "tiny-llama",
            {"prefill_replicas": 1, "decode_replicas": 1,
             "small_blob_bytes": 0},
            engine_config=ecfg,
        )
        try:
            prompt = _mixed_prompts(cfg, (11,), seed=9)[0]
            tracing.clear()
            with tracing.start_span("xhost-client") as root:
                out = co.generate(prompt, max_tokens=4, timeout_s=300.0)
            assert out["token_ids"]
            needed = {"disagg.prefill", "disagg.kv_migration",
                      "disagg.decode"}
            deadline = _time.monotonic() + 60
            spans = []
            while _time.monotonic() < deadline:
                spans = tracing.get_spans(root.trace_id)
                if needed <= {s["name"] for s in spans}:
                    break
                _time.sleep(0.5)
            names = {s["name"] for s in spans}
            assert needed <= names, f"federated spans missing: {names}"
            role_pids = {s["name"]: s["pid"] for s in spans
                         if s["name"] in ("disagg.prefill", "disagg.decode")}
            # STRICT_SPREAD put the roles on different hosts => processes
            assert role_pids["disagg.prefill"] != role_pids["disagg.decode"]
            assert len({s["pid"] for s in spans}) >= 2
        finally:
            co.close()


# --------------------------------------------------------------------------
# satellite: Pow2Router stale-load accounting across update_replicas
# --------------------------------------------------------------------------


class _FakeReplica:
    def __init__(self, aid):
        self._actor_id = aid
        self.calls = []

    class _Method:
        def __init__(self, outer):
            self.outer = outer

        def remote(self, *a):
            ref = object()
            self.outer.calls.append(ref)
            return ref

    @property
    def handle_request(self):
        return self._Method(self)


class TestPow2RouterResize:
    def test_pow2_choice_bounds(self):
        from ray_tpu.serve.router import pow2_choice

        with pytest.raises(ValueError):
            pow2_choice(0, lambda i: 0)
        assert pow2_choice(1, lambda i: 0) == 0

    def test_resize_preserves_surviving_inflight(self):
        from ray_tpu.serve.router import Pow2Router

        a, b, c = (_FakeReplica(x) for x in "abc")
        r = Pow2Router("dep")
        r.update_replicas([a, b], version=1)
        r1, r2, r3 = object(), object(), object()
        r._inflight = {0: [r1, r2], 1: [r3]}
        r.update_replicas([b, c], version=2)
        # b kept its queue at its NEW index; a's refs dropped; c starts empty
        assert r._inflight == {0: [r3], 1: []}

    def test_resize_remaps_model_affinity(self):
        from ray_tpu.serve.router import Pow2Router

        a, b, c = (_FakeReplica(x) for x in "abc")
        r = Pow2Router("dep")
        r.update_replicas([a, b], version=1)
        r._model_affinity = {"m1": 0, "m2": 1}
        r.update_replicas([b, c], version=2)
        # m2's replica (b) moved to index 0; m1's replica (a) vanished
        assert r._model_affinity == {"m2": 0}

    def test_assign_under_resize_prefers_fresh_replica(self, monkeypatch):
        from ray_tpu.serve import router as router_mod
        from ray_tpu.serve.router import Pow2Router

        # every seeded ref stays pending, so load == len(inflight)
        monkeypatch.setattr(router_mod.api, "wait",
                            lambda refs, num_returns, timeout: ([], refs))
        a, b, c = (_FakeReplica(x) for x in "abc")
        r = Pow2Router("dep")
        r.update_replicas([a, b], version=1)
        r._inflight = {0: [object()], 1: [object() for _ in range(6)]}
        r.update_replicas([b, c], version=2)
        # b still shows its 6 in-flight requests; c is empty — the next
        # assigns must land on c, NOT on b-as-inherited-index-0
        for _ in range(4):
            r.assign("m", (), {})
        assert len(c.calls) == 4 and not b.calls


# --------------------------------------------------------------------------
# satellite: _Writer reconnects once over a restarted channel service
# --------------------------------------------------------------------------


class TestWriterReconnect:
    def test_put_survives_service_restart(self):
        from ray_tpu.core import channels

        reg = channels._Registry()
        svc = channels.ChannelService(reg, port=0)
        host, port = svc.server_address
        w = channels._Writer(f"{host}:{port}")
        try:
            w.put("c1", "v1", 8, 5.0)
            svc.stop()  # kills the listener AND severs the pooled conn
            svc = channels.ChannelService(reg, port=port)
            # stale pooled socket: one in-place reconnect + replay
            w.put("c1", "v2", 8, 5.0)
            q = reg.get_or_create("c1", 8)
            assert q.get_nowait() == "v1"
            assert q.get_nowait() == "v2"
        finally:
            w.close()
            svc.stop()

    def test_killed_service_surfaces_after_one_retry(self):
        from ray_tpu.core import channels

        reg = channels._Registry()
        svc = channels.ChannelService(reg, port=0)
        host, port = svc.server_address
        w = channels._Writer(f"{host}:{port}")
        try:
            w.put("c2", "v1", 8, 5.0)
            svc.stop()
            # reconnect attempt dials a dead address -> transport error
            # propagates (exactly one retry, no infinite loop)
            with pytest.raises((OSError, channels.WireError)):
                w.put("c2", "v2", 8, 1.0)
        finally:
            w.close()

    def test_channel_full_is_not_a_transport_error(self):
        from ray_tpu.core import channels

        reg = channels._Registry()
        svc = channels.ChannelService(reg, port=0)
        host, port = svc.server_address
        w = channels._Writer(f"{host}:{port}")
        try:
            w.put("c3", "v1", 1, 1.0)  # maxsize=1: queue now full
            sock_before = w._sock
            with pytest.raises(queue.Full):
                w.put("c3", "v2", 1, 0.1)
            # app-level refusal must NOT tear down / redial the socket
            assert w._sock is sock_before
        finally:
            w.close()
            svc.stop()


# --------------------------------------------------------------------------
# satellite: config + schema validation
# --------------------------------------------------------------------------


class TestDisaggConfig:
    def test_defaults_and_parse(self):
        from ray_tpu.serve.config import DisaggConfig

        cfg = DisaggConfig.parse({"prefill_replicas": 2,
                                  "kv_transfer": "channel"})
        assert cfg.prefill_replicas == 2 and cfg.decode_replicas == 1
        assert DisaggConfig.parse(cfg) is cfg

    def test_rejects_bad_values(self):
        from ray_tpu.serve.config import DisaggConfig

        with pytest.raises(ValueError, match="kv_transfer"):
            DisaggConfig.parse({"kv_transfer": "carrier-pigeon"})
        with pytest.raises(ValueError, match="replica"):
            DisaggConfig.parse({"decode_replicas": 0})
        with pytest.raises(ValueError, match="unknown"):
            DisaggConfig.parse({"prefil_replicas": 1})

    def test_schema_validates_disagg_kwargs(self):
        from ray_tpu.serve.schema import ServeConfigSchema

        with pytest.raises(ValueError, match="app 'llm'"):
            ServeConfigSchema.parse({"applications": [{
                "name": "llm",
                "import_path": "x:y",
                "kwargs": {"disagg": {"kv_transfer": "bogus"}},
            }]})
