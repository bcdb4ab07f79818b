"""Continuously-batched LLM inference engine with a paged KV cache in HBM.

The TPU rebuild of what the reference delegates to vLLM (serve.llm, A4 in
SURVEY.md §2.3): requests join and leave the running decode batch every
step (continuous batching); KV lives in fixed-size pages addressed by
per-sequence page tables (paged attention — ops/paged_attention.py's
Pallas kernel); prompt prefill runs at compile-bucketed lengths so XLA
compiles a handful of shapes, not one per prompt length.

Execution shapes are static: the decode batch is a fixed-size slot array
(inactive slots write to a reserved trash page and are masked out of
attention by length=0), so the whole serving loop reuses two compiled
programs (prefill-per-bucket + one decode).

The layers themselves are not here: every program builds a mode of
models/stack.py (Decode, a Seq chunk, a whole Seq) and runs the model's
layer kinds through `stack.run_paged` / `stack.prefill`, whatever the
family; this file owns slots, pages, per-slot state and sampling.

Two execution threads, so prefill never blocks decode cadence (TTFT vs
ITL isolation — the role of vLLM's separate prefill scheduling): a
prefill thread runs prompt compute and samples the first token; the
decode thread only scatters the finished prefill's KV into pages at a
step boundary (cheap) and carries on batching.

Tensor parallelism: pass a mesh with a "tp" axis. Params shard by the
model's logical-axis rules (q heads and kv heads over tp), the page pool
shards over its kv-head dim, and XLA partitions the compiled step.
Paged attention runs the Pallas kernel inside shard_map over the tp
axis (each shard owns a contiguous block of q/kv heads and its slice of
the page pool), so TP serving keeps the kernel path.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import itertools
import os
import queue
import threading
import time
import types
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core.config import config
from ..core.logging import get_logger
from ..core.metrics import Counter, Gauge, Histogram
from ..util import profiler, slo, tracing
from ..models import ModelConfig, stack
from ..models.transformer import (
    _head_logits,
    moe_rows_computed,
    moe_seq_groups,
    moe_step_visits,
)
from ..ops import gather_pages, pool_shape, scatter_pages
from .config import SpeculationConfig
from .program import Arg, Program
from .spec_decode import SpecDecoder

logger = get_logger("serve.engine")

# Prometheus plane (reference: serve's autoscaling/ongoing-request metrics
# + vLLM's engine stats): scraped via util.state.start_metrics_server.
_m_requests = Counter("serve_requests_finished",
                      "Engine requests finished, by finish_reason.")
_m_running = Gauge("serve_requests_running",
                   "Requests currently admitted to decode slots.")
_m_tokens = Counter("serve_tokens_generated", "Tokens emitted by the engine.")
_m_prefix_hit_tokens = Counter(
    "serve_prefix_cache_hit_tokens",
    "Prompt tokens served from the prefix cache instead of prefilled.")
# a prefix that grows turn by turn is let go between turns: its pages wait
# in the LRU (`stats()["reusable_pages"]`), and a pool that fills takes them
# from there (evicted); evicted over registered is the share of the
# histories' pages that did not survive until they were asked for again
_m_prefix_registered = Counter(
    "serve_prefix_cache_registered_pages",
    "Full prompt pages that prefilled requests entered into the prefix "
    "cache (pages another request had entered already are not counted).")
_m_prefix_evicted = Counter(
    "serve_prefix_cache_evicted_pages",
    "Cached pages no sequence referenced that the allocator took from the "
    "prefix cache's LRU because the pool had no free page.")
# a chunk is padded to prefill_chunk rows: padding's share of the chunk
# programs' rows is the first over the second
_m_chunk_padding_tokens = Counter(
    "serve_chunk_padding_tokens",
    "Rows of prefill chunk programs that held no prompt token.")
_m_chunk_rows = Counter(
    "serve_chunk_rows",
    "Rows of the prefill chunk programs dispatched (calls x the program's "
    "rows).")
# which of the chunk programs ran: prefill_chunk rows, or twice that where
# the model has the wide one (`InferenceEngine._wide_chunk`)
_m_chunk_calls = Counter(
    "serve_chunk_calls", "Prefill chunk programs dispatched, by their rows.")
# a prompt's last chunk hands its first token to the next span on the
# device; the host reads it once that span is out. read=behind_span over
# both is the share of last chunks that the loop did not wait for
_m_first_reads = Counter(
    "serve_chunk_first_token_reads",
    "Last chunks of chunked prompts, by where the host read their first "
    "token (behind_span: after it had dispatched a decode span behind the "
    "chunk, so the device stayed fed; drained: with nothing dispatched "
    "behind the chunk: no sequence was live, the request exports its keys, "
    "speculation, or the loop drained).")
_m_ttft = Histogram(
    "serve_ttft_seconds", "Time to first token.",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
)
# Per-feature decode-step breakdown: every step() iteration observes each
# phase once, tagged {phase, mode} — mode is "spec" when speculative
# decoding drives the step, "plain" for the classic span path. "verify"
# is the device dispatch (the span/verify program), "sample" the blocking
# readback, "cache_bookkeeping" the host commit loop. Spec steps split
# "propose" into "propose_wait" (blocking on a prefetched draft from the
# overlapped previous round) and "propose_compute" (inline proposer work
# plus dispatching the next round's prefetch) — the overlap win is the
# wait share staying near zero. The export path additionally observes
# "kv_framing" (mode "export"): host time slicing KV into wire frames
# and pushing them to the sink.
_m_step_phase = Histogram(
    "serve_decode_step_phase_seconds",
    "Decode step wall time by phase (propose/propose_wait/propose_compute/"
    "verify/sample/cache_bookkeeping/cancellation_check; kv_framing on "
    "the export path).",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 1.0, 5.0),
)
# Where the two engine threads spend their wall time. The decode thread's
# phases tile one iteration (`iter`: chunk, install, cancel_check, build,
# dispatch, readback, commit; propose/propose_wait under speculation) and
# `idle` is the wait for work; the prefill thread's are admit, dispatch,
# readback, publish, idle. `readback` (and `chunk_readback`, the last
# chunk's share of `chunk`) block on the device, the rest is host time.
# Buckets reach 30 s so a standstill is one observation in one phase.
_m_loop = Histogram(
    "serve_engine_loop_seconds",
    "Engine thread wall time by {thread, phase}; same readings as the "
    "engine.* / prefill.* tracing regions.",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0),
)
# A request's life in stages that tile submitted_at -> finished_at:
# pending (queued for the prefill thread), waiting_for_pages (parked, pool
# full), chunk_wait (on the chunk queue behind other prompts), prefill
# (first dispatch -> first token, decode spans between its chunks
# included), ready (first token -> decode slot), decode; kv_import on a
# disaggregated decode replica (begin_kv_import -> ready). The first four
# tile submitted_at -> first_token_at. One observation per stage visit.
_m_stage = Histogram(
    "serve_request_stage_seconds",
    "Time requests spent in each engine stage, by stage.",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
             10.0, 30.0, 60.0),
)
_m_slot_steps = Counter(
    "serve_decode_slot_steps",
    "Decode slots x steps dispatched, by state (active: the slot held a "
    "request; empty: it was padding).")
_m_page_steps = Counter(
    "serve_kv_page_steps",
    "KV pages summed over engine iterations, by state (reserved: held by "
    "a slot, a chunked prompt or a prefill awaiting install; written: "
    "those that hold at least one cached token).")
_m_deferred = Counter(
    "serve_requests_deferred",
    "Requests parked at admission, by reason (no_pages: the pool could "
    "not hold prompt + max_tokens; no_window_pages: the window page space "
    "could not hold its ring; no_state_room: the sequences that wait for a "
    "decode slot already hold all the state of their own that they may).")
_m_front = Histogram(
    "serve_front_seconds",
    "What the serve front adds around the engine, by leg (inbound: the "
    "proxy's receipt of the POST -> engine.add_request; outbound: the "
    "engine's first token -> the first SSE chunk written).",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.5, 1.0, 5.0),
)
_STAGES = ("pending", "waiting_for_pages", "chunk_wait", "prefill", "ready",
           "decode", "kv_import")
_stage_child = {s: _m_stage.labels(stage=s) for s in _STAGES}
_step_phase = {(p, m): _m_step_phase.labels(phase=p, mode=m)
               for p in ("cancellation_check", "propose", "propose_wait",
                         "propose_compute", "verify", "sample",
                         "cache_bookkeeping")
               for m in ("plain", "spec")}
_slot_active = _m_slot_steps.labels(state="active")
_slot_empty = _m_slot_steps.labels(state="empty")
_pages_reserved = _m_page_steps.labels(state="reserved")
_pages_written = _m_page_steps.labels(state="written")
# a stack of unlike layers (models/stack.py) may have two pools: the pages
# of its full-attention layers, and the window layers' rings (a fixed ring
# per decode slot, or pages of a second space that `_window_allocator`
# serves: `ModelConfig.window_paged`)
_pages_by_pool = {
    (pool, st): _m_page_steps.labels(state=st, pool=pool)
    for pool in ("full", "window") for st in ("reserved", "written")}
_m_window_pages = Counter(
    "serve_window_page_steps",
    "Per window layer, summed over engine iterations: pages that active "
    "sequences hold keys in (state=held) against the most they may, "
    "active sequences x the ring's pages (state=bound), and against what "
    "caching every key would hold there (state=full_length; counted where "
    "the window layers' pages are allocated).")
_window_held = _m_window_pages.labels(state="held")
_window_bound = _m_window_pages.labels(state="bound")
_window_full_length = _m_window_pages.labels(state="full_length")
_m_state_slots = Counter(
    "serve_state_slots_installed",
    "Decode slots whose recurrent and window state a prefilled sequence "
    "overwrote at install (the slot's reset).")
_m_state_slot_steps = Counter(
    "serve_recurrent_state_slot_steps",
    "Decode slots x steps dispatched by a model whose layers keep recurrent "
    "state per slot (scan state, delta-rule and state-space state "
    "matrices), by state "
    "(live: the slot's state belongs to a sequence; held: every slot the "
    "engine holds state for, max_batch_size): live over held is the share "
    "of that state a step had to touch.")
_state_live = _m_state_slot_steps.labels(state="live")
_state_held = _m_state_slot_steps.labels(state="held")
_m_moe_rows_computed = Counter(
    "serve_moe_rows_computed",
    "Rows the expert products of the dispatched programs computed, over "
    "every expert layer: experts x the program's tokens where nothing can "
    "drop, rows x experts x capacity else, static in a program's shape; "
    "for a decode step that visits the experts its live rows chose, the "
    "experts visited x the step's rows, read back with the span.")
_m_moe_rows_routed = Counter(
    "serve_moe_rows_routed",
    "Rows the live tokens of the dispatched programs were routed to, over "
    "every expert layer: tokens x experts a token.")
_m_moe_shared_rows = Counter(
    "serve_moe_shared_rows",
    "Rows the shared experts of the dispatched programs computed, over "
    "every expert layer: every row of a program passes through them once, "
    "whatever the routed experts beside them visit (static in a program's "
    "shape; a model without shared experts counts none).")
_m_moe_choices = Counter(
    "serve_moe_choices",
    "Where a layer that holds a share of the experts sent the live tokens' "
    "choices, over every expert layer, by kind (all: tokens x experts a "
    "token; zero: on identity experts; held: on experts held here; the "
    "rest fell on experts held elsewhere). Counted on the device and read "
    "with the tokens: a span's with its readback, a prefill's with its "
    "logits.")
_m_moe_experts = Counter(
    "serve_moe_expert_steps",
    "Experts x expert layers x decode steps of the spans read back, by "
    "state (touched: at least one live row chose the expert, so the step's "
    "product visited it: the length of the kernel's own list, added up on "
    "the device and read with the tokens; held: every expert the layers "
    "hold). Counted in every family whose decode step visits (no sharded "
    "mesh): held - touched is the experts whose weights a step left "
    "unread.")
_experts_touched = _m_moe_experts.labels(state="touched")
_experts_held = _m_moe_experts.labels(state="held")
_choices_all = _m_moe_choices.labels(kind="all")
_choices_zero = _m_moe_choices.labels(kind="zero")
_choices_held = _m_moe_choices.labels(kind="held")
_deferred_no_pages = _m_deferred.labels(reason="no_pages")
_deferred_no_window_pages = _m_deferred.labels(reason="no_window_pages")
_deferred_no_state_room = _m_deferred.labels(reason="no_state_room")
_first_behind_span = _m_first_reads.labels(read="behind_span")
_first_drained = _m_first_reads.labels(read="drained")
_front_inbound = _m_front.labels(leg="inbound")
_front_outbound = _m_front.labels(leg="outbound")


def observe_front_outbound(first_token_ns: int) -> None:
    """The proxy's half of `serve_front_seconds`: called once a stream's
    first chunk is on the wire."""
    _front_outbound.observe((tracing.now_ns() - first_token_ns) * 1e-9)


def _phases(thread: str, prefix: str, names) -> Dict[str, tuple]:
    return {n: (f"{prefix}.{n}",
                _m_loop.labels(thread=thread, phase=n.replace(".", "_")))
            for n in names}


_DECODE_PHASES = _phases("decode", "engine", (
    "iter", "idle", "chunk", "chunk.readback", "install", "cancel_check",
    "build", "dispatch", "readback", "commit", "propose", "propose_wait"))
_PREFILL_PHASES = _phases("prefill", "prefill", (
    "idle", "admit", "dispatch", "readback", "publish"))


def _prefill_phase(name: str) -> "_Phase":
    return _Phase(*_PREFILL_PHASES[name])


class _Phase(tracing.region):
    """A region that also feeds its `serve_engine_loop_seconds` child and,
    on the decode thread, the iteration's row of the token ledger
    (`ledger`: `InferenceEngine._phase_done`, read by `_account`)."""

    __slots__ = ("_sink", "_ledger")

    def __init__(self, name: str, sink, ledger=None, **attrs):
        super().__init__(name, **attrs)
        self._sink = sink
        self._ledger = ledger

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        self._sink.observe(self.elapsed_ns * 1e-9)
        if self._ledger is not None:
            self._ledger(self.name, self.elapsed_ns)
        return False


# The token ledger: where the time between a sequence's tokens goes, in
# seconds weighed by the sequences that waited (an iteration with 30 live
# slots puts its wall time into 30 sequences' gaps). The parts tile the
# decode thread's time while sequences are live, so their sum is what
# `serve_request_stage_seconds{stage="decode"}` sums for the same requests.
_m_token_wait = Counter(
    "serve_token_wait_seconds",
    "Decode-thread wall time x the slots that held a sequence meanwhile, by "
    "part (chunk_host: engine.chunk less its readback; chunk_device_wait: "
    "engine.chunk.readback; host: install + cancel_check + build + commit, "
    "propose legs under speculation; dispatch; device_wait: "
    "engine.readback; loop: the iteration's remainder and the time between "
    "iterations). A host phase that ends while a program the decode thread "
    "dispatched before it, a span or a prefill chunk, is still unfinished "
    "kept no sequence waiting for the host: its seconds are device_wait "
    "(chunk_device_wait for engine.chunk).")
_token_wait = {p: _m_token_wait.labels(part=p) for p in (
    "chunk_host", "chunk_device_wait", "host", "dispatch", "device_wait",
    "loop")}
_m_span_seconds = Counter(
    "serve_decode_span_seconds",
    "Wall time of decode spans on the device's queue (from the later of "
    "the span's dispatch and the readback before it, to its own readback), "
    "by the slots the span was dispatched with (live_le) and whether "
    "prefill programs went out before it since the last span (prefill=1: a "
    "chunk or a bucket program of the prefill thread).")
_m_span_steps = Counter(
    "serve_decode_span_steps",
    "Decode steps of the spans in serve_decode_span_seconds, same labels.")
# [min((live - 1).bit_length(), 7)][shared with prefill] -> (seconds, steps)
_span_children = [
    [(_m_span_seconds.labels(live_le=le, prefill=p),
      _m_span_steps.labels(live_le=le, prefill=p)) for p in ("0", "1")]
    for le in ("1", "2", "4", "8", "16", "32", "64", "+Inf")]
_m_ahead = Counter(
    "serve_decode_ahead_steps",
    "Decode steps of the spans that were dispatched while the program the "
    "decode thread dispatched before them, a span or a prefill chunk, was "
    "unfinished on the device: over serve_decode_span_steps, the share of "
    "steps the device found queued when it finished what it had.")
_m_interleaved = Counter(
    "serve_decode_interleaved_prefill_tokens",
    "At each decode span, live slots x the prefill tokens (padded, as the "
    "programs compute them) dispatched since the last span: over committed "
    "decode tokens, the prefill tokens a decoded token waited behind.")


_m_weights_version = Gauge(
    "serve_weights_version",
    "Monotonic generation stamp of the weights an engine is serving "
    "(bumped by update_params live swaps), by role.")


@dataclasses.dataclass
class EngineConfig:
    max_batch_size: int = 8
    page_size: int = 16
    max_pages: int = 512  # total pages in the cache pool (incl. trash page)
    # pages of the window page space (incl. its trash page), where the
    # model's window layers hold their keys in allocated pages
    # (`ModelConfig.window_paged`); no other model reads it
    max_window_pages: int = 0
    max_seq_len: int = 1024
    prefill_buckets: tuple = (64, 128, 256, 512, 1024)
    # >1: prompts that wait together prefill together, in one padded
    # [K, bucket] dispatch. No benchmark cell sets it (ROADMAP D4 decides
    # whether it stays; D15 the batched bucket path it steers).
    prefill_batch_size: int = 1
    # With prefill_batch_size=K, padded batch shapes compile at {1, K, 2K,
    # 4K, ...} up to this cap and the prefill thread drains the whole
    # queue into one dispatch at the smallest covering tier; 0 leaves K
    # the cap. No cell sets it (D4).
    prefill_max_batch: int = 32
    # Chunked prefill (vLLM-style): prompts longer than prefill_chunk are
    # processed in chunks ON THE DECODE THREAD, with decode spans between:
    # one chunk an engine iteration for each prompt that waits in the
    # chunk queue, and no more rows than busy_span x prefill_chunk, the
    # span that follows a step a chunk (`InferenceEngine._advance_chunks`)
    # — a long prompt never monopolizes the device, so running requests
    # keep their inter-token latency AND the long prompt's KV lands
    # straight in its pages (no separate scatter). Also lifts the bucket
    # cap: prompts up to max_seq_len serve even past the largest compiled
    # bucket. prefill_chunk is the prompt length above which a prompt is
    # chunked, the fewest cached tokens that make a prefix-cache hit (the
    # hit itself is to the page), and the rows of THE chunk program; a
    # model whose chunk costs its experts' weights whatever its rows has a
    # second program of twice the rows for a prompt with more than
    # prefill_chunk tokens left (`_wide_chunk`: a rule on the model's
    # shapes, no option). Must be a multiple of page_size.
    chunked_prefill: bool = True
    prefill_chunk: int = 256
    eos_token_id: Optional[int] = None
    cache_dtype: str = "bfloat16"
    # Decode steps per device dispatch (vLLM multi-step scheduling
    # analogue): sampling stays on device and K tokens come back per
    # readback. Tokens stream in bursts of K, and a span boundary is the
    # only point where a prefilled request can enter the batch or a
    # finished one leave it: a slot whose answer ends inside a span decodes
    # to the span's end for nothing, at most K - 1 steps. 1 = classic
    # per-token stepping.
    # The loop runs one span ahead (`InferenceEngine.step`): the next span
    # is dispatched from the device's own carry before this one is read
    # back, so the placements, the call, the readback and the host's
    # commit pass while the device computes and a longer span buys no
    # throughput. (16 against 4 once measured +43% decode tok/s on v5e:
    # that was the dispatch and the readback on every token's path.) What
    # the length still sets is how many decode steps a program that
    # arrives in a quiet period finds queued ahead of it: two spans of 8,
    # where one of 16 was.
    decode_span: int = 8
    # While a prefill is queued or running, decode spans shrink to this so
    # the single device yields quickly and first tokens (which come from
    # the PREFILL program) aren't pinned behind a long decode span —
    # vLLM-style prefill priority without chunking the prefill itself.
    # Once the prefill backlog drains, spans return to decode_span. Both
    # lengths run the SAME decode program, which takes its step count as
    # an argument (`InferenceEngine._build_decode`), and a prefill program
    # finds at most two busy spans queued ahead of it.
    # No cell sets it or `adaptive_span` (D4).
    busy_span: int = 4
    adaptive_span: bool = True
    # Automatic prefix caching (vLLM APC analogue): full prompt pages are
    # content-addressed by a chained hash of their token prefix and kept
    # (refcounted) after their request finishes; a new prompt sharing the
    # prefix reuses those pages and prefills only the tail through the
    # chunked path. Cached zero-ref pages are reclaimed LRU-first under
    # allocator pressure, so caching never reduces serveable capacity.
    # Requires chunked_prefill (hits enter through the chunk scheduler).
    prefix_caching: bool = True
    # Speculative decoding (serve/spec_decode.py): None/"off" = classic
    # one-token decode; a SpeculationConfig (or its dict form from YAML)
    # with mode "ngram"/"draft" turns decode steps into propose-k +
    # verify-once rounds committing 1..k+1 tokens each.
    speculation: Optional[Any] = None

    def __post_init__(self) -> None:
        if (self.chunked_prefill or self.prefix_caching) and (
                self.prefill_chunk % self.page_size != 0):
            raise ValueError(
                "prefill_chunk must be a multiple of page_size when "
                "chunked prefill or prefix caching is enabled (chunk KV "
                "lands directly in pages and a cache hit resumes at a page): "
                f"prefill_chunk={self.prefill_chunk} "
                f"page_size={self.page_size}")
        if self.speculation is not None:
            self.speculation = SpeculationConfig.parse(self.speculation)

    @property
    def pages_per_seq(self) -> int:
        """Width of a sequence's page table in THE pool (`max_pages`): every
        layer's pages for the one-block models, the full-attention layers'
        for a stack of unlike layers. Window layers hold their keys beside
        it, in one of two designs (models/stack.py: ring_pages): a fixed
        ring per decode slot, sized by `max_batch_size` (the "window"
        kind), or a ring of pages allocated from a second page space of
        `max_window_pages` pages, as many as the sequence's tokens need and
        the ring's width at the most (the "swa" kind)."""
        return -(-self.max_seq_len // self.page_size)

    @property
    def span_rows(self) -> int:
        """Rows of a decode span's readback, K: the longest span the loop
        picks. The decode program writes a span of n <= K steps into rows
        [:n] and leaves the rest zero."""
        return max(1, self.decode_span,
                   self.busy_span if self.adaptive_span else 0)

    def prefill_tiers(self) -> List[int]:
        """Compiled padded-batch sizes: {1, K, 2K, 4K, ...} capped at
        prefill_max_batch. Bounded count (log2 of the cap) keeps compile
        cost predictable while every burst size pads to <2x itself.
        prefill_batch_size=1 means batching is OFF — tiers stay [1]
        (steady low-QPS serving pays padding and per-tier compiles for
        nothing; the r3 measurement that motivated this default)."""
        K = max(1, self.prefill_batch_size)
        if K == 1:
            return [1]
        cap = max(K, self.prefill_max_batch) if self.prefill_max_batch else K
        tiers = {1, K}
        t = K
        while t < cap:
            t *= 2
            tiers.add(min(t, cap))
        return sorted(tiers)


@dataclasses.dataclass
class Request:
    request_id: str
    prompt: List[int]
    max_tokens: int
    temperature: float = 0.0
    top_p: float = 1.0   # nucleus sampling mass (1.0 = off)
    top_k: int = 0       # rank cut (0 = off)
    # stop sequences as TOKEN-ID lists; a matched suffix finishes the
    # request ("stop") and is stripped from the final output. A flat
    # [int, ...] (vLLM's stop_token_ids convention) normalizes to one
    # single-token stop per id at admission.
    stop: Optional[List[List[int]]] = None
    # stream hold-back: with stops configured, the newest max(stop)-1
    # tokens wait here before emitting so a matched stop sequence never
    # leaks to streaming consumers (flushed at finish)
    _held: List[int] = dataclasses.field(default_factory=list)
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    # per-token logprob of each OUTPUT token under the raw model
    # distribution (log_softmax of the unscaled logits — temperature/
    # top-p/top-k shape what gets SAMPLED, not what gets REPORTED, which
    # is what both the OpenAI `logprobs` field and RL importance ratios
    # need). Aligned 1:1 with `output`, stripped in lockstep when eos or
    # a stop suffix is removed. None entries mark tokens whose logits
    # were unavailable (speculative commits, migration-seeded tokens
    # from pre-logprob exports).
    output_logprobs: List[Optional[float]] = dataclasses.field(
        default_factory=list)
    # generation stamp for online RL staleness accounting: the engine's
    # weights_version when this request's first token was sampled
    weights_version: Optional[int] = None
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    error: Optional[str] = None
    finish_reason: Optional[str] = None  # "stop" (eos) | "length"
    # seconds on tracing.now_ns()'s clock, each the same reading as the
    # stage boundary it coincides with (so differences tile exactly)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # the open stage, when it began (ns), and the seconds of closed
    # stages by name (see serve_request_stage_seconds); _enter_stage moves
    stage: Optional[str] = "pending"
    stage_ns: int = 0
    stage_seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    # receipt instant at the serve front (tracing.now_ns on the proxy),
    # carried in the payload's reserved "_received_ns" key; None when the
    # request did not come through the proxy
    received_ns: Optional[int] = None
    # root of this request's stage spans when it is traced (the caller's
    # thread carried a span at add_request, or the sampler fired)
    span: Optional[Any] = None
    # streaming consumers: tokens pushed as generated, None terminates
    stream_q: Optional["queue.Queue"] = None
    # set by engine.cancel(): the request finishes ("cancelled") at its
    # next scheduling point and its pages free — wherever it currently is
    cancelled: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    # disaggregated serving: prefill-only requests run the normal prefill
    # path but never take a decode slot — at install time their KV is
    # gathered into a host blob (export_kv_pages) and the request finishes
    # with finish_reason="prefill_done". Pages are only held for the
    # prompt, not prompt+max_tokens.
    prefill_only: bool = False
    _kv_export: Optional[Dict[str, Any]] = None
    # admitted and not yet in a slot: its state (`stack.new_request_state`)
    # lies outside the slots' (engine `_states_out` counts these)
    _state_out: bool = False
    # its last chunk's first token while the host has not read it (`_First`)
    _first: Optional[Any] = None
    # streamed KV export (disaggregated serving): when set on a
    # prefill_only request, KV frames are pushed to this callable as
    # prefill commits them (page-window slices of the bucketed row cache,
    # or per-chunk gathers on the chunked path) instead of one blob
    # parked in _kv_export after the first token. The sink runs on engine
    # threads and must never block for long; a raising sink fails the
    # request. Frame shape: see _stream_kv_frames.
    kv_sink: Optional[Callable[[Dict[str, Any]], None]] = None
    kv_window: int = 256  # tokens per streamed frame (bucketed path)
    # streamed-frame layout: "layer" (wire v2 — frames carry a slab of
    # consecutive layers for a token range, so the stream starts during
    # the first layers of the device->host pull), "token" (wire v1 —
    # all layers per frame), or "" to follow config.kv_frame_layout
    kv_frame_layout: str = ""

    def __post_init__(self) -> None:
        self.stage_ns = tracing.now_ns()
        self.submitted_at = self.stage_ns * 1e-9

    def _emit(self, tok: Optional[int]) -> None:
        if self.stream_q is not None:
            self.stream_q.put(tok)

    def enter_stage(self, stage: Optional[str], now_ns: int) -> None:
        """Close the open stage at `now_ns` (counter, and a child span
        when traced) and open `stage` (None: the request is finished)."""
        prev = self.stage
        if prev is not None:
            seconds = (now_ns - self.stage_ns) * 1e-9
            _stage_child[prev].observe(seconds)
            self.stage_seconds[prev] = self.stage_seconds.get(prev, 0.0) \
                + seconds
            if self.span is not None:
                tracing.record_child(self.span, "engine.stage." + prev,
                                     self.stage_ns, now_ns)
        self.stage, self.stage_ns = stage, now_ns


class TokenStream:
    """Iterator over a streaming request's tokens (first at TTFT, not at
    completion); raises the request's error, if any, at the end. An
    object, not a generator, so the serve front can read the request's
    `first_token_ns` once the first chunk is on the wire."""

    def __init__(self, request: Request, timeout_s: float):
        self.request = request
        self._timeout_s = timeout_s
        self._done = False

    def __iter__(self) -> "TokenStream":
        return self

    def __next__(self) -> int:
        if self._done:
            raise StopIteration
        tok = self.request.stream_q.get(timeout=self._timeout_s)
        if tok is None:
            self._done = True
            if self.request.error:
                raise ValueError(self.request.error)
            raise StopIteration
        return tok

    def close(self) -> None:
        self._done = True

    @property
    def first_token_ns(self) -> Optional[int]:
        at = self.request.first_token_at
        return None if at is None else int(at * 1e9)


class _ChunkState:
    """One long prompt mid-chunked-prefill."""

    __slots__ = ("request", "pages", "table", "true_len", "done",
                 "emitted_upto", "sink_seq", "state", "window_table")

    def __init__(self, request: Request, pages: List[int], table, true_len: int):
        self.request = request
        self.pages = pages
        self.table = table  # np [pages_per_seq]
        # np [ring]: its pages in the window page space, where there is one
        self.window_table = None
        self.true_len = true_len
        # prompt tokens whose keys are in its pages (a cache hit's, then
        # its chunks'): where its next chunk starts
        self.done = 0
        # streamed export bookkeeping: tokens already pushed to kv_sink
        # (page-aligned except after the final frame) and the frame seq
        self.emitted_upto = 0
        self.sink_seq = 0
        # what the chunks so far left behind beside the pages
        # (stack.new_request_state), handed from chunk to chunk
        self.state = None


class _First:
    """A chunked prompt's first token from its last chunk's dispatch to the
    host's read of it (`InferenceEngine._read_first`). The chunk program
    drew it; the sequence's first span takes it from the device
    (`_install_ready`), and the host reads it once that span is out."""

    __slots__ = ("request", "token", "row", "rows", "tokens",
                 "weights_version", "slot", "pages")

    def __init__(self, request: Request, token, row, rows: int, tokens: int,
                 weights_version: int):
        self.request = request
        self.token = token  # int32 scalar, on the device
        # float32 [2 (+2)], on the device: the token, its log-probability
        # and, where the device counts them, the request's choices
        self.row = row
        # the chunk's rows, and the prompt tokens among them
        self.rows, self.tokens = rows, tokens
        self.weights_version = weights_version  # at the chunk's dispatch
        # the slot that took the sequence with its token unread, if one did
        self.slot: Optional[int] = None
        # of a request that takes no slot (`max_tokens` 1: it ends at the
        # read): its pages, freed there
        self.pages: Optional[List[int]] = None


class _Slot:
    __slots__ = ("request", "pages", "position", "generated")

    def __init__(self):
        self.request: Optional[Request] = None
        self.pages: List[int] = []
        self.position = 0  # next write position (== current length)
        self.generated = 0


class _Span:
    """A decode span from its dispatch to its commit: what came out of the
    program (still on the device), and what the host needs to commit it
    without looking at `engine.slots`, which may have moved on."""

    __slots__ = ("seq", "logps", "steps", "members", "prefill_tokens",
                 "dispatched_ns", "release")

    def __init__(self, steps: int, members: Dict[int, Request],
                 prefill_tokens: int):
        self.seq = self.logps = None  # [K, B], on the device: rows [:steps]
        self.steps = steps
        # slot index -> the request the slot held at dispatch: the span
        # commits to these and to nobody who took a slot since
        self.members = members
        self.prefill_tokens = prefill_tokens  # dispatched since the last span
        self.dispatched_ns = 0  # the end of its `engine.dispatch`
        # page lists of members that ended after this span went out with
        # their tables: freed once it has been read back
        self.release: List[List[int]] = []

    @property
    def attrs(self) -> Dict[str, int]:
        """What the span's `engine.dispatch` region carries."""
        return {"live": len(self.members), "steps": self.steps,
                "prefill_tokens": self.prefill_tokens}


@functools.lru_cache(maxsize=None)
def _span_count(n: int):
    """A span's step count as the decode program takes it: a scalar that
    lies on the device already, made once a length, so that a dispatch
    places nothing for it."""
    return jnp.int32(n)


class _SpanOf:
    """A decode program with a span's step count bound: what
    `InferenceEngine._build_decode()(n, advanced)` hands out. Called (or
    lowered) with the program's arguments but `n`; `__wrapped__` is the
    jitted program itself, the same object whatever `n`."""

    def __init__(self, call, jitted, n: int):
        self._call, self.__wrapped__, self._n = call, jitted, n

    def __call__(self, *args):
        return self._call(*args, n=_span_count(self._n))

    def lower(self, *args):
        return self.__wrapped__.lower(
            *args, n=jax.ShapeDtypeStruct((), jnp.int32))


class PrefixCache:
    """Content-addressed prompt pages (vLLM automatic-prefix-caching
    analogue). A full page's KV is a pure function of the token prefix
    through its last token (causal attention + absolute positions), so
    page i of a prompt is keyed by the CHAIN hash of pages 0..i. Shared
    pages are refcounted; zero-ref pages sit in an LRU the allocator can
    reclaim under pressure. All calls run under the engine's _alloc_lock.

    Safety: only FULL prompt pages are ever registered, and lookups are
    capped below the last prompt token, so every sequence prefills >= 1
    token (producing its first-token logits) and decode never writes into
    a shared page (first write position >= cached_len + 1). The one
    program that does is a prompt's last chunk that starts earlier to end
    with the page table (`_advance_chunk`): it writes again, from the same
    tokens at the same positions, rows that a hit's pages already hold."""

    def __init__(self, page_size: int):
        self.ps = page_size
        self.by_hash: Dict[bytes, int] = {}
        self.by_page: Dict[int, bytes] = {}
        self.refs: Dict[int, int] = {}
        self.lru: "OrderedDict[int, None]" = OrderedDict()  # zero-ref pages

    def page_hashes(self, prompt, n_pages: int) -> List[bytes]:
        """Chain hashes for the first n_pages full pages of `prompt`."""
        out, h = [], b""
        for i in range(n_pages):
            chunk = np.asarray(
                prompt[i * self.ps:(i + 1) * self.ps], np.int32).tobytes()
            h = hashlib.sha1(h + chunk).digest()
            out.append(h)
        return out

    def lookup_acquire(self, prompt, min_tokens: int,
                       hashes: Optional[List[bytes]] = None) -> List[int]:
        """Longest cached page run for `prompt`, to the page, refs bumped.
        Capped below the last token (>= 1 token must prefill); a run of
        fewer than `min_tokens` tokens (the chunk program's rows) is no
        hit: the tail prefill resumes at the run's end in a chunk, and
        such a prompt keeps the bucket, or the chunks from 0, that it has
        without a cache.
        `hashes`: precomputed page_hashes (callers hash OUTSIDE the
        engine's _alloc_lock; dict lookups are all that runs inside)."""
        T = len(prompt)
        max_pages = (T - 1) // self.ps  # never the page holding token T-1
        if hashes is None:
            hashes = self.page_hashes(prompt, max_pages)
        hashes = hashes[:max_pages]
        n = 0
        for h in hashes:
            if self.by_hash.get(h) is None:
                break
            n += 1
        if n * self.ps < min_tokens:
            n = 0
        pages = []
        for h in hashes[:n]:
            pid = self.by_hash[h]
            self.refs[pid] = self.refs.get(pid, 0) + 1
            self.lru.pop(pid, None)
            pages.append(pid)
        return pages

    def register(self, prompt, pages: List[int],
                 hashes: Optional[List[bytes]] = None) -> None:
        """Offer a prefilled request's full prompt pages to the cache.
        First writer wins per hash; pages already cached (the request's
        own shared prefix) are skipped. Registered pages get one ref on
        behalf of this request (dropped via release_and_filter).
        `hashes`: precomputed page_hashes (hash outside the lock)."""
        n_pages = min(len(prompt) // self.ps, len(pages))
        if hashes is None:
            hashes = self.page_hashes(prompt, n_pages)
        entered = 0
        for h, pid in zip(hashes[:n_pages], pages[:n_pages]):
            if pid in self.by_page:
                continue  # already cached (this request's shared prefix)
            if h in self.by_hash:
                continue  # another page already serves this prefix
            self.by_hash[h] = pid
            self.by_page[pid] = h
            self.refs[pid] = self.refs.get(pid, 0) + 1
            entered += 1
        if entered:
            _m_prefix_registered.inc(entered)

    def release_and_filter(self, pages: List[int]) -> List[int]:
        """Drop one ref per cached page in `pages`; -> the pages the
        caller still owns (uncached ones) to return to the allocator."""
        mine = []
        for pid in pages:
            if pid in self.by_page:
                self.refs[pid] -= 1
                if self.refs[pid] <= 0:
                    del self.refs[pid]
                    self.lru[pid] = None
                    self.lru.move_to_end(pid)
            else:
                mine.append(pid)
        return mine

    def evict(self, n: int) -> List[int]:
        """Reclaim up to n zero-ref cached pages, LRU first."""
        out = []
        while self.lru and len(out) < n:
            pid, _ = self.lru.popitem(last=False)
            del self.by_hash[self.by_page.pop(pid)]
            out.append(pid)
        _m_prefix_evicted.inc(len(out))
        return out

    def stats(self) -> Dict[str, int]:
        return {"cached_pages": len(self.by_page),
                "reusable_pages": len(self.lru)}


class _SeqPages(list):
    """The pages a sequence holds in THE pool and, beside them, `window`:
    those it holds in the window page space. It takes them as it grows
    (`InferenceEngine._grow`: chunk by chunk in prefill, page by page in
    decode) out of what admission promised it: `promised` and
    `window_promised` are the pages it may still take. Every station hands
    a sequence's pages on as one list, and `_free_pages_and_revive` gives
    back what it holds and what it never took."""

    __slots__ = ("window", "promised", "window_promised")

    def __init__(self, promised: int, window_promised: int):
        super().__init__()
        self.window: List[int] = []
        self.promised, self.window_promised = promised, window_promised


class PageAllocator:
    """Free-list over page ids; page 0 is the reserved trash page that
    inactive decode slots write into. A sequence that takes its pages as
    it grows is admitted by a promise (`promise`): pages that stay on the
    free list until it takes them (`take`) or ends (`unpromise`), and that
    nobody else is given meanwhile, so a growing sequence never waits."""

    def __init__(self, num_pages: int):
        self._free = list(range(num_pages - 1, 0, -1))
        self._promised = 0

    def alloc(self, n: int) -> Optional[List[int]]:
        if self.num_free < n:
            return None
        out = [self._free.pop() for _ in range(n)]
        return out

    def free(self, pages: List[int]) -> None:
        self._free.extend(pages)

    def promise(self, n: int) -> bool:
        if self.num_free < n:
            return False
        self._promised += n
        return True

    def take(self, n: int) -> List[int]:
        self._promised -= n
        return [self._free.pop() for _ in range(n)]

    def unpromise(self, n: int) -> None:
        self._promised -= n

    @property
    def num_free(self) -> int:
        """Pages that are free and promised to nobody."""
        return len(self._free) - self._promised


class InferenceEngine:
    def __init__(
        self,
        params,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig,
        mesh=None,
        draft_params=None,
    ):
        self._describe(model_cfg, engine_cfg, mesh)
        # the `replica.start` trace this engine was built under
        # (`serve/llm.py start_engine` sets both): its id for `get_trace`,
        # and the finished tree, which outlives the span ring; None, []:
        # built bare
        self.startup_trace_id: Optional[str] = None
        self.startup_trace: List[Dict[str, Any]] = []
        B = engine_cfg.max_batch_size
        # THE pool, one layout for every model (ops/paged_attention.py:
        # a token's kv heads in one row): the layers that cache keys and
        # values, which for a stack of unlike layers are its full-attention
        # layers alone
        P, ps = engine_cfg.max_pages, engine_cfg.page_size
        pool = self.abstract_pool()
        # the allocator that serves the window page space
        # (`cfg.window_paged`) beside `self.allocator`
        self._window_allocator = (
            PageAllocator(engine_cfg.max_window_pages) if self._ring else None)
        # What the model's layers keep per decode slot beside their pages
        # (conv tails, scan state, the window layers' rings), sized by
        # max_batch_size and the model: the empty tree for the one-block
        # models. Every program takes it and hands it back.
        self.state = self._new_state()
        # what the engine holds beside THE pool, whatever the slots hold:
        # per-slot state (tails, scan, delta-rule and state-space state)
        # and the window layers' pools
        self._state_bytes = tree_bytes(self.state)
        # a sequence's start, shared by every chunked prompt's first
        # chunk (never donated: a chunk hands back a new state)
        self._request_start = self._new_request_start()
        if mesh is not None:
            self.params = jax.device_put(params, self._param_shardings())
            # tp>1: each shard holds its kv heads' lanes of every row
            self.k_pages = jax.device_put(
                jnp.zeros(pool.shape, pool.dtype), self._kv_sharding)
            self.v_pages = jax.device_put(
                jnp.zeros(pool.shape, pool.dtype), self._kv_sharding)
        else:
            self.params = params
            self.k_pages = jnp.zeros(pool.shape, pool.dtype)
            # a pool of latents is ONE array: a token's row is key and
            # value both, and every program hands the empty tree through
            self.v_pages = (None if model_cfg.latent_cache
                            else jnp.zeros(pool.shape, pool.dtype))
        # how many sequences may hold such a state outside the slots
        # (prefilled and waiting for one, or in the chunk queue): what the
        # device has left now that it holds the weights, the pools and the
        # slots' state, HALVED (the other half is the programs' temporaries:
        # a bucket program's padded rows make state too), over one
        # sequence's state; admission parks the next one
        # (`_admit_for_prefill`). Pages bound the sequences in flight where
        # keys are most of what one holds; a state of tens of MB a sequence
        # fills the chip long before the pool runs out (chip, PR 45: 72 MB
        # each, out of memory at 1.5 x the knee). None: a backend that
        # keeps no count of its memory (the CPU), or no such state
        held = tree_bytes(self._request_start)
        free = _device_free_bytes(self.k_pages) if held else None
        self._state_room = None if free is None else max(1, free // 2 // held)
        self._states_out = 0  # such sequences now; under _alloc_lock
        self.allocator = PageAllocator(P)
        # off by derivation where layers keep recurrent state: a page hit
        # without the state at that boundary would be wrong
        self.prefix = (PrefixCache(ps)
                       if engine_cfg.prefix_caching
                       and engine_cfg.chunked_prefill
                       and not model_cfg.has_state else None)
        self.slots = [_Slot() for _ in range(B)]
        self.pending: "queue.Queue[Request]" = queue.Queue()
        self._step_count = 0
        # monotonic generation stamp of the served weights; bumped by
        # update_params (online RL weight re-sync) and stamped onto every
        # request at first-token time
        self.weights_version = 0
        # Fresh sampling stream per engine instance: a fixed base key would
        # replay identical temperature>0 outputs across restarts.
        self._base_key = jax.random.PRNGKey(
            int.from_bytes(os.urandom(4), "little")
        )
        # the chunk programs' draws of first tokens: a stream beside the
        # spans' (`step` folds in its count of spans, from 1)
        self._first_key = jax.random.fold_in(self._base_key, 0)
        self._first_draws = 0
        self._lock = threading.Lock()
        self._alloc_lock = threading.Lock()  # allocator: prefill + decode threads
        self._ready: "list" = []  # prefilled, awaiting a decode slot
        self._ready_lock = threading.Lock()
        self._waiting: "list[Request]" = []  # admitted but no pages free yet
        self._loop_thread: Optional[threading.Thread] = None
        self._prefill_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # Decode-thread wake signal: set whenever new work appears (a prefill
        # published to _ready). The decode loop clears-then-rechecks before
        # waiting, so a wake can never be lost (VERDICT r2 weak #1).
        self._work = threading.Event()
        # prefill batches currently executing (read by the decode thread's
        # adaptive-span decision; int writes are GIL-atomic)
        self._prefill_inflight = 0
        # streamed KV imports staged (begin_kv_import .. finish/abort) —
        # the disagg analogue of prefill pressure for the span decision
        self._importing = 0
        # SLO latency digests (util/slo.py, shipped with heartbeat
        # telemetry). The serving layer stamps slo_role after construction
        # (llm.LLMServer: colocated/prefill/decode), so digest handles
        # resolve lazily on first observation; the enable switch resolves
        # once here.
        self.slo_role = "engine"
        self._slo_on = slo.enabled()
        self._slo: Dict[str, slo.Digest] = {}
        if self._spec is not None:
            self._spec.start(draft_params)
        # tokens-per-decode-step accounting: committed tokens over slot
        # participations (plain: span per active slot per dispatch; spec:
        # one per active slot per round)
        self._tps_committed = 0
        self._tps_steps = 0
        # long-prompt chunk states, consumed a few chunks per step() by the
        # DECODE thread (chunk programs donate the same page pool the
        # decode program does — two threads dispatching donated updates
        # to one buffer would race; serializing on the decode thread is
        # the TPU-static-shape form of vLLM's mixed prefill/decode sched)
        self._chunk_queue: "list[_ChunkState]" = []
        self._chunk_lock = threading.Lock()
        self._requests: Dict[str, Request] = {}  # live (uncompleted) ids
        self._req_lock = threading.Lock()
        # The decode loop runs one span ahead (`step`): the span that is
        # dispatched and not read back yet; the `tokens` and `positions`
        # the last span's scan ended on, which the next span's continuing
        # slots start from without a readback; when the last readback
        # returned
        self._inflight: Optional[_Span] = None
        carry = (jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))
        if mesh is not None:
            carry = jax.device_put(carry, self._whole)
        self._carry = carry
        # first tokens the chunk programs drew and the host has not read
        # (`_First`), in the order of their dispatch; decode thread only
        self._firsts: List[_First] = []
        # an output of the program this thread dispatched last, a span or
        # a chunk: unfinished, the device is busy (`_device_busy`); and what
        # the last dispatch found of the program BEFORE it, until the phase
        # that dispatched is filed (`_dispatched`, `_phase_done`)
        self._last_out = None
        self._found_busy: Optional[bool] = None
        self._read_ns = 0
        # weight swaps that `update_params` posted for the decode thread,
        # which drains the span in flight before it rebinds
        self._swaps: List[Any] = []
        self._loop_done = False
        # the token ledger's row of the running iteration (`_account`): ns
        # of each decode phase, by whether a program dispatched before it
        # was unfinished when the phase ended (`_hidden_ns`: the sequences
        # waited for the device, not for the host); the slots live after `install` and at
        # the last iteration's end; where the last iteration ended, and
        # whether the device was busy when this one began
        self._phase_ns = {name: 0 for name, _sink in _DECODE_PHASES.values()
                          if name not in ("engine.iter", "engine.idle")}
        self._hidden_ns = dict(self._phase_ns)
        # the part of `engine.chunk.readback` that ran inside
        # `engine.chunk` (a first token read where it was dispatched)
        self._nested_read_ns = 0
        self._live = self._live_at_end = 0
        self._iter_end_ns = 0
        self._began_busy = False
        # padded prompt tokens of the bucket programs dispatched so far
        # (the prefill thread writes, the decode thread reads), what the
        # last span saw of it, and the chunk tokens dispatched since
        self._bucket_tokens = 0
        self._bucket_tokens_seen = 0
        self._chunk_tokens = 0

    def _describe(self, model_cfg: ModelConfig, engine_cfg: EngineConfig,
                  mesh) -> None:
        """What an engine is before its first array, from the two configs
        and the mesh alone: what they refuse, the sizes derived from them
        (`_tp`, `_wide`, `_ring`), where a mesh places what, and every
        jitted program (`programs`). `__init__` starts here and `abstract`
        ends here."""
        self.cfg, self.ecfg, self.mesh = model_cfg, engine_cfg, mesh
        self._tp = 1
        self._refuse_for_stack(mesh, engine_cfg)
        # the second chunk program's rows, and the ring's width in the
        # window page space (`cfg.window_paged`)
        self._wide = self._wide_chunk()
        self._ring = self._window_ring()
        self._kv_sharding = self._whole = None
        pinned: Dict[str, Any] = {}
        if mesh is not None:
            axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            self._tp = int(axis_sizes.get("tp", 1))
            KVH = model_cfg.cache_dims[1]
            if self._tp > 1 and KVH % self._tp != 0:
                raise ValueError(
                    f"tp={self._tp} must divide kv_heads={KVH} to shard the page pool"
                )
            self._kv_sharding = NamedSharding(
                mesh,
                PartitionSpec(None, None, None, None,
                              "tp" if self._tp > 1 else None),
            )
            self._whole = NamedSharding(mesh, PartitionSpec())
            pinned["out_shardings"] = (self._whole,) * 2
        self._install_state = jax.jit(
            tracing.named(functools.partial(
                stack.install_state, cfg=model_cfg,
                page_size=engine_cfg.page_size),
                "install_state"),
            donate_argnums=(0,))
        self._decode = self._build_decode()
        self._prefill_cache: Dict[int, Any] = {}
        self._chunk_fn = self._build_chunk_prefill()
        # a sequence whose first token is still on the device joins its
        # first span through the carry: its slot's row takes the token and
        # the position (`_install_ready`), and the decode programs stay as
        # they are
        self._join_carry = self._under_mesh(jax.jit(
            tracing.named(_join_carry, "join_carry"), **pinned))
        scfg = engine_cfg.speculation
        self._spec: Optional[SpecDecoder] = (
            SpecDecoder(self, scfg)
            if scfg is not None and scfg.enabled else None)

    @classmethod
    def abstract(cls, model_cfg: ModelConfig, engine_cfg: EngineConfig,
                 mesh=None) -> "InferenceEngine":
        """An engine without arrays: what `__init__` does up to its first
        allocation. It answers `programs`, `abstract_pool` and
        `abstract_state`, and serves nothing."""
        engine = object.__new__(cls)
        engine._describe(model_cfg, engine_cfg, mesh)
        return engine

    def _param_shardings(self):
        from ..models.transformer import param_axes
        from ..parallel.sharding import tree_shardings

        return tree_shardings(param_axes(self.cfg), self.mesh)

    def phase(self, name: str, **attrs: Any) -> _Phase:
        """`engine.<name>` on the decode thread (spec_decode.py times its
        propose/verify legs through this too: it cannot import this
        module, which imports it)."""
        return _Phase(*_DECODE_PHASES[name],
                      None if name in ("iter", "idle") else self._phase_done,
                      **attrs)

    def _device_busy(self) -> bool:
        """Is the program this thread dispatched last, a decode span or a
        prefill chunk, still unfinished? Asks the runtime about one of its
        outputs; no sync. The device runs this thread's programs in the
        order of their dispatch, so while the last one is unfinished the
        device never ran dry, whatever it is working on: a chunk that went
        out behind span N keeps it busy long after span N is done."""
        out = self._last_out
        if out is None:
            return False
        if out.is_ready():
            self._last_out = None
            return False
        return True

    def _dispatched(self, out) -> bool:
        """This thread has just dispatched a program, a span or a chunk, and
        `out` is one of its outputs. -> was the program BEFORE it unfinished
        (the device never ran dry while the host placed and dispatched this
        one)? The phase that dispatched is filed by that answer
        (`_phase_done`): its own program is unfinished by construction and
        says nothing about what the sequences waited for."""
        found = self._found_busy = self._device_busy()
        self._last_out = out
        return found

    def _phase_done(self, name: str, ns: int) -> None:
        """A decode phase's time, filed by what the sequences were waiting
        for while it ran: a host phase that ends with a program of this
        thread unfinished on the device (`_device_busy`) delayed no token.
        A phase that dispatched a program itself (`engine.chunk`,
        `engine.dispatch`) is asked about the program before its own, as its
        last dispatch found it (`_dispatched`). A readback leaves nothing of
        what it read unfinished, so the phase around it (`engine.chunk`
        around a first token read where its chunk went out) is asked anew."""
        found, self._found_busy = self._found_busy, None
        if name.endswith("readback"):
            hidden = False
        else:
            hidden = self._device_busy() if found is None else found
        (self._hidden_ns if hidden else self._phase_ns)[name] += ns

    @property
    def _steps_visit(self) -> bool:
        """Whether a decode step of this engine visits the experts its live
        rows chose and no others (models/stack.py `_experts`), so that its
        spans count them."""
        return moe_step_visits(self.cfg, self.mesh)

    def _wide_chunk(self) -> int:
        """Rows of the second chunk program, twice `prefill_chunk` (0: the
        model has none). A chunk that runs each expert over the rows that
        chose it (`moe_seq_groups`: routed experts, no sharded mesh, the
        rows whole in the kernel's fast memory) costs its experts' WEIGHTS'
        time whatever its rows, so a prompt with more than `prefill_chunk`
        tokens left reads them once for twice the rows; a dense chunk is
        at its products' time at `prefill_chunk` rows already and gains
        nothing. The rule is the model's shapes and the mesh, and a page
        table that holds the rows (a chunk may not run past it:
        `_advance_chunk`). Under speculation the chunks stay as they are
        (no test or cell runs a round's programs beside a wide chunk)."""
        ecfg = self.ecfg
        C = ecfg.prefill_chunk
        scfg = ecfg.speculation
        if (not ecfg.chunked_prefill or ecfg.busy_span < 2
                or (scfg is not None and scfg.enabled)
                or 2 * C > ecfg.pages_per_seq * ecfg.page_size):
            return 0
        return 2 * C if all(moe_seq_groups(self.cfg, 1, rows, self.mesh)
                            for rows in (C, 2 * C)) else 0

    def _window_ring(self) -> int:
        """Pages of a sequence's ring in the window page space (0: the
        model has none), and what the two configs must agree on there."""
        if not self.cfg.window_paged:
            return 0
        ecfg, name = self.ecfg, self.cfg.name
        ring = stack.ring_pages(
            self.cfg, ecfg.page_size,
            max(ecfg.prefill_chunk, self._wide_chunk())
            if ecfg.chunked_prefill else 0)
        if ecfg.max_window_pages <= ring:
            raise ValueError(
                f"{name!r} holds its window layers' keys in allocated "
                f"pages: EngineConfig.max_window_pages must hold one "
                f"sequence's ring of {ring} pages and the trash page; got "
                f"{ecfg.max_window_pages}")
        if max(ecfg.prefill_buckets) > self.cfg.window:
            raise ValueError(
                f"{name!r}: a prefill bucket of {max(ecfg.prefill_buckets)} "
                f"tokens is longer than the window ({self.cfg.window}): its "
                "keys would wrap their own ring before they are written; "
                "longer prompts go through chunked prefill")
        return ring

    def _refuse_for_stack(self, mesh, ecfg: EngineConfig) -> None:
        """What assumes that pages are the whole state of a request, or
        the one-block models' sharding rules, and is not made right for a
        stack of unlike layers yet: refused here, with the reason."""
        if not self.cfg.is_stack:
            return
        name = self.cfg.name
        if mesh is not None:
            raise ValueError(
                f"{name!r}: a stack of unlike layers has no sharding rules "
                "yet (models/stack.py); serve it on one chip, mesh=None")
        if self.cfg.hc_streams > 1:
            raise ValueError(
                f"{name!r}: a residual of {self.cfg.hc_streams} streams "
                "(`hc_streams`) is trained, not served: the layers' function "
                "is shared, but no decode step or chunk has been held to a "
                "reference with the streams in its carry (models/stack.py). "
                "Train it (train/lm.py make_train_step)")
        scfg = ecfg.speculation
        if scfg is not None and scfg.enabled and self.cfg.latent_cache:
            raise ValueError(
                f"{name!r}: no kernel verifies a span of drafts over a pool "
                "of latents (ops/mla_attention.py has decode and chunk), and "
                "the draft's pool is keys and values. Serve it with "
                "speculation off")
        if scfg is not None and scfg.enabled and self.cfg.window_paged:
            raise ValueError(
                f"{name!r}: a draft's keys overwrite the page behind the "
                "window in the sequence's ring, which no position rewinds, "
                "and Verify is not written over two page spaces "
                "(models/stack.py). Serve it with speculation off")
        if scfg is not None and scfg.enabled:
            raise ValueError(
                f"{name!r}: speculative decoding rewinds rejected drafts by "
                "position alone; recurrent state (conv tails, scan state, "
                "delta-rule state matrices) and window rings cannot be "
                "rewound that way. Serve it with speculation off")

    def _refuse_kv_transfer(self, what: str) -> None:
        if self.cfg.latent_cache:
            raise ValueError(
                f"{what}: {self.cfg.name!r} caches one latent row a token "
                "and no values; the KV wire carries keys and values by "
                "head. Disaggregated roles and KV export/import are "
                "refused for it")
        if self.cfg.window_paged:
            raise ValueError(
                f"{what}: {self.cfg.name!r} holds its window layers' keys "
                "in a second page space, where a page behind the window is "
                "overwritten: the KV wire carries ONE table of pages that "
                "stand for every layer. Disaggregated roles and KV "
                "export/import are refused for it")
        if self.cfg.is_stack:
            raise ValueError(
                f"{what}: {self.cfg.name!r} keeps state beside its pages "
                "(conv tails, scan state, delta-rule state matrices, "
                "window rings) that the KV wire does not carry; "
                "disaggregated roles and KV export/import are refused "
                "for it")

    def abstract_pool(self, sharding=None) -> jax.ShapeDtypeStruct:
        """k_pages / v_pages as this engine's programs take them, from its
        two configs alone: a tool that compiles the programs for a
        described chip asks here and builds no pool by hand."""
        layers, kv_heads, head_dim = self.cfg.cache_dims
        return jax.ShapeDtypeStruct(
            pool_shape(layers, self.ecfg.max_pages, self.ecfg.page_size,
                       kv_heads, head_dim),
            jnp.dtype(self.ecfg.cache_dtype), sharding=sharding)

    def _new_state(self):
        """`self.state`, from the two configs alone."""
        return stack.new_engine_state(
            self.cfg, self.ecfg.max_batch_size, self.ecfg.page_size,
            jnp.dtype(self.cfg.dtype), jnp.dtype(self.ecfg.cache_dtype),
            self.ecfg.max_window_pages)

    def _new_request_start(self):
        """`self._request_start`, from the model's config alone."""
        return stack.new_request_state(self.cfg, 1, jnp.dtype(self.cfg.dtype))

    def abstract_state(self, sharding=None):
        """What the programs take beside the pool, `self.state`, as
        abstract arrays: the window page space's two pools where the model
        has one (`cfg.window_paged`), and the per-slot state."""
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding),
            jax.eval_shape(self._new_state))

    # ------------------------------------------------------------- compiled

    @functools.cached_property
    def _forward(self):
        """One decode step's layers and head, a jit of its own inside the
        decode programs: (params, tokens [B], positions [B], page tables,
        the window tables or None, (k_pages, v_pages), state) -> (logits
        [B, V], k_pages, v_pages, state). jax keeps an inner jit's trace by
        its arguments' shapes, whoever calls it, so the two decode programs
        (`_build_decode`: one a sampler) trace the layers and their Pallas
        kernels in Python ONCE between them; XLA inlines the call."""
        cfg, ps = self.cfg, self.ecfg.page_size

        def decode_forward(params, tokens, positions, page_tables,
                           window_tables, pools, state):
            # tp>1: the paged kernel runs inside shard_map over the tp
            # axis (the mode hands it the mesh), not by XLA's fallback
            x, k_pages, v_pages, state = stack.run_paged(
                params, tokens[:, None], cfg,
                stack.Decode(cfg, positions, page_tables, ps, self.mesh,
                             window_tables),
                pools, state)
            with jax.named_scope("lm_head"):
                logits = _head_logits(x, lambda x: x[:, 0], params, cfg,
                                      "bd,dv->bv")
            return logits, k_pages, v_pages, state

        return jax.jit(decode_forward)

    def _decode_step(self, params, page_tables, window_tables, sample):
        """-> step(carry, i): one decode step of the batch as a loop body.
        `carry` is (tokens [B], positions [B], k_pages, v_pages, state);
        the step runs the layers over the tokens' pages (`_forward`), draws
        the next tokens with `sample(logits [B, V], i)` and returns the
        carry one position on and (tokens, their log-probabilities under
        the RAW distribution)."""

        def step(carry, i):
            tokens, positions, k_pages, v_pages, state = carry
            logits, k_pages, v_pages, state = self._forward(
                params, tokens, positions, page_tables, window_tables,
                (k_pages, v_pages), state)
            with jax.named_scope("sample"):
                toks = sample(logits, i)
                # logprob of the sampled token under the RAW distribution
                # (negligible next to the lm_head matmul, so it is
                # computed unconditionally)
                logps = jnp.take_along_axis(
                    jax.nn.log_softmax(logits, axis=-1),
                    toks[:, None].astype(jnp.int32), axis=-1)[:, 0]
            return (toks, positions + 1, k_pages, v_pages, state), (
                toks, logps)

        return step

    def _build_decode(self):
        """Jit the decode programs of this engine, one a sampler: a loop of
        `n` steps over the single-step body (`_decode_step`) with
        device-side sampling feeding the next step, and the loop's last
        tokens and positions handed on to the next span on the device. One
        dispatch + one [K, B] readback per span. `n`, the span the loop
        chose (`decode_span`, or `busy_span` under prefill pressure), is an
        ARGUMENT: a length is no program. Until PR 53 two lengths x two
        samplers were four programs, each traced, lowered and loaded at a
        replica's start (6 to 8 s each in a stack of unlike layers); now the
        layers are traced once (`_forward`) and lowered once a sampler.
        The sampler stays a program of its own (`advanced`, the host's
        choice a batch): chosen on the device instead, by a conditional in
        the step or by a second loop beside the first, it cost the plain
        sampler's steps 0.10 to 0.21 ms in the Mixtral and granite cells
        (chip, PR 53: the compiler gave the loop it shares a program with
        less of the fast memory), and a token is paid for at every step, a
        start once.
        -> for_span(n, advanced) -> that sampler's program with `n` bound
        (`_SpanOf`)."""
        cfg, K = self.cfg, self.ecfg.span_rows

        def decode_span(params, k_pages, v_pages, tokens, positions,
                        page_tables, temps, top_ps, top_ks, key, state=None,
                        carry=None, *, n, advanced):
            """tokens/positions [B]; page_tables [B, pages_per_seq] (where
            the window layers' pages are allocated, `cfg.window_paged`: a
            pair, that and the slots' rings [B, ring]); `state`:
            what the layers keep per slot beside their pages (None: the
            empty tree); `carry`: (tokens, positions, fresh), the [B] pair
            the span before ended on and a [B] mask of the slots that take
            the host's `tokens` / `positions` instead (new since that span;
            None: all of them); `n`: the span's steps, an int32 scalar,
            1 <= n <= K. -> seq/logps [K, B] of which rows [:n] are the
            span's (the rest zero), the pool, the state, and the (tokens,
            positions) this span ended on. What
            the device counts comes back in the readback the tokens come
            back in, a row of logps more for each, behind row K: where the
            live tokens' choices of experts are counted
            (`cfg.counts_choices`), a row whose first two entries are the
            span's counts; then, where the steps visit the experts their
            live rows chose (`self._steps_visit`), a row whose first entry
            is how many they visited, over the span's steps and layers."""
            window_tables = None
            if cfg.window_paged:
                page_tables, window_tables = page_tables
            if carry is not None:
                carried_tokens, carried_positions, fresh = carry
                tokens = jnp.where(fresh, tokens, carried_tokens)
                positions = jnp.where(fresh, positions, carried_positions)

            def sample(logits, i):
                ki = jax.random.fold_in(key, i)
                if advanced:
                    return _device_sample_topk_topp(logits, temps, top_ps,
                                                    top_ks, ki)
                return _sample_plain(logits, temps, ki)

            step = self._decode_step(params, page_tables, window_tables,
                                     sample)

            def write(i, loop):
                *at, seq, logps = loop
                at, (toks, step_logps) = step(tuple(at), i)
                return (*at, seq.at[i].set(toks), logps.at[i].set(step_logps))

            state = state or {}
            if cfg.counts_choices:
                state = {**state, "choices": jnp.zeros((2,), jnp.float32)}
            if self._steps_visit:
                state = {**state, "touched": jnp.zeros((1,), jnp.float32)}
            B = tokens.shape[0]
            tokens, positions, k_pages, v_pages, state, seq, logps = \
                jax.lax.fori_loop(
                    0, n, write,
                    (tokens, positions, k_pages, v_pages, state,
                     jnp.zeros((K, B), jnp.int32),
                     jnp.zeros((K, B), jnp.float32)))
            for name in ("choices", "touched"):
                if name in state:
                    state = dict(state)
                    counts = state.pop(name)
                    row = jnp.zeros((1, logps.shape[1]), logps.dtype).at[
                        0, :counts.shape[0]].set(counts)
                    logps = jnp.concatenate([logps, row])
            return seq, logps, k_pages, v_pages, state, (tokens, positions)

        pinned: Dict[str, Any] = {}
        if self.mesh is not None:
            # the carry comes back as it goes in, replicated: left to the
            # partitioner's choice, another sharding would be another
            # program at the next call, compiled inside traffic
            pinned["out_shardings"] = (None,) * 5 + ((self._whole,) * 2,)
        # `advanced` compiles the top-k/top-p sampler (one vocab sort per
        # step) as a SEPARATE program: default-sampling batches never pay
        # for it
        jitted = {advanced: jax.jit(
            tracing.named(functools.partial(decode_span, advanced=advanced),
                          "decode_span" + ("_adv" if advanced else "")),
            donate_argnums=(1, 2, 10), **pinned) for advanced in (False, True)}
        calls = {advanced: self._under_mesh(program)
                 for advanced, program in jitted.items()}

        def for_span(n_steps: int, advanced: bool = False):
            if not 1 <= n_steps <= K:
                raise ValueError(
                    f"a span of {n_steps} steps: the decode programs hold "
                    f"spans of 1 to {K} (decode_span / busy_span)")
            return _SpanOf(calls[advanced], jitted[advanced], n_steps)

        return for_span

    def _build_chunk_prefill(self):
        """Jit a C-token prefill chunk of one sequence: the chunk's keys
        and values go straight into the sequence's pages and its queries
        attend over the paged prefix (per-row causal bound), through the
        layers of models/stack.py in its chunk mode. Whatever C, one call
        of the attention kernel takes `prefill_chunk` rows: the wide
        program (`_wide_chunk`) makes the calls two chunks would.
        Attention runs the Pallas chunk kernel
        (ops.paged_attention_chunk: blocks of page DMAs, reads only the
        valid prefix pages) where shapes allow;
        the XLA gather fallback, which touches the whole table, covers CPU
        tests, odd head dims, and TP meshes (GSPMD partitions the
        fallback's einsums; a bare pallas_call it cannot)."""
        cfg, ps = self.cfg, self.ecfg.page_size
        if cfg.vocab_size > 1 << 24:
            raise ValueError(
                f"{cfg.name!r}: a chunk program hands the host its first "
                "token in a float32 row, which holds ids under 2**24 exactly; "
                f"vocab_size is {cfg.vocab_size}")

        def chunk_step(params, k_pages, v_pages, tokens, start, page_table,
                       last_idx, state=None, how=None, key=None,
                       export=False):
            """tokens [C]; start/last_idx scalars; page_table [pps] (where the
            window layers' pages are allocated, `cfg.window_paged`: a pair,
            that and the sequence's ring [ring], and `state` is the engine's
            own, the window page space's pools, handed back like the pool);
            `state`:
            what the sequence's chunks so far left behind beside its pages
            (None: a sequence's start where pages are all there is); `how`
            [3] float32: the request's temperature, top_p and top_k, and
            `key` what a draw takes (`_sample_first`; the engine hands both
            to every chunk, and a caller that lowers the program for its
            sizes alone may leave them out: the argmax).
            Returns (token, first, k_pages, v_pages, state): the token drawn
            from the logits at last_idx, an int32 scalar that joins the next
            decode span without leaving the device (`_install_ready`), and
            `first`, the float32 row the host reads once that span is out
            (`_read_first`): the token again, its log-probability under the
            raw distribution and, where the device counts the tokens' choices
            of experts (`cfg.counts_choices`), the request's counts so far.
            With export=True (static) the chunk's own KV slabs
            [L, C, KVH, hd] in the pool dtype come before the state, so
            streamed export ships this chunk without a separate page-gather
            dispatch (which would queue behind whatever decode span is in
            flight). Rows past last_idx are padding; what a chunk that is
            not its prompt's last draws is never read."""
            window_table = None
            if cfg.window_paged:
                page_table, window_table = page_table
            x, new_k, new_v, state = stack.run_paged(
                params, tokens[None, :], cfg,
                stack.Seq(cfg, n_valid=(last_idx + 1)[None], keep=True,
                          chunk=(start, page_table), page_size=ps,
                          mesh=self.mesh, export=export,
                          window_table=window_table,
                          attend_rows=self.ecfg.prefill_chunk),
                (k_pages, v_pages), state)
            with jax.named_scope("lm_head"):
                logits = _head_logits(x, lambda x: x[0, last_idx], params,
                                      cfg, "d,dv->v")
            with jax.named_scope("sample"):
                if how is None:
                    how, key = _how_to_sample(0.0, 1.0, 0), jnp.zeros(
                        (2,), jnp.uint32)
                tok, logp = _sample_first(logits, how, key)
                # a token id is exact in float32 (checked above)
                first = jnp.stack([tok.astype(jnp.float32), logp])
            if cfg.counts_choices:
                first = jnp.concatenate([first, state["choices"]])
            if export:
                return (tok, first, new_k, new_v, state.pop("k")[:, 0],
                        state.pop("v")[:, 0], state)
            return tok, first, new_k, new_v, state

        cache: Dict[Any, Any] = {}

        def for_chunk(C: int, export: bool = False):
            key = (C, export)
            if key not in cache:
                if export:
                    self._refuse_kv_transfer("chunk export")
                cache[key] = self._under_mesh(jax.jit(
                    tracing.named(
                        functools.partial(chunk_step, export=export),
                        f"chunk_prefill_{C}" + ("_export" if export else "")),
                    # the window page space's pools come and go with THE
                    # pool; a sequence's own state is handed from chunk to
                    # chunk and its start is shared
                    donate_argnums=(1, 2, 7) if cfg.window_paged else (1, 2)))
            return cache[key]

        return for_chunk

    def _under_mesh(self, fn):
        """Trace/execute under THIS engine's mesh context, so in-jit
        sharding constraints resolve against it — never against whatever
        mesh some other component registered as the process default
        (parallel/sharding.py:_current_mesh falls back to the registry)."""
        if self.mesh is None:
            return fn

        @functools.wraps(fn)
        def call(*args, **kwargs):
            with self.mesh:
                return fn(*args, **kwargs)

        return call

    def _programs(self, buckets=None, batch_sizes=None):
        """Every program a replica compiles before it serves, in the order
        `warmup` compiles them, each with THE list of what it takes (`Arg`s
        and names of what the engine keeps: serve/program.py): the bucket
        prefill a (bucket, padded batch), the decode program of each
        sampler at its longest span (another span is another value of `n`),
        both chunk programs the queue picks from (`_advance_chunk`), the
        program that hands a slot's row of the carry a first token from
        where a chunk program left it, and the one that hands a slot its
        state. (A speculative round's verify follows them:
        `SpecDecoder._programs`.)"""
        ecfg = self.ecfg
        B, pps, K = ecfg.max_batch_size, ecfg.pages_per_seq, ecfg.span_rows
        i32, f32 = jnp.int32, jnp.float32
        program = functools.partial(Program.under, self.mesh)

        for bucket in (ecfg.prefill_buckets if buckets is None else buckets):
            for Bp in (ecfg.prefill_tiers() if batch_sizes is None
                       else batch_sizes):
                yield program(
                    f"prefill_bucket_{bucket}x{Bp}",
                    self._prefill_fn(bucket, Bp),
                    ("params", Arg((Bp, bucket), i32, 1), Arg((Bp,), i32, 1)),
                    rows=Bp * bucket)
        for advanced in (False, True):
            span = self._decode(K, advanced)
            yield program(
                "decode_span" + ("_adv" if advanced else ""), span,
                ("params", "k_pages", "v_pages",
                 Arg((B,), i32), Arg((B,), i32),  # tokens, positions
                 self._tables(Arg((B, pps), i32), Arg((B, self._ring), i32)),
                 # temperatures, top_p, top_k, the key
                 Arg((B,), f32), Arg((B,), f32, 1), Arg((B,), i32),
                 Arg((2,), jnp.uint32), "state",
                 ("_carry.0", "_carry.1", Arg((B,), jnp.bool_, True))),
                back=(None, None, "k_pages", "v_pages", "state", "_carry"),
                jitted=span, steps=K)
        if ecfg.chunked_prefill:
            for C in filter(None, (ecfg.prefill_chunk, self._wide)):
                yield program(
                    f"chunk_prefill_{C}", self._chunk_fn(C),
                    ("params", "k_pages", "v_pages",
                     Arg((C,), i32), Arg((), i32),  # tokens, start
                     self._tables(Arg((pps,), i32), Arg((self._ring,), i32)),
                     Arg((), i32, C - 1),  # last_idx
                     "state" if self._ring else "_request_start",
                     Arg((3,), f32, (0.0, 1.0, 0.0)),  # `_how_to_sample`
                     "_first_key"),
                    # (all-zero tables wrote the trash pages alone)
                    back=("token", None, "k_pages", "v_pages",
                          "state" if self._ring else None),
                    rows=C)
            yield program(
                "join_carry", self._join_carry,
                ("_carry", Arg((), i32, host=True), "token",
                 Arg((), i32, host=True)),  # the slot, the position
                back="_carry")
            if self.cfg.has_state and not self._ring:
                yield program(
                    "install_state", self._install_state,
                    ("state", "_request_start", Arg((), i32),
                     Arg((), i32, 1)),  # the slot, the sequence's length
                    back="state")

    def programs(self, params=None, sharding=None, buckets=None,
                 batch_sizes=None) -> Dict[str, Program]:
        """What this engine compiles and what each program takes, by the
        name of its `engine.warmup.program` region and in `warmup`'s
        order, from the two configs and the mesh alone: nothing is
        allocated, and an engine without arrays (`abstract`) answers as a
        running one does. `Program.args` are trees of
        `jax.ShapeDtypeStruct`; `Program.lower()` lowers the jitted
        program for them. `params`: the weights' shapes and dtypes, a tree
        of arrays or of `jax.ShapeDtypeStruct`s (None: this engine's own).
        `sharding`: where everything lies for an engine without a mesh, as
        a described chip's `SingleDeviceSharding`; under a mesh the
        weights, the pool and the rest lie as `__init__` places them."""
        pool_at = rest = sharding
        if self.mesh is not None:
            pool_at, rest = self._kv_sharding, self._whole
        params = self.params if params is None else params
        if self.mesh is not None:
            params = jax.tree.map(
                lambda a, at: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=at),
                params, self._param_shardings())
        pool = self.abstract_pool(pool_at)
        B = self.ecfg.max_batch_size
        kept = {
            "params": params, "k_pages": pool,
            "v_pages": None if self.cfg.latent_cache else pool,
            "state": self.abstract_state(rest),
            "_request_start": jax.eval_shape(self._new_request_start),
            "_carry": (Arg((B,), jnp.int32),) * 2,
            "_first_key": Arg((2,), jnp.uint32), "token": Arg((), jnp.int32),
        }

        def abstract(a):
            if isinstance(a, str):
                return jax.tree.map(abstract, _kept(kept.__getitem__, a),
                                    is_leaf=lambda a: isinstance(a, Arg))
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None) or rest)

        return {p.name: p.bind(abstract) for p in itertools.chain(
            self._programs(buckets, batch_sizes),
            self._spec._programs() if self._spec is not None else ())}

    def warmup(self, buckets=None, batch_sizes=None) -> None:
        """Compile the serving-path programs off the request path: prefill
        per (bucket, padded-batch) and the decode program of each sampler,
        which runs every span the adaptive policy can pick (both samplers:
        the first top-p/top-k request must not jit inside the decode loop
        under live traffic). Call before admitting traffic (the decode
        thread must be idle: warmup threads the donated KV pages through
        the compiled call exactly like step() does).

        Reference analogue: vLLM's startup CUDA-graph capture /
        determinism warmup. Default compiles every configured bucket —
        pass buckets=[...] to warm only the shapes a deployment serves.
        """
        self._warm(self._programs(buckets, batch_sizes))
        if self._spec is not None:
            self._spec.proposer.warmup(self)
            self._warm(self._spec._programs())

    def _warm(self, programs) -> None:
        """Run each of `programs` once with the values its list names:
        arrays of the `Arg`s' fills, and what the engine keeps as it
        stands, taken back from the program where it was donated
        (`Program.back`). Each program under a region of its own, from its
        first call to its blocked end: on a traced thread (`serve/llm.py
        start_engine`) the `xla.*` spans of what it compiles are that
        region's children."""
        held: Dict[str, Any] = {}  # handed back, and no attribute's

        def value(a):
            if isinstance(a, str):
                return _kept(lambda name: held[name] if name in held
                             else getattr(self, name), a)
            made = np.full(a.shape, a.fill, a.dtype)
            # a jit keeps a numpy scalar and an Array apart; an Array is
            # placed as the loop places its batch: no program runs for it
            return made[()] if a.host else jnp.asarray(made)

        def keep(back, out) -> None:
            if isinstance(back, str):
                if hasattr(self, back):
                    setattr(self, back, out)
                else:
                    held[back] = out
            elif back is not None:
                for b, o in zip(back, out):
                    keep(b, o)

        for p in programs:
            with tracing.region("engine.warmup.program", program=p.name,
                                **p.attrs):
                out = jax.block_until_ready(p.call(*p.bind(value).args))
                keep(p.back, out)

    def _tables(self, table, window_table):
        """A program's page tables: THE pool's, and beside it the window
        page space's where the model has one."""
        return (table, window_table) if self._ring else table

    def _run_decode(self, out) -> tuple:
        """Take back what a decode program was handed by donation, the
        pool and the per-slot state, and keep what its scan ended on for
        the next span. -> (seq, logps)."""
        (seq, logps, self.k_pages, self.v_pages, self.state,
         self._carry) = out
        return seq, logps

    def _prefill_fn(self, bucket: int, batch: int = 1):
        key = (bucket, batch)
        if key not in self._prefill_cache:
            cfg = self.cfg

            def run(params, tokens, true_len):
                # the module stays `jit_run` (the benchmark's trace_names
                # keys on it); the scope says which shape class it is
                with jax.named_scope(f"prefill_bucket_{bucket}x{batch}"):
                    x, cache = stack.prefill(params, cfg, tokens, true_len)
                    at = (true_len - 1)[:, None, None].astype(jnp.int32)
                    with jax.named_scope("lm_head"):
                        logits = _head_logits(
                            x, lambda x: jnp.take_along_axis(x, at, 1)[:, 0],
                            params, cfg, "bd,dv->bv")
                    if cfg.counts_choices:
                        # the batch's counts behind row 0's logits
                        tail = jnp.zeros((logits.shape[0], 2),
                                         logits.dtype).at[0].set(
                                             cache.pop("choices"))
                        logits = jnp.concatenate([logits, tail], axis=1)
                    return logits, cache

            self._prefill_cache[key] = self._under_mesh(jax.jit(run))
        return self._prefill_cache[key]

    def _scatter_prefill(self, cache, pages: List[int], true_len: int):
        """Write a prefill cache [L,1,Tpad,KVH,hd] into the page pool."""
        ps = self.ecfg.page_size
        n = len(pages)
        k = cache["k"][:, 0]  # [L, Tpad, KVH, hd]
        v = cache["v"][:, 0] if "v" in cache else None  # latents: no values
        Tpad = k.shape[1]
        n_full = min(n, Tpad // ps)
        page_arr = jnp.asarray(pages[:n_full], jnp.int32)
        self.k_pages, self.v_pages = _scatter_pages_jit(
            self.k_pages, self.v_pages, k, v, page_arr
        )
        if self._ring:
            # the bucket's window keys, into the sequence's ring: a bucket
            # is no longer than the window, so its pages are the ring's
            # first, in order
            n_ring = min(len(pages.window), Tpad // ps)
            self.state["wk"], self.state["wv"] = _scatter_pages_jit(
                self.state["wk"], self.state["wv"], cache["wk"][:, 0],
                cache["wv"][:, 0], jnp.asarray(pages.window[:n_ring],
                                               jnp.int32))

    def _export_blob(self, req: Request, pages: List[int], cache,
                     T: int) -> Dict[str, Any]:
        """Gather a prefill_only request's KV into a token-contiguous host
        blob [L, T, KVH, hd] in the page-pool dtype (decode thread only —
        the chunked path reads the donated page pools). Both export paths
        apply the same elementwise dtype cast the colocated scatter path
        does, so import → decode continues token-exactly."""
        dtype = self.k_pages.dtype
        if "k" in cache:
            # bucketed prefill: the row cache IS the KV; no scatter needed
            k = np.asarray(cache["k"][:, 0, :T].astype(dtype))
            v = np.asarray(cache["v"][:, 0, :T].astype(dtype))
        else:
            # chunked prefill wrote pages directly: gather and trim
            page_arr = jnp.asarray(pages, jnp.int32)
            k, v = _gather_pages_jit(self.k_pages, self.v_pages, page_arr,
                                     self.cfg.kv_heads)
            k = np.asarray(k[:, :T])
            v = np.asarray(v[:, :T])
        return {
            "k": k,
            "v": v,
            "true_len": T,
            "first_token": int(req.output[-1]),
            "first_logprob": (req.output_logprobs[-1]
                              if req.output_logprobs else None),
            "layers": int(k.shape[0]),
            "kv_heads": int(k.shape[2]),
            "head_dim": int(k.shape[3]),
            "dtype": str(dtype),
        }

    def export_kv_pages(self, req: Request,
                        timeout_s: float = 600.0) -> Dict[str, Any]:
        """Block until a prefill_only request finishes and return its KV
        blob (see _export_blob). The blob is engine-agnostic: it can be
        imported into a pool with a different page_size/max_pages."""
        self._refuse_kv_transfer("export_kv_pages")
        if not req.done.wait(timeout_s):
            self.cancel(req.request_id)
            raise TimeoutError(f"request {req.request_id} timed out")
        if req.error:
            raise ValueError(req.error)
        blob, req._kv_export = req._kv_export, None
        if blob is None:
            raise ValueError(
                f"request {req.request_id} has no KV export (prefill_only="
                f"{req.prefill_only}, finish_reason={req.finish_reason!r})")
        return blob

    def _kv_layout(self, req: Request) -> str:
        """Resolve a request's streamed-frame layout: request override,
        else the config.kv_frame_layout knob; anything unknown falls back
        to "layer" (the default wire v2)."""
        lay = req.kv_frame_layout or str(config.kv_frame_layout)
        return lay if lay in ("layer", "token") else "layer"

    def _stream_kv_frames(self, req: Request, k, v, start: int, *,
                          true_len: int, last: bool, seq0: int = 0,
                          layer0: int = 0, n_layers: Optional[int] = None
                          ) -> int:
        """Push host KV `k`/`v` ([Ln, t, KVH, hd], covering prompt tokens
        [start, start+t)) to req.kv_sink in kv_window-token frames.
        Returns the next frame seq. Frame wire format:

          {"request_id", "seq", "start", "k", "v", "last"}

        plus the blob metadata (true_len/layers/kv_heads/head_dim/dtype)
        on seq 0 — everything begin_kv_import needs — and, on the final
        frame, "first_token" for finish_kv_import.

        Wire v1 (token-major): every frame carries the FULL layer stack
        for its token range (layer0=0, Ln == n_layers). Wire v2
        (layer-major): `k`/`v` are a slab of Ln consecutive layers
        starting at `layer0`; frames gain a "layer0" key and seq 0
        stamps "kv_wire": 2 (frame "layers" metadata stays the model
        TOTAL). `last` must only be set on the final slab's final
        window of the whole stream. A raising sink propagates to the
        caller, which fails the request."""
        t0 = time.monotonic()
        win = max(int(req.kv_window), self.ecfg.page_size)
        L_total = int(n_layers) if n_layers is not None else int(k.shape[0])
        layered = layer0 > 0 or int(k.shape[0]) != L_total
        t = k.shape[1]
        seq, off = seq0, 0
        while True:
            end = min(off + win, t)
            frame = {
                "request_id": req.request_id,
                "seq": seq,
                "start": start + off,
                "k": k[:, off:end],
                "v": v[:, off:end],
                "last": False,
            }
            if layered:
                frame["layer0"] = int(layer0)
            if seq == 0:
                frame.update(
                    true_len=int(true_len),
                    layers=L_total,
                    kv_heads=int(k.shape[2]),
                    head_dim=int(k.shape[3]),
                    dtype=str(k.dtype),
                )
                if layered:
                    frame["kv_wire"] = 2
            tail = end >= t
            if tail and last:
                frame["last"] = True
                frame["true_len"] = int(true_len)
                frame["first_token"] = int(req.output[-1])
                frame["first_logprob"] = (req.output_logprobs[-1]
                                          if req.output_logprobs else None)
            req.kv_sink(frame)
            seq += 1
            off = end
            if tail:
                _m_step_phase.observe(
                    time.monotonic() - t0,
                    tags={"phase": "kv_framing", "mode": "export"})
                return seq

    def _stream_chunk_frames(self, st: _ChunkState, upto: int,
                             last: bool, chunk_kv=None) -> None:
        """Chunked-prefill streamed export (decode thread only): ship the
        KV committed since the last frame to the sink. `chunk_kv` is the
        latest chunk's own (k, v, start) slabs straight off the chunk
        dispatch — when the pending window lies inside it (every call
        except a prefix-hit's first, whose cached pages predate the
        chunk) the export is a pure host slice, no gather program. The
        fallback gathers pages — including the cached prefix — with one
        page-granular dispatch. Non-final frames stop at a page boundary,
        so migration overlaps the remaining chunks instead of waiting for
        the first token. With layer-major framing the window is sliced
        into per-layer-group frames, so the decode side can start staging
        while later groups of the SAME token window are still in
        flight."""
        ps = self.ecfg.page_size
        if not last:
            upto = (upto // ps) * ps
        if upto <= st.emitted_upto:
            return
        if chunk_kv is not None and st.emitted_upto >= chunk_kv[2]:
            cs = chunk_kv[2]
            k = np.asarray(chunk_kv[0])[:, st.emitted_upto - cs:upto - cs]
            v = np.asarray(chunk_kv[1])[:, st.emitted_upto - cs:upto - cs]
        else:
            p0 = st.emitted_upto // ps  # emitted_upto is page-aligned here
            p1 = -(-upto // ps)
            page_arr = jnp.asarray(st.pages[p0:p1], jnp.int32)
            k, v = _gather_pages_jit(self.k_pages, self.v_pages, page_arr,
                                     self.cfg.kv_heads)
            k = np.asarray(k[:, : upto - p0 * ps])
            v = np.asarray(v[:, : upto - p0 * ps])
        if self._kv_layout(st.request) == "layer":
            groups = _kv_layer_groups(int(k.shape[0]))
            seq = st.sink_seq
            for gi, (l0, l1) in enumerate(groups):
                seq = self._stream_kv_frames(
                    st.request, k[l0:l1], v[l0:l1], st.emitted_upto,
                    true_len=st.true_len,
                    last=last and gi == len(groups) - 1,
                    seq0=seq, layer0=l0, n_layers=int(k.shape[0]))
            st.sink_seq = seq
        else:
            st.sink_seq = self._stream_kv_frames(
                st.request, k, v, st.emitted_upto, true_len=st.true_len,
                last=last, seq0=st.sink_seq)
        st.emitted_upto = upto

    def begin_kv_import(self, req: Request, true_len: int,
                        meta: Dict[str, Any],
                        timeout_s: float = 60.0) -> bool:
        """Start a partial (streamed) KV import: validate against this
        model, allocate pages for prompt+max_tokens, and stage a host
        buffer that ingest_kv_chunk fills as frames arrive. Returns False
        if the request was failed instead (req.error/done set — matching
        import_kv_pages' failure contract). `meta` carries the frame-0
        header fields (layers/kv_heads/head_dim/dtype)."""
        try:
            self._refuse_kv_transfer("begin_kv_import")
            req.stop = _normalize_stops(req.stop)
        except ValueError as e:
            self._finish_request(req, error=str(e))
            return False
        try:
            T = int(true_len)
            Lb = int(meta["layers"])
            KVHb = int(meta["kv_heads"])
            hdb = int(meta["head_dim"])
        except (KeyError, TypeError, ValueError) as e:
            self._finish_request(req, error=f"malformed kv blob: {e!r}")
            return False
        # wire-format guard: v1 token-major frames carry no marker, v2
        # adds layer-major slabs ("layer0" per frame). Anything newer
        # than this engine speaks must be refused up front rather than
        # silently mis-staged.
        wire = int(meta.get("kv_wire", 1))
        if wire > 2:
            self._finish_request(req, error=(
                f"unsupported kv wire format v{wire} (this engine speaks "
                "<= v2)"))
            return False
        L, KVH, hd = self.cfg.n_layers, self.cfg.kv_heads, self.cfg.hdim
        if (Lb, KVHb, hdb) != (L, KVH, hd):
            self._finish_request(req, error=(
                f"kv blob shape {(Lb, T, KVHb, hdb)} does not match model "
                f"[layers={L}, true_len={T}, kv_heads={KVH}, head_dim={hd}]"))
            return False
        if len(req.prompt) != T:
            self._finish_request(req, error=(
                f"kv blob covers {T} tokens but the prompt has "
                f"{len(req.prompt)}"))
            return False
        total = T + req.max_tokens
        if total > self.ecfg.max_seq_len:
            self._finish_request(req, error=(
                f"prompt+max_tokens {T}+{req.max_tokens} exceeds "
                f"max_seq_len {self.ecfg.max_seq_len}"))
            return False
        n_pages = -(-total // self.ecfg.page_size)
        if n_pages > self.ecfg.max_pages - 1:
            self._finish_request(req, error=(
                f"request needs {n_pages} pages but the pool only has "
                f"{self.ecfg.max_pages - 1}; raise EngineConfig.max_pages"))
            return False
        if self.prefix is not None:
            req._page_hashes = self.prefix.page_hashes(
                req.prompt, T // self.ecfg.page_size)
        with self._req_lock:
            self._requests[req.request_id] = req
        req.enter_stage("kv_import", tracing.now_ns())
        deadline = time.monotonic() + timeout_s
        pages = None
        while True:
            with self._alloc_lock:
                if req.cancelled.is_set():
                    break
                pages = self._alloc_with_reclaim(n_pages)
            if pages is not None:
                break
            if time.monotonic() >= deadline:
                self._finish_request(req, error=(
                    f"no pages free for KV import within {timeout_s}s"))
                return False
            time.sleep(0.005)
        if req.cancelled.is_set():
            if pages:
                self._free_pages_and_revive(pages)
            self._finish_request(req, "cancelled")
            return False
        ps = self.ecfg.page_size
        Tpad = -(-T // ps) * ps
        # host staging in the SOURCE dtype: finish casts to the pool
        # dtype exactly as the one-shot path does, so decode continues
        # token-identically
        dt = np.dtype(meta.get("dtype", str(self.k_pages.dtype)))
        req._kv_ingest = {
            "pages": pages,
            "T": T,
            "k": np.zeros((L, Tpad, KVH, hd), dt),
            "v": np.zeros((L, Tpad, KVH, hd), dt),
        }
        # streamed-import pressure: while any import is staged, the
        # exporting peer's page gathers are contending for this device's
        # queue and the arriving request is waiting on a decode slot —
        # shrink decode spans exactly as local prefill pressure does
        self._importing += 1
        return True

    def ingest_kv_chunk(self, req: Request, frame: Dict[str, Any]) -> None:
        """Copy one streamed frame into the staging buffer (any order;
        duplicate writes are idempotent). Token-major (wire v1) frames
        cover the full layer stack; layer-major (wire v2) frames carry a
        slab of consecutive layers at frame["layer0"] — a missing key is
        the v1 degenerate case layer0=0, so old senders keep importing.
        Raises on malformed frames — the caller aborts the import."""
        st = req._kv_ingest
        s = int(frame["start"])
        k, v = frame["k"], frame["v"]
        t = int(k.shape[1])
        l0 = int(frame.get("layer0", 0))
        ln = int(k.shape[0])
        if s < 0 or s + t > st["k"].shape[1]:
            raise ValueError(
                f"kv frame [{s}:{s + t}) outside the staged "
                f"{st['k'].shape[1]} tokens")
        if l0 < 0 or l0 + ln > st["k"].shape[0]:
            raise ValueError(
                f"kv frame layers [{l0}:{l0 + ln}) outside the staged "
                f"{st['k'].shape[0]} layers")
        st["k"][l0:l0 + ln, s:s + t] = k
        st["v"][l0:l0 + ln, s:s + t] = v

    def finish_kv_import(self, req: Request, first_token: int,
                         first_logprob: Optional[float] = None) -> Request:
        """Finalize a streamed import: move the staged KV to device and
        publish the request to the decode batch, seeding the first token
        exactly as the prefill emitters do (it was sampled and
        TTFT-observed on the prefill engine; its logprob rides the
        export metadata — None for pre-logprob exports)."""
        st, req._kv_ingest = req._kv_ingest, None
        self._importing = max(0, self._importing - 1)
        if req.cancelled.is_set():
            self._free_pages_and_revive(st["pages"])
            self._finish_request(req, "cancelled")
            return req
        dtype = self.k_pages.dtype
        # reshape on the host BEFORE the device put: [:, None] on a jax
        # array is an XLA program that queues behind in-flight decode
        # spans, while a numpy view is free and device_put skips the
        # compute queue entirely
        cache = {
            "k": jnp.asarray(st["k"][:, None], dtype),  # [L,1,Tpad,KVH,hd]
            "v": jnp.asarray(st["v"][:, None], dtype),
        }
        if not req.output:
            self._give_first_token(
                req, int(first_token),
                float(first_logprob) if first_logprob is not None else None,
                self.weights_version)
        req.enter_stage("ready", tracing.now_ns())
        with self._ready_lock:
            self._ready.append((req, st["pages"], cache, st["T"]))
        self._work.set()
        self._ensure_loop()
        return req

    def abort_kv_import(self, req: Request,
                        error: Optional[str] = None) -> None:
        """Tear down a partial import (stream died / cancelled): free the
        staged pages and finish the request."""
        st = getattr(req, "_kv_ingest", None)
        req._kv_ingest = None
        if st is not None:
            self._importing = max(0, self._importing - 1)
        if st is not None and st.get("pages"):
            self._free_pages_and_revive(st["pages"])
        if not req.done.is_set():
            if error is not None:
                self._finish_request(req, error=error)
            else:
                self._finish_request(req, "cancelled")

    def import_kv_pages(self, req: Request, blob: Dict[str, Any],
                        timeout_s: float = 60.0) -> Request:
        """Admit `req` straight into the decode phase from an exported KV
        blob (disaggregated serving: prefill ran on another engine). The
        blob is re-paginated for THIS engine's page_size/max_pages; the
        request then behaves exactly as if prefilled here (stops, stream
        hold-back, prefix registration, speculation all apply). One-shot
        wrapper over begin/ingest/finish_kv_import — the streamed path
        uses those directly and lands token-identically.

        Failures surface on the request (req.error + done set), matching
        add_request's contract. Pages are allocated inline with a bounded
        retry instead of parking in _waiting: revival re-queues to the
        PREFILL thread, which would prefill the prompt a second time and
        append a duplicate first token."""
        try:
            self._refuse_kv_transfer("import_kv_pages")
            req.stop = _normalize_stops(req.stop)
        except ValueError as e:
            self._finish_request(req, error=str(e))
            return req
        try:
            k, v = blob["k"], blob["v"]
            T = int(blob["true_len"])
            first = int(blob["first_token"])
        except (KeyError, TypeError) as e:
            self._finish_request(req, error=f"malformed kv blob: {e!r}")
            return req
        L, KVH, hd = self.cfg.n_layers, self.cfg.kv_heads, self.cfg.hdim
        if tuple(k.shape) != (L, T, KVH, hd) or tuple(v.shape) != k.shape:
            self._finish_request(req, error=(
                f"kv blob shape {tuple(k.shape)} does not match model "
                f"[layers={L}, true_len={T}, kv_heads={KVH}, head_dim={hd}]"))
            return req
        meta = {"layers": L, "kv_heads": KVH, "head_dim": hd,
                "dtype": str(np.asarray(k).dtype)}
        if not self.begin_kv_import(req, T, meta, timeout_s=timeout_s):
            return req
        try:
            self.ingest_kv_chunk(req, {"start": 0, "k": k, "v": v})
        except Exception as e:  # noqa: BLE001 — fail just this request
            self.abort_kv_import(req, f"kv ingest failed: {e!r}")
            return req
        return self.finish_kv_import(req, first,
                                     first_logprob=blob.get("first_logprob"))

    # ------------------------------------------------------------- requests

    def add_request(self, req: Request) -> None:
        try:
            req.stop = _normalize_stops(req.stop)
            if req.prefill_only:
                self._refuse_kv_transfer("prefill_only request")
        except ValueError as e:
            self._finish_request(req, error=str(e))
            return
        # prefill_only requests never decode here: they only ever hold
        # pages for the prompt, so capacity checks exclude max_tokens
        total = len(req.prompt) + (0 if req.prefill_only else req.max_tokens)
        if total > self.ecfg.max_seq_len:
            req.error = (
                f"prompt+max_tokens {len(req.prompt)}+{req.max_tokens} exceeds "
                f"max_seq_len {self.ecfg.max_seq_len}"
            )
            req.done.set()
            req._emit(None)
            return
        # Reject at admission anything the pool can never satisfy (page 0 is
        # the reserved trash page) — otherwise _admit_one re-queues it forever.
        n_pages = -(-total // self.ecfg.page_size)
        if n_pages > self.ecfg.max_pages - 1:
            req.error = (
                f"request needs {n_pages} pages but the pool only has "
                f"{self.ecfg.max_pages - 1}; raise EngineConfig.max_pages"
            )
            req.done.set()
            req._emit(None)
            return
        if req.received_ns is not None:
            _front_inbound.observe(
                (tracing.now_ns() - req.received_ns) * 1e-9)
        # traced when the caller's thread carries a span or the sampler
        # fires: the root of the request's stage spans, finished with it
        req.span = tracing.maybe_begin("engine.request",
                                       {"request_id": req.request_id})
        with self._req_lock:
            self._requests[req.request_id] = req
        self.pending.put(req)
        self._ensure_loop()

    def cancel(self, request_id: str) -> bool:
        """Cancel a live request (reference: serve's disconnect-driven
        cancellation). Wherever it currently is — pending, parked for
        pages, mid-chunked-prefill, awaiting install, or decoding — it
        finishes with finish_reason="cancelled" at its next scheduling
        point and its pages free. Returns False for unknown/finished ids.
        The device is never interrupted mid-program: an in-flight prefill
        completes and the result is dropped at install."""
        with self._req_lock:
            req = self._requests.get(request_id)
        if req is None or req.done.is_set():
            return False
        req.cancelled.set()
        # Chunked-prefill and active-slot retirement belong to the DECODE
        # thread alone (it checks the flag at every chunk/step boundary):
        # removing a _ChunkState here would race the in-flight chunk and
        # double-free its pages. Only the stations no thread is actively
        # driving get swept here.
        with self._ready_lock:
            for item in list(self._ready):
                if item[0] is req:
                    self._ready.remove(item)
                    self._free_pages_and_revive(item[1])
                    self._finish_request(req, "cancelled")
        with self._alloc_lock:
            parked = req in self._waiting
            if parked:
                self._waiting.remove(req)
        if parked:
            self._finish_request(req, "cancelled")
        self._work.set()  # decode thread sweeps chunks/slots promptly
        return True

    def _finish_request(self, req: Request, reason: Optional[str] = None,
                        error: Optional[str] = None) -> None:
        """The one request-completion choreography (finish/fail/cancel all
        route here): stamp, count, unregister, signal, terminate stream."""
        if req.done.is_set():
            return
        if error is not None:
            req.error = error
        else:
            req.finish_reason = reason
            _m_requests.inc(tags={"finish_reason": reason})
        now = tracing.now_ns()
        req.enter_stage(None, now)
        req.finished_at = now * 1e-9
        if req.span is not None:
            req.span.attrs["finish_reason"] = reason or "error"
            req.span.finish(now)
        if self._slo_on and error is None and reason != "cancelled":
            self._slo_digest("serve_e2e_seconds").add(
                req.finished_at - req.submitted_at)
        self._forget(req)
        self._state_in(req)
        for tok in req._held:  # flush the stream hold-back (post-strip)
            req._emit(tok)
        req._held.clear()
        req.done.set()
        req._emit(None)

    def _forget(self, req: Request) -> None:
        with self._req_lock:
            self._requests.pop(req.request_id, None)

    def _ensure_loop(self):
        with self._lock:
            if self._loop_thread is None or not self._loop_thread.is_alive():
                self._stop.clear()
                self._loop_done = False
                self._loop_thread = threading.Thread(
                    target=self._loop, daemon=True, name="engine-decode"
                )
                self._loop_thread.start()
            if self._prefill_thread is None or not self._prefill_thread.is_alive():
                self._prefill_thread = threading.Thread(
                    target=self._prefill_loop, daemon=True, name="engine-prefill"
                )
                self._prefill_thread.start()

    def _active(self) -> List[_Slot]:
        return [s for s in self.slots if s.request is not None]

    def _has_work(self) -> bool:
        if self._inflight is not None or self._swaps:
            return True  # a span to read back, weights to rebind
        with self._ready_lock:
            if self._ready:
                return True
        with self._chunk_lock:
            if self._chunk_queue:
                return True
        return any(s.request is not None for s in self.slots)

    def _loop(self):
        """Decode thread. Runs until stop(); when idle it blocks on the
        _work event (clear → recheck → wait, so a prefill publishing to
        _ready between the recheck and the wait still wakes it). It leaves
        nothing behind: the span in flight is read back and committed, and
        posted weights are bound (`update_params` takes the same lock to
        learn whether this thread will still do it)."""
        while not self._stop.is_set():
            if self._has_work():  # then step() progresses
                self._iterate()
                continue
            self._work.clear()
            if self._has_work() or self._stop.is_set():
                continue
            with self.phase("idle"):
                self._work.wait(timeout=0.5)
        with self._lock:
            self._swap_params()
            self._loop_done = True

    def _iterate(self) -> None:
        """One `engine.iter`, and its row of the token ledger. The row
        opens empty: a phase that ran since the last iteration's end and
        outside any (the drain under `update_params` on a caller's thread,
        or at the loop's end) is inside `between`, this iteration's start
        less the last one's end, and its own time must not be taken from
        this iteration's a second time: `rest` below turned negative by
        that drain's readback, and a counter refused it."""
        for row in (self._phase_ns, self._hidden_ns):
            row.update(dict.fromkeys(row, 0))
        self._live = self._nested_read_ns = 0
        self._began_busy = self._device_busy()
        with self.phase("iter") as it:
            self.step()
        self._account(it)

    def _account(self, it: _Phase) -> None:
        """Close the iteration's row: each phase's time goes to the
        sequences that waited through it. Through `chunk` and `install`
        those are the slots live at the iteration's start, after `install`
        the slots that hold a sequence (`_live`): a sequence joins
        part-way through `install` and leaves part-way through `commit` or
        `cancel_check`, which is what the sum can differ by from the
        `decode` stage's (a fraction of two phases a request). What the
        phases leave of the iteration is mostly its tail (a
        finished request wakes its reader), so it goes to the slots live
        at the end, like the time to the next iteration's start.

        WHICH part a phase's time is depends on what the sequences waited
        for (`_phase_done`): with a dispatched program of this thread, a
        span or a chunk, unfinished at the phase's end the device set their
        pace, not the host, and the time is `device_wait`
        (`chunk_device_wait` for `engine.chunk`), like the readbacks'. So
        `host`, `dispatch`, `chunk_host` and `loop` are what the host still
        costs a token once the loop runs ahead.

        `engine.chunk.readback`, the read of a prompt's first token, is
        `chunk_device_wait` wherever it runs, weighed like `engine.chunk`
        by the slots live at the iteration's start: behind the iteration's
        dispatch, or inside `engine.chunk` (`_nested_read_ns`: the request
        needs its token where the chunk went out), whose time holds it
        then."""
        ns, hid, live = self._phase_ns, self._hidden_ns, self._live
        live_at_start = self._live_at_end  # only this thread frees a slot
        live_at_end = self._live_at_end = sum(
            1 for s in self.slots if s.request is not None)
        if live_at_start or live:
            chunk_wait, readback = (ns["engine.chunk.readback"],
                                    ns["engine.readback"])
            nested = self._nested_read_ns
            chunk, chunk_hid = ns["engine.chunk"], hid["engine.chunk"]
            if chunk_hid:  # a nested read is inside whichever it was
                chunk_hid -= nested
            else:
                chunk -= nested
            others = ("engine.commit", "engine.cancel_check", "engine.build",
                      "engine.propose", "engine.propose_wait")
            other, other_hid = (sum(d[n] for n in others) for d in (ns, hid))
            phases = sum(ns.values()) + sum(hid.values()) - nested
            rest = live_at_end * (it.elapsed_ns - phases)
            between = live_at_start * (it.start_ns - self._iter_end_ns)
            waited = (live * (readback + other_hid + hid["engine.dispatch"])
                      + live_at_start * hid["engine.install"])
            loop = 0
            for slot_ns, busy in ((between, self._began_busy),
                                  (rest, self._device_busy())):
                if busy:
                    waited += slot_ns
                else:
                    loop += slot_ns
            for part, slot_ns in (
                    ("chunk_host", live_at_start * chunk),
                    ("chunk_device_wait",
                     live_at_start * (chunk_wait + chunk_hid)),
                    ("host", live_at_start * ns["engine.install"]
                     + live * other),
                    ("dispatch", live * ns["engine.dispatch"]),
                    ("device_wait", waited),
                    ("loop", loop)):
                _token_wait[part].inc(slot_ns * 1e-9)
        self._iter_end_ns = it.end_ns

    def _open_span(self, steps: int,
                   members: Optional[Dict[int, Request]] = None) -> _Span:
        """A decode dispatch is about to go out with `members` (slot ->
        request; None: every live slot): the span, which takes with it
        the prefill tokens dispatched since the last one."""
        if members is None:
            members = {i: s.request for i, s in enumerate(self.slots)
                       if s.request is not None}
        bucket_tokens = self._bucket_tokens
        prefill_tokens = (bucket_tokens - self._bucket_tokens_seen
                          + self._chunk_tokens)
        self._bucket_tokens_seen, self._chunk_tokens = bucket_tokens, 0
        return _Span(steps, members, prefill_tokens)

    def _span_read(self, span: _Span, ph: _Phase) -> None:
        """`engine.readback` of `span` has returned: file its wall time on
        the device's queue, which began when it was dispatched or, if the
        device was still on the span before, when that one's readback
        returned; under the slots and the prefill tokens it went out with."""
        read_ns = ph.end_ns
        live = len(span.members)
        seconds, n_steps = _span_children[
            min((live - 1).bit_length(), 7)][span.prefill_tokens > 0]
        seconds.inc((read_ns - max(span.dispatched_ns, self._read_ns)) * 1e-9)
        n_steps.inc(span.steps)
        _m_interleaved.inc(live * span.prefill_tokens)
        self._read_ns = read_ns

    # ------------------------------------------------------------- prefill
    # Runs on its own thread so a long prompt never stalls the decode
    # cadence: the decode thread only pays the page scatter at a step
    # boundary. (vLLM-style prefill/decode isolation; VERDICT r1 item 5.)

    def _prefill_loop(self):
        """Prefill thread. Runs until stop(); blocks on the pending queue,
        so it can never exit with a request enqueued (no park race).
        Queued prompts coalesce into padded batches (continuous batching on
        the PREFILL side too): under load, one [K, bucket] program replaces
        K serial [1, bucket] calls — the MXU sees one big matmul and queue
        TTFT drops accordingly."""
        while not self._stop.is_set():
            with _prefill_phase("idle"):
                try:
                    req = self.pending.get(timeout=0.2)
                except queue.Empty:
                    continue
            batch = [req]
            # drain the WHOLE burst (up to the largest compiled tier):
            # one padded dispatch beats serial rounds for every waiter
            drain_cap = self.ecfg.prefill_tiers()[-1]
            while len(batch) < drain_cap:
                try:
                    batch.append(self.pending.get_nowait())
                except queue.Empty:
                    break
            # _prefill_batch handles every request's outcome itself
            # (deferred / errored / published / failed-with-pages-freed);
            # a blanket catch here would double-fail batch-mates that were
            # already parked in _waiting or published to _ready
            self._prefill_inflight += 1
            try:
                self._prefill_batch(batch)
            finally:
                self._prefill_inflight -= 1

    def _fail_request(self, req: Request, msg: str) -> None:
        self._finish_request(req, error=msg)

    def _free_pages_and_revive(self, pages: List[int]) -> None:
        """Free pages AND re-queue page-starved parked requests: every
        free site must revive _waiting, or a parked request can only be
        rescued by some unrelated request finishing later. Cached pages
        in `pages` only drop a ref (the prefix cache owns them)."""
        with self._alloc_lock:
            if self.prefix is not None:
                pages = self.prefix.release_and_filter(pages)
            self.allocator.free(pages)
            if self._ring:
                self._window_allocator.free(pages.window)
                self.allocator.unpromise(pages.promised)
                self._window_allocator.unpromise(pages.window_promised)
            waiting, self._waiting = self._waiting, []
        self._requeue(waiting)

    def _requeue(self, waiting: "list[Request]") -> None:
        now = tracing.now_ns() if waiting else 0
        for w in waiting:
            w.enter_stage("pending", now)
            self.pending.put(w)

    def _grow(self, pages: "_SeqPages", tokens: int) -> None:
        """A sequence of two page spaces is about to hold `tokens` tokens:
        it takes the pages they need and it does not hold yet, in both
        spaces, out of what admission promised it (`ceil(tokens /
        page_size)` pages of THE pool, and of the window space the ring's
        width at the most; never more than the promise, so rows past a
        sequence's end write the trash page as everywhere)."""
        n = -(-tokens // self.ecfg.page_size)
        more = min(n - len(pages), pages.promised)
        window_more = min(min(n, self._ring) - len(pages.window),
                          pages.window_promised)
        if more <= 0 and window_more <= 0:
            return
        with self._alloc_lock:
            if more > 0:
                pages.extend(self.allocator.take(more))
                pages.promised -= more
            if window_more > 0:
                pages.window.extend(self._window_allocator.take(window_more))
                pages.window_promised -= window_more

    def _alloc_with_reclaim(self, n: int) -> Optional[List[int]]:
        """allocator.alloc, reclaiming zero-ref cached pages on miss —
        caching must never reduce serveable capacity. Caller holds
        _alloc_lock."""
        pages = self.allocator.alloc(n)
        if pages is None and self.prefix is not None:
            short = n - self.allocator.num_free
            reclaimed = self.prefix.evict(short)
            if reclaimed:
                self.allocator.free(reclaimed)
                pages = self.allocator.alloc(n)
        return pages

    def _admit_for_prefill(self, req: Request):
        """-> (pages, T, bucket, cached_len); bucket None = chunked path,
        cached_len = tokens served by the prefix cache (whole pages).
        Or None (deferred to _waiting / errored)."""
        T = len(req.prompt)
        total = T + (0 if req.prefill_only else req.max_tokens)
        n_pages = -(-total // self.ecfg.page_size)
        C = self.ecfg.prefill_chunk
        hashes: List[bytes] = []
        if self.prefix is not None:
            # hash OUTSIDE the lock (sha1 over the whole prompt); stashed
            # on the request so install-time register() reuses the chain
            hashes = self.prefix.page_hashes(
                req.prompt, T // self.ecfg.page_size)
            req._page_hashes = hashes
        with self._alloc_lock:
            shared: List[int] = []
            if self.prefix is not None:
                shared = self.prefix.lookup_acquire(req.prompt, C,
                                                    hashes=hashes)
            short = _deferred_no_pages
            if (self._state_room is not None
                    and self._states_out >= self._state_room):
                pages, short = None, _deferred_no_state_room
            elif not self._ring:
                pages = self._alloc_with_reclaim(n_pages - len(shared))
            # two page spaces: a promise in both or in neither, and the
            # pages themselves as the sequence grows (`_grow`). The ring is
            # as many pages as its tokens need, its width at the most
            elif not self.allocator.promise(n_pages):
                pages = None
            elif not self._window_allocator.promise(min(n_pages, self._ring)):
                self.allocator.unpromise(n_pages)
                pages, short = None, _deferred_no_window_pages
            else:
                pages = _SeqPages(n_pages, min(n_pages, self._ring))
            if pages is None:
                if shared:  # drop the refs we just took
                    self.prefix.release_and_filter(shared)
                # Cancelled while we were admitting? Park nothing: no
                # station re-checks _waiting, and cancel()'s sweep may
                # already have run (it takes this same lock, so either
                # its sweep sees our append or we see its flag here).
                if req.cancelled.is_set():
                    cancelled = True
                else:
                    # no capacity; revived by _maybe_finish on page frees
                    req.enter_stage("waiting_for_pages", tracing.now_ns())
                    short.inc()
                    self._waiting.append(req)
                    return None
            else:
                cancelled = False
                if shared:
                    pages = shared + pages
                req._state_out = True
                self._states_out += 1
        if cancelled:
            self._finish_request(req, "cancelled")
            return None
        cached_len = len(shared) * self.ecfg.page_size
        if cached_len:
            _m_prefix_hit_tokens.inc(cached_len)
        if shared or (self.ecfg.chunked_prefill and T > C):
            # long prompt (or cached prefix): chunk on the decode thread —
            # KV lands straight in pages and the chunk scheduler resumes
            # at the first uncached token
            return pages, T, None, cached_len
        bucket = next(
            (b for b in self.ecfg.prefill_buckets if b >= T),
            self.ecfg.prefill_buckets[-1],
        )
        if T > bucket:
            self._free_pages_and_revive(pages)
            self._fail_request(
                req, f"prompt length {T} exceeds largest bucket {bucket} "
                "(enable chunked_prefill to serve longer prompts)"
            )
            return None
        return pages, T, bucket, 0

    def _state_in(self, req: Request) -> None:
        """The sequence holds no state outside the slots any more (a slot
        took it, or the request ended before one did): one more may be
        admitted, and those parked are asked again."""
        if not req._state_out:
            return
        waiting: "list[Request]" = []
        with self._alloc_lock:
            req._state_out = False
            if (self._state_room is not None
                    and self._states_out >= self._state_room):
                waiting, self._waiting = self._waiting, []  # parked for it
            self._states_out -= 1
        self._requeue(waiting)

    def _prefill_batch(self, reqs: List[Request]) -> None:
        """Admit + prefill a drained batch. Never raises: each request
        ends this call deferred (_waiting), published (_ready), or failed
        (error set, pages freed) — independently of its batch-mates."""
        with _prefill_phase("admit"):
            admitted = self._admit_batch(reqs)
        by_bucket: Dict[int, List[tuple]] = {}
        for item in admitted:
            by_bucket.setdefault(item[3], []).append(item)
        tiers = self.ecfg.prefill_tiers()
        for bucket, group in sorted(by_bucket.items()):
            try:
                self._prefill_group(bucket, group, tiers)
            except Exception as e:  # noqa: BLE001 — fail this group only
                logger.warning("prefill failed for bucket %d", bucket,
                               exc_info=True)
                for req, pages, _T, _b, _cl in group:
                    self._free_pages_and_revive(pages)
                    if not req.done.is_set():
                        self._fail_request(req, f"prefill failed: {e!r}")

    def _admit_batch(self, reqs: List[Request]) -> List[tuple]:
        """Pages for each request; chunked prompts go to the decode
        thread's chunk queue. -> the bucket-path admissions."""
        admitted: List[tuple] = []
        for req in reqs:
            if req.cancelled.is_set():  # cancelled while queued
                self._finish_request(req, "cancelled")
                continue
            try:
                out = self._admit_for_prefill(req)
            except Exception as e:  # noqa: BLE001 — fail just this request
                logger.warning("admission failed for %s", req.request_id,
                               exc_info=True)
                self._fail_request(req, f"prefill admission failed: {e!r}")
                continue
            if out is not None:
                admitted.append((req, *out))
        chunked = [it for it in admitted if it[3] is None]
        if chunked:
            pps = self.ecfg.pages_per_seq
            now = tracing.now_ns()
            with self._chunk_lock:
                for req, pages, T, _b, cached_len in chunked:
                    table = np.zeros((pps,), np.int32)
                    table[: len(pages)] = pages
                    st = _ChunkState(req, pages, table, T)
                    if self._ring:  # filled as the chunks take their pages
                        st.window_table = np.zeros((self._ring,), np.int32)
                    st.done = cached_len  # resume past the hits
                    req.enter_stage("chunk_wait", now)
                    self._chunk_queue.append(st)
            self._work.set()  # the decode thread runs the chunks
        return [it for it in admitted if it[3] is not None]

    def _prefill_group(self, bucket: int, group: List[tuple],
                       tiers: List[int]) -> None:
        B = len(group)
        # smallest compiled tier covering the group; oversize groups split
        # across dispatches at the largest tier
        Bpad = next((t for t in tiers if t >= B), tiers[-1])
        if B > Bpad:
            self._prefill_group(bucket, group[:Bpad], tiers)
            self._prefill_group(bucket, group[Bpad:], tiers)
            return
        with _prefill_phase("dispatch") as ph:
            padded = np.zeros((Bpad, bucket), np.int32)
            lens = np.ones((Bpad,), np.int32)  # dummy rows: true_len 1
            for i, (req, _pages, T, _b, _cl) in enumerate(group):
                padded[i, :T] = req.prompt
                lens[i] = T
                req.enter_stage("prefill", ph.start_ns)
            logits, cache = self._prefill_fn(bucket, Bpad)(
                self.params, jnp.asarray(padded), jnp.asarray(lens)
            )
            self._bucket_tokens += Bpad * bucket
        # first generated tokens: one small readback, on THIS thread.
        # Sample every row BEFORE emitting/publishing anything: if this
        # raises, the caller's failure path can still free every page
        # safely because no request has been published to _ready yet.
        with _prefill_phase("readback"):
            live = sum(g[2] for g in group)
            logits_host = self._take_choices(  # a dummy row holds a token
                np.asarray(logits), Bpad, bucket, live,
                live + Bpad - len(group))
            firsts = [
                _sample_host(logits_host[i], req.temperature,
                             req.top_p, req.top_k)
                for i, (req, _p, _T, _b, _cl) in enumerate(group)
            ]
            first_lps = [_host_logprob(logits_host[i], firsts[i])
                         for i in range(len(group))]
        with _prefill_phase("publish") as ph:
            self._publish_group(group, firsts, first_lps, cache,
                                ph.start_ns)

    def _publish_group(self, group: List[tuple], firsts, first_lps, cache,
                       now_ns: int) -> None:
        """First tokens to their requests, and the group to `_ready` (or,
        for streamed exports, its KV frames to the sinks)."""
        wv = self.weights_version  # generation stamp: sampled under these
        streamed = [i for i, it in enumerate(group)
                    if it[0].prefill_only and it[0].kv_sink is not None]
        with self._ready_lock:
            for i, (req, pages, T, _b, _cl) in enumerate(group):
                self._note_first_token(req, now_ns)
                _m_tokens.inc()
                self._give_first_token(req, int(firsts[i]), first_lps[i], wv)
                if i in streamed:
                    continue  # frames pushed below; never parks in _ready
                # every leaf has the batch on axis 1 (a stack's cache also
                # holds its state beside pages)
                row_cache = jax.tree.map(lambda a: a[:, i:i + 1], cache)
                self._ready.append((req, pages, row_cache, T))
        self._work.set()  # revive the decode thread if it is idle-waiting
        if streamed:
            self._stream_group_kv(group, streamed, cache)

    def _stream_group_kv(self, group: List[tuple], streamed: List[int],
                         cache) -> None:
        """Streamed-export leg of a bucketed prefill group (prefill
        thread). Group-wide device->host pulls instead of per-request row
        readbacks — and with layer-major framing the pull itself is
        SPLIT by layer group: each group's frames are on the wire while
        the next group is still crossing device->host, so the decode
        side sees its first frame after ~1/G of the transfer instead of
        all of it (the first-frame latency that sets mixed-load TTFT).
        Cast matches _export_blob so import -> decode continues
        token-exactly. Failures fail only the affected request."""
        dtype = self.k_pages.dtype
        token_major = [i for i in streamed
                       if self._kv_layout(group[i][0]) != "layer"]
        layer_major = [i for i in streamed if i not in token_major]
        live = set(streamed)

        def fail(i: int, e: Exception) -> None:
            req, pages = group[i][0], group[i][1]
            logger.warning("kv stream failed for %s", req.request_id,
                           exc_info=True)
            self._free_pages_and_revive(pages)
            self._fail_request(req, f"kv stream failed: {e!r}")
            live.discard(i)

        if token_major:
            k_host = np.asarray(cache["k"].astype(dtype))
            v_host = np.asarray(cache["v"].astype(dtype))
            for i in token_major:
                req, pages, T, _b, _cl = group[i]
                try:
                    self._stream_kv_frames(req, k_host[:, i, :T],
                                           v_host[:, i, :T], 0,
                                           true_len=T, last=True)
                except Exception as e:  # noqa: BLE001 — fail this request
                    fail(i, e)
        if layer_major:
            L = int(cache["k"].shape[0])
            groups_l = _kv_layer_groups(L)
            seqs = {i: 0 for i in layer_major}
            # ONE device->host pull, slabs sliced from the host copy: a
            # per-slab device slice is its own XLA program and every one
            # of them queues behind whatever decode span is in flight —
            # measured here, two slab pulls cost more wall than the whole
            # cache. The wire stays layer-major (per-slab frames) either
            # way; only the pull is batched.
            k_all = np.asarray(cache["k"].astype(dtype))
            v_all = np.asarray(cache["v"].astype(dtype))
            for gi, (l0, l1) in enumerate(groups_l):
                kg = k_all[l0:l1]
                vg = v_all[l0:l1]
                for i in layer_major:
                    if i not in live:
                        continue
                    req, _pages, T, _b, _cl = group[i]
                    try:
                        seqs[i] = self._stream_kv_frames(
                            req, kg[:, i, :T], vg[:, i, :T], 0,
                            true_len=T, last=gi == len(groups_l) - 1,
                            seq0=seqs[i], layer0=l0, n_layers=L)
                    except Exception as e:  # noqa: BLE001 — this req only
                        fail(i, e)
        for i in streamed:
            if i not in live:
                continue
            req, pages = group[i][0], group[i][1]
            self._free_pages_and_revive(pages)
            self._finish_request(req, "prefill_done")

    def _install_ready(self) -> bool:
        """Decode thread: move finished prefills into free decode slots
        (KV page scatter + slot bookkeeping only). A chunked prompt comes
        with its first token still on the device (`Request._first`): its
        slot's row of the carry takes the token from there, so the sequence
        joins the span this iteration dispatches as one that continues, and
        what needs the token's VALUE (the stage, an ending it decides)
        waits for the read behind that span (`_read_firsts`). No sync."""
        installed = False
        while True:
            free_slots = [s for s in self.slots if s.request is None]
            with self._ready_lock:
                if not self._ready:
                    return installed
                if free_slots:
                    idx = 0
                else:
                    # prefill-only requests never take a slot: export them
                    # even while the decode batch is full
                    idx = next((j for j, it in enumerate(self._ready)
                                if it[0].prefill_only), None)
                    if idx is None:
                        return installed
                req, pages, cache, T = self._ready.pop(idx)
            if req.cancelled.is_set():  # cancelled between prefill/install
                self._free_pages_and_revive(pages)
                self._finish_request(req, "cancelled")
                installed = True
                continue
            if req.prefill_only:
                try:
                    blob = self._export_blob(req, pages, cache, T)
                except Exception as e:  # noqa: BLE001 — fail this request
                    logger.warning("kv export failed for %s", req.request_id,
                                   exc_info=True)
                    self._free_pages_and_revive(pages)
                    self._fail_request(req, f"kv export failed: {e!r}")
                    installed = True
                    continue
                if self.prefix is not None:
                    # the prefill fleet still benefits from prefix hits:
                    # land the KV in pages and offer them to the cache
                    if "k" in cache:
                        self._scatter_prefill(cache, pages, T)
                    hashes = getattr(req, "_page_hashes", None)
                    with self._alloc_lock:
                        self.prefix.register(req.prompt, pages, hashes=hashes)
                req._kv_export = blob
                self._free_pages_and_revive(pages)
                self._finish_request(req, "prefill_done")
                installed = True
                continue
            # chunked prefills wrote pages directly
            if "k" in cache:
                if self._ring:
                    # the bucket's pages, in both spaces: those its padded
                    # rows fill too, which the scatter writes whole (one
                    # program a bucket) and decode will soon need
                    self._grow(pages, cache["k"].shape[2])
                self._scatter_prefill(cache, pages, T)
            slot = free_slots[0]
            if self.state and not self._ring:
                # the slot's reset: the sequence's conv tails, scan state
                # and window keys overwrite what the last occupant left
                self.state = self._install_state(
                    self.state, {n: cache[n] for n in self.state},
                    jnp.int32(self.slots.index(slot)), jnp.int32(T))
                _m_state_slots.inc()
            self._state_in(req)
            if self.prefix is not None:
                # the prompt's full pages are now valid: offer them to the
                # cache so later prompts sharing the prefix skip prefill
                # (hash chain computed at admission; lock sees dict ops only)
                hashes = getattr(req, "_page_hashes", None)
                with self._alloc_lock:
                    self.prefix.register(req.prompt, pages, hashes=hashes)
            slot.request = req
            slot.pages = pages
            slot.position = T  # the sampled token will be written at T
            slot.generated = 1
            first = req._first
            if first is not None:  # its token is still on the device
                first.slot = self.slots.index(slot)
                self._carry = self._join_carry(
                    self._carry, np.int32(first.slot), first.token,
                    np.int32(T))
            else:
                req.enter_stage("decode", tracing.now_ns())
                if self._spec is not None:
                    # draft proposer: prefill the prompt into the slot's
                    # draft pages (runs on the decode thread — donated draft
                    # pools are only ever touched here and in run_step)
                    self._spec.on_install(self.slots.index(slot), req)
                self._maybe_finish(slot, req.output[-1])
            installed = True
            _m_running.set(sum(1 for s in self.slots if s.request is not None))

    # ------------------------------------------------------------- stepping

    def _advance_chunks(self) -> bool:
        """The iteration's turn of the chunk queue: as many chunks as prompts
        wait there, the oldest prompt's first, and no more ROWS than the
        span under prefill pressure has steps of `prefill_chunk`. One prompt
        alone advances a chunk an iteration whatever its length, a wide one
        (`_wide_chunk`) while it has more than `prefill_chunk` tokens left;
        a deep queue (twelve long contexts asked for the first time, their
        re-asks behind them) takes turns with the decoders chunk for step,
        so the time the queue needs does not grow with the decoders' spans
        between its chunks, and the stall a decoder sees between two spans
        stays `busy_span x prefill_chunk` rows at most, a wide chunk two of
        them (PERF.md section 6, PR 39 and PR 46)."""
        chunked = False
        C = self.ecfg.prefill_chunk
        room = max(1, self.ecfg.busy_span) * C
        # racy read: the prefill thread appends, only this thread removes
        for _ in range(max(1, min(len(self._chunk_queue),
                                  self.ecfg.busy_span))):
            rows = self._advance_chunk(room) if room >= C else None
            if rows is None:
                break
            chunked = True
            room -= rows
        return chunked

    def _advance_chunk(self, room: Optional[int] = None) -> Optional[int]:
        """Run ONE prefill chunk of the oldest chunked request (decode
        thread only — chunk programs donate the page pool). A decode
        span follows the iteration's chunks, so long prompts and the
        running batch interleave at chunk granularity (vLLM chunked
        prefill). The wide program where the model has one, the prompt has
        MORE than `prefill_chunk` tokens left and the turn has `room` for
        its rows (None: any): exactly where `prefill_chunk` rows would have
        run twice for the same prompt; a prompt's tail, a cached prompt's
        one chunk and a streamed export keep `prefill_chunk` rows.

        No chunk is waited for here, a prompt's last neither: its program
        draws the first token with the request's temperature, top_p and
        top_k, and the request goes to `_ready` with the token on the
        device (`_First`), to be read behind the iteration's span
        (`_read_firsts`). Two kinds of request need the token's value
        where the chunk is dispatched and are read here, with the device
        dry behind them: a `prefill_only` one (its export carries the
        token) and any request of an engine that speculates (a round
        starts from committed tokens).
        -> the rows that ran (0: a cancelled prompt left the queue), None
        where nothing waits."""
        with self._chunk_lock:
            if not self._chunk_queue:
                return None
            st = self._chunk_queue[0]
            if st.request.cancelled.is_set():  # cancelled between chunks
                self._chunk_queue.pop(0)
                self._free_pages_and_revive(st.pages)
                self._finish_request(st.request, "cancelled")
                return 0
        if self.prefix is not None and not st.request.prefill_only:
            self._take_late_hits(st)
        req = st.request
        streaming = req.prefill_only and req.kv_sink is not None
        start = st.done
        C = self.ecfg.prefill_chunk
        if (self._wide and st.true_len - start > C and not streaming
                and (room is None or room >= self._wide)):
            C = self._wide  # this chunk's rows
        # A chunk writes all its rows, padding too, to `table[at // ps]`,
        # and past the table's end the device reads its LAST entry: a real
        # page of a sequence that holds them all, where a padding row would
        # land on a prompt's own. Such a chunk (a prompt's last) starts as
        # many pages earlier as it takes to end with the table, and computes
        # again rows the pages already hold. (Not where layers keep state
        # beside the pages, which is the state at `st.done`.)
        end = len(st.table) * self.ecfg.page_size
        if start + C > end and not self.cfg.has_state:
            start = end - C  # >= 0: the table holds either program's rows
        toks = req.prompt[start:start + C]
        padded = np.zeros((C,), np.int32)
        padded[: len(toks)] = toks
        is_last = start + C >= st.true_len
        last_idx = (st.true_len - 1 - start) if is_last else C - 1
        if req.stage == "chunk_wait":  # its first chunk goes out now
            req.enter_stage("prefill", tracing.now_ns())
        if self._ring:  # this chunk's pages, in both spaces
            self._grow(st.pages, start + C)
            st.table[: len(st.pages)] = st.pages
            st.window_table[: len(st.pages.window)] = st.pages.window
        if st.state is None:
            # (where the window layers' pages are allocated a sequence
            # keeps nothing beside its pages: the chunk takes the engine's
            # state, the window page space's pools, and hands it back)
            st.state = {} if self._ring else self._request_start
        # export variant (streaming): the SAME dispatch also returns this
        # chunk's KV slabs, so the streamed frames below need no
        # page-gather program (which would queue behind in-flight decode
        # spans)
        with tracing.region("engine.chunk.put"):
            key = self._first_key
            if is_last and req.temperature > 0:  # a draw of its own
                self._first_draws += 1
                key = jax.random.fold_in(key, self._first_draws)
            placed = (jnp.asarray(padded), jnp.int32(start),
                      jax.tree.map(jnp.asarray, self._tables(
                          st.table, st.window_table)),
                      jnp.int32(last_idx))
            how = _how_to_sample(req.temperature, req.top_p, req.top_k)
        with tracing.region("engine.chunk.call", start=start,
                            tokens=len(toks), padded=C, rows=C):
            token, row, self.k_pages, self.v_pages, *kv, state = \
                self._chunk_fn(C, streaming)(
                    self.params, self.k_pages, self.v_pages, *placed,
                    self.state if self._ring else st.state, how, key)
            if self._ring:
                self.state = state
            else:
                st.state = state
            del placed  # as in `step()`
        self._dispatched(row)
        self._chunk_tokens += C
        _m_chunk_rows.inc(C)
        _m_chunk_calls.labels(rows=str(C)).inc()
        _m_chunk_padding_tokens.inc(C - len(toks))
        if not is_last:  # the last chunk's are counted with its logits
            self._count_moe_rows(1, C, len(toks), held=len(toks))
        chunk_kv = (*kv, start) if streaming else None
        st.done = start + C
        if not is_last and self.prefix is not None:
            # the chunk's pages are written by a program already in the
            # device's queue: whoever asks for this prefix from now on
            # (or waits behind this prompt) reads them, not its own. Were
            # that program to fail, the pool it returns by donation is
            # gone with it, for every later program, cached pages or not
            with self._alloc_lock:
                self.prefix.register(
                    req.prompt[:start + C], st.pages,
                    hashes=getattr(req, "_page_hashes", None))
        if not is_last:
            if streaming:
                # pages for [emitted_upto, start+C) are committed: ship
                # them NOW so migration overlaps the remaining chunks
                # (the first call also covers a cached prefix, whose
                # shared pages hold identical KV by the chain-hash key)
                try:
                    self._stream_chunk_frames(st, start + C, last=False,
                                              chunk_kv=chunk_kv)
                except Exception as e:  # noqa: BLE001 — fail this request
                    logger.warning("kv stream failed for %s",
                                   req.request_id, exc_info=True)
                    with self._chunk_lock:
                        if st in self._chunk_queue:
                            self._chunk_queue.remove(st)
                    self._free_pages_and_revive(st.pages)
                    self._fail_request(req, f"kv stream failed: {e!r}")
            return C
        with self._chunk_lock:
            self._chunk_queue.pop(0)
        first = req._first = _First(req, token, row, C, len(toks),
                                    self.weights_version)
        if not req.prefill_only and self._spec is None:
            # like any other chunk, the last is not waited for: the token
            # joins the next span on the device (`_install_ready`) and the
            # host reads it once that span is out (`_read_firsts`)
            self._firsts.append(first)
            if req.max_tokens <= 1:  # known to end there: it takes no slot
                first.pages = st.pages
                return C
            with self._ready_lock:
                # no keys in the cache: this prompt's KV is already in its
                # pages (state beside pages still has to reach its slot)
                self._ready.append((req, st.pages, st.state, st.true_len))
            return C
        # The token's VALUE is needed here: an export carries it (the
        # streamed final frame, the blob), and a round of speculation
        # starts from committed tokens. Neither joins a span from the carry
        # (a property of the request and of the engine, no option)
        first.pages = st.pages  # a read that fails frees them
        if not self._read_first(first, nested=True):
            return C
        if streaming:
            # final frame carries first_token; pages free immediately —
            # the request never parks in _ready on the streamed path
            try:
                self._stream_chunk_frames(st, st.true_len, last=True,
                                          chunk_kv=chunk_kv)
            except Exception as e:  # noqa: BLE001 — fail this request
                logger.warning("kv stream failed for %s", req.request_id,
                               exc_info=True)
                self._free_pages_and_revive(st.pages)
                self._fail_request(req, f"kv stream failed: {e!r}")
                return C
            self._free_pages_and_revive(st.pages)
            self._finish_request(req, "prefill_done")
            return C
        with self._ready_lock:
            self._ready.append((req, st.pages, st.state, st.true_len))
        return C

    def _read_firsts(self, behind_span: bool = False) -> None:
        """Read the first tokens that chunk programs drew since the last
        read, in the order of their dispatch. `step()` comes here once the
        iteration's span is out (`behind_span`) and before it reads the
        span before it, so a request has its first token before any token
        of a span can be committed to it, and the read waits for the chunk
        alone: the first token reaches its reader no later than it did when
        the chunk was read where it was dispatched. `_drain()` comes here
        with nothing behind the chunks.

        What the token decides, it decides here. A request that took no
        slot (`max_tokens` 1) ends, its pages freed. A sequence whose slot
        joined the span just dispatched and whose first token ends it (eos,
        a stop sequence; or a cancel since) is an ending the host could not
        foresee (`step`): the request finishes now, that span commits
        nothing to it, and its pages are freed once the span is read. A
        sequence that still waits in `_ready` is installed with its token
        known, as a bucket's is."""
        firsts, self._firsts = self._firsts, []
        for first in firsts:
            if not self._read_first(first, behind_span):
                continue
            req = first.request
            if first.pages is not None:  # it took no slot
                if self.prefix is not None:
                    with self._alloc_lock:
                        self.prefix.register(
                            req.prompt, first.pages,
                            hashes=getattr(req, "_page_hashes", None))
                reason = self._ending(req, 1, req.output[-1])
                self._free_pages_and_revive(first.pages)
                self._finish_request(req, reason or "length")
            elif first.slot is not None:
                slot = self.slots[first.slot]
                if slot.request is req:
                    self._maybe_finish(slot, req.output[-1])

    def _read_first(self, first: _First, behind_span: bool = False,
                    nested: bool = False) -> bool:
        """Block until a last chunk is done and hand its request the first
        token: `engine.chunk.readback`, the one place where the host waits
        for a chunk program (`nested`: inside `engine.chunk`, which
        `_account` has to know). -> False where there is nobody to hand it to:
        the request ended meanwhile (a cancel), or the chunk program raised,
        which surfaces here: that request fails, and what it holds (a slot,
        a place in `_ready`, its pages) is let go as for a cancel."""
        req = first.request
        try:
            with self.phase("chunk.readback") as ph:
                row = np.asarray(first.row)
        except Exception as e:  # noqa: BLE001 — fail this request
            logger.warning("chunk failed for %s", req.request_id,
                           exc_info=True)
            self._fail_first(first, f"prefill failed: {e!r}")
            return False
        finally:
            req._first = None
        if nested:
            self._nested_read_ns += ph.elapsed_ns
        (_first_behind_span if behind_span else _first_drained).inc()
        # `cancel()` ends a request that waits in `_ready` under this lock
        with self._ready_lock:
            if req.done.is_set():
                return False
            # where the device counts choices, those of every chunk the
            # request ran came with its first token
            counted = ({"choices": row[2:4]} if self.cfg.counts_choices
                       else {})  # by keyword, where there are any
            self._count_moe_rows(1, first.rows, first.tokens,
                                 held=first.tokens, **counted)
            now = tracing.now_ns()
            self._note_first_token(req, now)
            _m_tokens.inc()
            self._give_first_token(req, int(row[0]), float(row[1]),
                                   first.weights_version)
            if first.slot is not None:  # a slot took it before its token
                req.enter_stage("decode", now)
        return True

    def _fail_first(self, first: _First, msg: str) -> None:
        """The chunk program of `first` raised: its request fails and lets
        go of what it holds."""
        req = first.request
        pages = first.pages
        if first.slot is not None and self.slots[first.slot].request is req:
            self._retire(self.slots[first.slot])
        with self._ready_lock:
            for item in list(self._ready):
                if item[0] is req:
                    self._ready.remove(item)
                    pages = item[1]
        if pages is not None:
            self._free_pages_and_revive(pages)
        if not req.done.is_set():
            self._fail_request(req, msg)

    def _take_late_hits(self, st: _ChunkState) -> None:
        """A chunked prompt about to run a chunk looks its prefix up once
        more: pages that another prompt registered since this one was
        admitted (the same context, asked a moment earlier and prefilled
        ahead of it in the queue) take the place of its own, which are
        freed, and it resumes past them. Without it every ask of a context
        that arrives while the first is still being prefilled runs the
        whole prefill again. It resumes at the page the run ends at, as an
        admitted hit does. One dict lookup where nothing is new."""
        req = st.request
        C, ps = self.ecfg.prefill_chunk, self.ecfg.page_size
        hashes = getattr(req, "_page_hashes", None)
        at = st.done // ps  # the page its next chunk writes first
        if not hashes or at >= len(hashes):
            return
        with self._alloc_lock:
            if self.prefix.by_hash.get(hashes[at]) in (None, st.pages[at]):
                return
            shared = self.prefix.lookup_acquire(req.prompt, C, hashes=hashes)
            n = len(shared)
            if n * ps <= st.done:
                self.prefix.release_and_filter(shared)
                return
            own = st.pages[:n]
            st.pages[:n] = shared
            st.table[:n] = shared
        # cached pages among its own drop the ref they held; the others go
        # back to the allocator (what earlier chunks of this prompt wrote
        # there is in the shared pages too, by the chain hash)
        self._free_pages_and_revive(own)
        _m_prefix_hit_tokens.inc(n * ps - st.done)
        st.done = n * ps

    def step(self) -> bool:
        """One engine iteration: advance the chunk queue (`_advance_chunks`:
        a chunk a waiting prompt, at most `busy_span`), install finished
        prefills, then dispatch an n-step decode span for the
        active batch (n = decode_span, or busy_span under prefill pressure:
        an argument of the decode program) and only then read
        back and commit the span that the iteration BEFORE dispatched.
        The loop runs one span ahead: span N+1 starts from the `tokens` and
        `positions` span N's scan ended on, which never leave the device,
        and the host's phases pass while the device computes. Returns True
        if work happened.

        What the host knows at build time decides who is in span N+1:
        - a slot whose answer ends by `max_tokens` inside span N (unread,
          but its length is known) is left out, and leaves at commit N;
        - a slot installed since N went out joins with the host's token
          and position (`fresh` in the program);
        - a slot installed with its first token still on the device (a
          chunked prompt's: the chunk program drew it, `_install_ready`
          wrote it into the carry) joins as one that continues. The host
          reads that token once N+1 is out and before it reads N
          (`_read_firsts`), so the request has it before any token of N+1
          can be committed; an ending it decides (eos, a stop sequence) is
          one the host could not foresee, below. A request that is known
          to end at its first token (`max_tokens` 1) takes no slot;
        - an ending the host cannot foresee (eos, a stop sequence, a
          cancel) finds the slot in N+1 already. The request finishes at
          commit N; N+1 commits to the requests it was dispatched WITH
          (`_Span.members`), so that column is dropped whoever holds the
          slot by then.

        A slot that finishes mid-span keeps decoding to span end; its
        extra tokens are discarded by the host loop, and its extra KV
        writes are harmless: table entries past the allocated pages are 0
        (the reserved trash page), and a sequence's pages are freed only
        once the LAST span dispatched with its table has been read back
        (`_maybe_finish` hands them to that span's `release`), so no
        recycled page can be written. Every program that writes the pool or
        the per-slot state is dispatched from this thread and takes both by
        donation, so the device runs them in this order whatever the host
        has read.

        Where the loop must know span N's tokens before it acts it drains
        first (`_drain`: the first tokens still on the device, then the
        span), which is this same pipeline at depth 0: speculation
        (EngineConfig.speculation: ONE propose-k/verify-once round per
        iteration committing 1..k+1 tokens per slot, spec_decode.SpecDecoder,
        whose proposer reads committed tokens), `update_params` (a span
        dispatched under the old weights commits under their
        `weights_version`), `stop()`, and an engine with nothing live.

        Every iteration with active slots observes the per-phase timing
        histogram (serve_decode_step_phase_seconds, tagged phase+mode)."""
        with self.phase("chunk"):
            chunked = self._advance_chunks()
        with self.phase("install"):
            installed = self._install_ready()
        if self._swaps:
            self._swap_params()
        # Cancellation sweep: a request cancelled mid-decode (or mid-
        # speculation round) frees its slot at this step boundary instead
        # of riding out the span / the committed draft prefix.
        with self.phase("cancel_check") as ph:
            for s in self.slots:
                if s.request is not None and s.request.cancelled.is_set():
                    self._maybe_finish(s, -1)
            active = self._active()
        prev = self._inflight
        if not active:
            self._drain()
            return installed or chunked or prev is not None
        n_active = self._live = len(active)
        mode = "spec" if self._spec is not None else "plain"
        _step_phase["cancellation_check", mode].observe(ph.elapsed_s)

        with self.phase("build"):
            (members, tokens, positions, tables, temps, top_ps, top_ks,
             fresh, advanced) = self._build_batch(prev)
            # Adaptive span (VERDICT r3 #2): while prefill work is queued
            # or running, shrink the span so the device yields between
            # decode dispatches and arriving requests get their first
            # token (emitted by the prefill program) without waiting out a
            # long span. An iteration that dispatched a chunk counts as
            # such: nobody waited for the chunk, so the prompts that arrive
            # while it runs are not in the queues yet (chip, PR 51: without
            # it the build after a last chunk took the long span and the
            # next prompt's first token came 8 to 25 ms later).
            if self.ecfg.adaptive_span and (
                self._prefill_inflight > 0
                or not self.pending.empty()
                or self._chunk_queue  # racy read is fine: pressure hint only
                or chunked
                or self._importing > 0  # streamed KV imports staged (disagg)
            ):
                span = max(1, self.ecfg.busy_span)
            else:
                span = max(1, self.ecfg.decode_span)
            self._count_pages()
            if members:
                self._step_count += 1
                key = jax.random.fold_in(self._base_key, self._step_count)
        if self._spec is not None:
            # drained by now: nothing stays in flight under speculation, so
            # every live slot is a member
            if self._step_spec(tokens, positions, tables, temps, top_ps,
                               top_ks, advanced, key, n_active):
                return True
            # zero-draft fallback: the (cheap) proposer found nothing to
            # draft anywhere in the batch this round — the plain span
            # below commits span tokens per slot where the S-wide verify
            # would commit exactly one
        cur = None  # every live slot may end inside `prev`
        if members:
            cur = self._open_span(span, members)
            with self.phase("dispatch", **cur.attrs) as ph:
                with tracing.region("engine.dispatch.put"):
                    placed = [jax.tree.map(jnp.asarray, a) for a in (
                        tokens, positions, tables, temps, top_ps, top_ks,
                        fresh)]
                with tracing.region("engine.dispatch.call"):
                    cur.seq, cur.logps = self._run_decode(
                        self._decode(span, advanced)(
                            self.params, self.k_pages, self.v_pages,
                            *placed[:6], key, self.state,
                            (*self._carry, placed[6])))
                    # dropped while the program holds them: freed after the
                    # readback they cost 5 ms an iteration (chip, PR 36)
                    del placed
                    if self._dispatched(cur.seq):
                        _m_ahead.inc(span)  # the device never ran dry
            cur.dispatched_ns = ph.end_ns
            _step_phase["verify", "plain"].observe(ph.elapsed_s)
        self._inflight = cur
        self._read_firsts(behind_span=cur is not None)
        if prev is not None:
            self._finish_span(prev)
        if self._spec is not None or self._stop.is_set():
            self._drain()
        return True

    def _drain(self) -> None:
        """Read the first tokens that are still on the device, then read
        back and commit the span in flight, if any (in that order: the span
        may hold a sequence whose first token is among them): the pipeline
        at depth 0."""
        self._read_firsts()
        span, self._inflight = self._inflight, None
        if span is not None:
            self._finish_span(span)

    def _finish_span(self, span: _Span) -> None:
        """Read a dispatched span back and commit it to the requests it
        went out with; then the pages that rode it are free."""
        with self.phase("readback") as ph:
            # one readback per span: [K, B], rows [:steps] the span's
            seq = np.asarray(span.seq)
            logps = np.asarray(span.logps)  # and the counts' behind row K
        self._span_read(span, ph)
        _step_phase["sample", "plain"].observe(ph.elapsed_s)
        with self.phase("commit") as ph:
            n = len(span.members)
            self._count_slot_steps(n, span.steps)
            # by keyword, and only where there are any: callers that wrap
            # this method know its four positional arguments; the counts'
            # rows follow the K of the steps in the order the program
            # appends them
            counted, row = {}, self.ecfg.span_rows
            if self.cfg.counts_choices:
                counted["choices"], row = logps[row, :2], row + 1
            if self._steps_visit:
                counted["touched"] = float(logps[row, 0])
            self._count_moe_rows(self.ecfg.max_batch_size, 1, n, span.steps,
                                 **counted)
            self._tps_committed += self._commit_span(span, seq, logps)
            for pages in span.release:
                self._free_pages_and_revive(pages)
        _step_phase["cache_bookkeeping", "plain"].observe(ph.elapsed_s)

    def _build_batch(self, prev: Optional[_Span]):
        """The next span's members (slot -> request) and its batch as numpy
        arrays, one row per slot. A slot that rides `prev`, the span still
        unread, continues from the device's carry (`fresh` False; the host
        does not know its token yet), unless its answer ends inside `prev`
        by `max_tokens`: then it is left out, a row of zeros like an empty
        slot's, which costs its programs nothing. A slot installed with its
        first token unread continues from the carry too: its row holds the
        token (`_install_ready`)."""
        B = self.ecfg.max_batch_size
        pps = self.ecfg.pages_per_seq
        tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        tables = np.zeros((B, pps), np.int32)  # page 0 = trash
        window_tables = np.zeros((B, self._ring), np.int32)
        temps = np.zeros((B,), np.float32)
        top_ps = np.ones((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        fresh = np.ones((B,), bool)
        advanced = False
        members: Dict[int, Request] = {}
        riding = prev.members if prev is not None else {}
        for i, s in enumerate(self.slots):
            req = s.request
            if req is None:
                continue
            rides = riding.get(i) is req
            if rides:
                if s.generated + prev.steps >= req.max_tokens:
                    continue
                fresh[i] = False
            elif req._first is not None:
                fresh[i] = False
            else:
                tokens[i] = req.output[-1]
                positions[i] = s.position
            members[i] = req
            if self._ring:
                # the pages of the tokens this span may write (after those
                # of the span still unread), in both spaces
                ahead = prev.steps if rides else 0
                self._grow(s.pages, s.position + ahead + max(
                    self.ecfg.decode_span, self.ecfg.busy_span, 1))
                window_tables[i, : len(s.pages.window)] = s.pages.window
            tables[i, : len(s.pages)] = s.pages
            temps[i] = req.temperature
            top_ps[i] = req.top_p
            top_ks[i] = req.top_k
            if req.temperature > 0 and (req.top_p < 1.0 or req.top_k > 0):
                advanced = True  # the sort-based sampler program runs
        return (members, tokens, positions,
                self._tables(tables, window_tables), temps, top_ps, top_ks,
                fresh, advanced)

    def _commit_span(self, span: _Span, seq, logps) -> int:
        """The host loop after a span's readback: tokens to the requests
        the span was dispatched with, finished slots retired. A member
        whose slot has let it go since (it ended in the span before, or was
        cancelled; the slot may be another's by now) gets nothing.
        -> tokens committed."""
        committed = 0
        eos = self.ecfg.eos_token_id
        rows = [(i, self.slots[i], req) for i, req in span.members.items()]
        for t in range(span.steps):
            for i, s, req in rows:
                if s.request is not req:
                    continue  # finished earlier (in this span or before it)
                s.position += 1
                tok = int(seq[t, i])
                if s.generated < req.max_tokens and not req.done.is_set():
                    req.output.append(tok)
                    req.output_logprobs.append(float(logps[t, i]))
                    s.generated += 1
                    committed += 1
                    _m_tokens.inc()
                    if eos is not None and tok == eos:
                        pass  # eos is control, not content
                    elif req.stop:
                        # hold back: _maybe_finish drains tokens that can
                        # no longer be part of a stop match, strips matched
                        # tails, and _finish_request flushes the rest — a
                        # matched stop never leaks to streaming consumers
                        req._held.append(tok)
                    else:
                        req._emit(tok)
                self._maybe_finish(s, tok)
        return committed

    def _count_moe_rows(self, rows: int, row_tokens: int, live: int,
                        times: int = 1, choices=None, touched=None,
                        held=None) -> None:
        """A program over `rows` rows of `row_tokens` tokens, `live` of
        them real, dispatched `times` over (a span's steps): each of its
        expert layers computed what `moe_rows_computed` says of the form
        the program took for live x k routed. On the host, from the
        program's static shape, but for two forms whose rows are data.
        `touched`: the experts the steps of a span visited, over its steps
        and layers, which the device counted (`self._steps_visit`): each
        ran over the step's rows, and the others' weights were not read.
        `held`: the tokens the rows of a bucket or a chunk hold (a batch's
        dummy rows among them): where such a program runs each expert over
        the rows that chose it (`moe_seq_groups`), its rows are the passes
        of the kernel, of which the host counts the bound (never less).
        `choices` (zero, held): what the device counted for a layer that
        holds a share of the experts; the rows routed to THIS layer's
        products are then the held ones. Every counter here but
        `serve_moe_shared_rows` speaks of the ROUTED experts alone."""
        layers = self.cfg.second_halves.count("moe")
        if not layers:
            return
        if self.cfg.d_ff_shared:
            _m_moe_shared_rows.inc(times * layers * rows * row_tokens)
        if touched is None:
            _m_moe_rows_computed.inc(
                times * layers * moe_rows_computed(
                    self.cfg, rows, row_tokens, self.mesh, tokens=held))
        else:
            _m_moe_rows_computed.inc(touched * rows)
            _experts_touched.inc(touched)
            _experts_held.inc(times * layers * self.cfg.num_experts)
        chosen = times * layers * live * self.cfg.num_selected_experts
        if not self.cfg.counts_choices:
            _m_moe_rows_routed.inc(chosen)
            return
        _choices_all.inc(chosen)
        if choices is not None:  # a chunk's come with its request's last
            _m_moe_rows_routed.inc(float(choices[1]))
            _choices_held.inc(float(choices[1]))
            _choices_zero.inc(float(choices[0]))

    def _take_choices(self, logits_host: np.ndarray, rows: int,
                      row_tokens: int, live: int, held: int) -> np.ndarray:
        """A prefill program's logits as they came back -> the logits
        alone, the program's expert rows counted (`held`: the tokens its
        rows hold, dummy rows' too). Where the device counted its tokens'
        choices of experts (`cfg.counts_choices`) they are the last two
        entries (of row 0, for a batch)."""
        if not self.cfg.counts_choices:
            self._count_moe_rows(rows, row_tokens, live, held=held)
            return logits_host
        self._count_moe_rows(rows, row_tokens, live, held=held,
                             choices=logits_host[..., -2:].reshape(-1, 2)[0])
        return logits_host[..., :-2]

    def _count_slot_steps(self, n_active: int, steps: int) -> None:
        self._tps_steps += n_active * steps
        _slot_active.inc(n_active * steps)
        _slot_empty.inc((self.ecfg.max_batch_size - n_active) * steps)
        if {"ssm", "gdn", "ssd"} & set(self.state):
            _state_live.inc(n_active * steps)
            _state_held.inc(self.ecfg.max_batch_size * steps)

    def _count_pages(self) -> None:
        """Once an iteration: pages held by slots, chunked prompts and
        prefills awaiting install, and how many of them hold a token."""
        ps = self.ecfg.page_size
        reserved = written = 0
        for s in self.slots:
            if s.request is not None:
                reserved += len(s.pages)
                written += -(-s.position // ps)
        with self._chunk_lock:
            for st in self._chunk_queue:
                reserved += len(st.pages)
                written += -(-min(st.done, st.true_len) // ps)
        with self._ready_lock:
            for _req, pages, _cache, T in self._ready:
                reserved += len(pages)
                written += -(-T // ps)
        layers = self.cfg.count("window") or self.cfg.count("swa")
        if not layers:  # one pool
            _pages_reserved.inc(reserved)
            _pages_written.inc(written)
            return
        _pages_by_pool["full", "reserved"].inc(reserved)
        _pages_by_pool["full", "written"].inc(written)
        if self._ring:
            # allocated rings: a sequence of n tokens holds keys in
            # ceil(n / ps) pages and the ring's width at the most, where
            # caching every key would hold ceil(n / ps)
            live = [s for s in self.slots if s.request is not None]
            full_length = [-(-s.position // ps) for s in live]
            held = sum(min(n, self._ring) for n in full_length)
            _window_held.inc(held)
            _window_bound.inc(len(live) * self._ring)
            _window_full_length.inc(sum(full_length))
            _pages_by_pool["window", "reserved"].inc(
                layers * sum(len(s.pages.window) for s in live))
            _pages_by_pool["window", "written"].inc(layers * held)
            return
        # window layers: a slot owns its ring whatever it holds; what an
        # active sequence holds keys in are the pages its window spans
        ring = stack.ring_pages(self.cfg, ps)
        W = self.cfg.window
        held = sum((s.position - 1) // ps - max(s.position - W, 0) // ps
                   + 1 for s in self.slots
                   if s.request is not None and s.position > 0)
        n_active = sum(1 for s in self.slots if s.request is not None)
        _window_held.inc(held)
        _window_bound.inc(n_active * ring)
        _pages_by_pool["window", "reserved"].inc(n_active * ring * layers)
        _pages_by_pool["window", "written"].inc(held * layers)

    def _step_spec(self, tokens, positions, tables, temps, top_ps, top_ks,
                   advanced, key, n_active) -> bool:
        """One speculative round for the built batch arrays: propose up to
        k drafts per slot (capped to the slot's remaining token budget and
        sequence room so no verify write can land past its allocation),
        verify them in one span forward, commit the accepted prefix plus
        the bonus token through the same budget/eos/stop/finish path the
        plain loop uses. Returns False when the proposer declined the
        round (zero drafts batch-wide) — the caller runs a plain span."""
        spec = self._spec
        ecfg = self.ecfg
        caps = np.zeros((ecfg.max_batch_size,), np.int32)
        for i, s in enumerate(self.slots):
            if s.request is None:
                continue
            caps[i] = max(0, min(
                spec.k,
                s.request.max_tokens - s.generated - 1,
                ecfg.max_seq_len - 1 - s.position))
        committed, n_comm, n_draft, times = spec.run_step(
            tokens, positions, tables, caps, temps, top_ps, top_ks,
            advanced, key)
        if committed is None:
            for phase in ("propose", "propose_wait", "propose_compute"):
                _step_phase[phase, "spec"].observe(times[phase])
            return False
        with self.phase("commit") as ph:
            self._count_slot_steps(n_active, 1)
            self._tps_committed += self._commit_spec(committed, n_comm,
                                                     n_draft)
        for phase in ("propose", "propose_wait", "propose_compute",
                      "verify", "sample"):
            _step_phase[phase, "spec"].observe(times[phase])
        _step_phase["cache_bookkeeping", "spec"].observe(ph.elapsed_s)
        return True

    def _commit_spec(self, committed, n_comm, n_draft) -> int:
        """The host loop after a verify round: each slot's accepted
        prefix plus its bonus token. -> tokens committed."""
        spec, ecfg = self._spec, self.ecfg
        proposed = accepted = n_tokens = 0
        for i, s in enumerate(self.slots):
            if s.request is None:
                continue
            proposed += int(n_draft[i])
            accepted += int(n_comm[i]) - 1
            for t in range(int(n_comm[i])):
                if s.request is None:
                    break  # finished on an earlier committed token
                s.position += 1
                tok = int(committed[i, t])
                if (s.generated < s.request.max_tokens
                        and not s.request.done.is_set()):
                    s.request.output.append(tok)
                    # the verify program does not surface per-token
                    # logits to the host; speculative commits carry no
                    # logprob (callers needing them serve without spec)
                    s.request.output_logprobs.append(None)
                    s.generated += 1
                    n_tokens += 1
                    _m_tokens.inc()
                    eos = ecfg.eos_token_id
                    if eos is not None and tok == eos:
                        pass  # eos is control, not content
                    elif s.request.stop:
                        s.request._held.append(tok)
                    else:
                        s.request._emit(tok)
                self._maybe_finish(s, tok)
        spec.record(proposed, accepted)
        return n_tokens

    def _give_first_token(self, req: Request, tok: int,
                          logprob: Optional[float],
                          weights_version: int) -> None:
        """`req`'s first output token, sampled under `weights_version`: to
        its result and to its stream (eos is control; with stops configured
        the hold-back starts at token 1)."""
        req.output.append(tok)
        req.output_logprobs.append(logprob)
        req.weights_version = weights_version
        eos = self.ecfg.eos_token_id
        if eos is not None and tok == eos:
            pass  # eos is control
        elif req.stop:
            req._held.append(tok)
        else:
            req._emit(tok)

    def _note_first_token(self, req: Request, now_ns: int) -> None:
        """The first-token instant: closes the request's `prefill` stage
        and is the TTFT every surface reports."""
        req.enter_stage("ready", now_ns)
        req.first_token_at = now_ns * 1e-9
        ttft = req.first_token_at - req.submitted_at
        _m_ttft.observe(ttft)
        if self._slo_on:
            self._slo_digest("serve_ttft_seconds").add(ttft)

    def _slo_digest(self, name: str) -> "slo.Digest":
        d = self._slo.get(name)
        if d is None:
            d = slo.digest(name, {"role": self.slo_role})
            self._slo[name] = d
        return d

    def _maybe_finish(self, slot: _Slot, last_tok: int) -> None:
        req = slot.request
        if req is None:
            return
        reason = self._ending(req, slot.generated, last_tok)
        if reason is None:
            return
        self._retire(slot)
        self._finish_request(req, reason)

    def _ending(self, req: Request, generated: int,
                last_tok: int) -> Optional[str]:
        """Whether `req` ends with `last_tok`, its `generated`-th token, and
        as what (None: it goes on, and tokens that can no longer be part of
        a stop match reach the stream). An ending strips what is control,
        the eos or the stop sequence, from the result and the hold-back."""
        eos = self.ecfg.eos_token_id
        stopped = eos is not None and last_tok == eos
        stop_len = 0 if stopped else _match_stop(req.output, req.stop)
        stopped = stopped or stop_len > 0
        cancelled = req.cancelled.is_set()
        if not (generated >= req.max_tokens or stopped or cancelled):
            if req._held:
                # no match right now: tokens older than the longest
                # possible stop suffix can safely reach the stream
                hold = max(len(x) for x in req.stop) - 1
                while len(req._held) > hold:
                    req._emit(req._held.pop(0))
            return None
        if eos is not None and req.output and req.output[-1] == eos:
            req.output.pop()
            if req.output_logprobs:
                req.output_logprobs.pop()
        elif stop_len:
            # the stop sequence is control: strip it from the result AND
            # from the stream hold-back so it never reaches consumers
            del req.output[-stop_len:]
            if req.output_logprobs:
                del req.output_logprobs[-min(stop_len,
                                             len(req.output_logprobs)):]
            if req._held:
                del req._held[-min(stop_len, len(req._held)):]
        return ("cancelled" if cancelled
                else "stop" if stopped else "length")

    def _retire(self, slot: _Slot) -> None:
        """The slot lets its sequence go; the caller finishes the request.

        When are the pages free? An ending by `max_tokens` (foreseen: no
        unread span went out with this table) and any ending of a drained
        loop free them here, BEFORE completion is signalled: that caller
        returns from generate() to a stats() that counts them. An ending
        the host could not foresee (eos, a stop sequence, a cancel; a first
        token read behind its sequence's first span) finds the
        sequence in the span in flight, and the device may yet write
        those pages: the request finishes now all the same, and the pages
        are freed one span later, at that span's readback (`_finish_span`
        walks `release`). Until then `stats()["free_pages"]` is short by
        them, so a caller that counts the pool after such an answer polls.
        Admission never reads the stat: a request that finds the pool
        short parks in `_waiting`, and every free, this one too, goes
        through `_free_pages_and_revive`, which wakes it."""
        index = self.slots.index(slot)
        riding = self._inflight
        if riding is not None and riding.members.get(index) is slot.request:
            riding.release.append(slot.pages)
        else:
            self._free_pages_and_revive(slot.pages)
        if self._spec is not None:
            # proposer hygiene: drop the slot's ngram context / invalidate
            # any prefetched draft row so the next occupant can never see
            # this request's state
            self._spec.on_evict(index)
        slot.request = None
        slot.pages = []
        slot.position = 0
        slot.generated = 0
        _m_running.set(sum(1 for s in self.slots if s.request is not None))

    # ------------------------------------------------------------- blocking

    def generate(
        self,
        prompt: List[int],
        max_tokens: int = 32,
        temperature: float = 0.0,
        request_id: Optional[str] = None,
        timeout_s: float = 600.0,
        top_p: float = 1.0,
        top_k: int = 0,
        stop: Optional[List[List[int]]] = None,
        received_ns: Optional[int] = None,
    ) -> Dict[str, Any]:
        import uuid

        req = Request(
            request_id=request_id or uuid.uuid4().hex,
            prompt=list(prompt),
            max_tokens=max_tokens,
            temperature=temperature,
            top_p=top_p,
            top_k=top_k,
            stop=stop,
            received_ns=received_ns,
        )
        self.add_request(req)
        if not req.done.wait(timeout_s):
            # the caller is gone: cancel so the slot/pages free instead
            # of decoding to max_tokens for nobody
            self.cancel(req.request_id)
            raise TimeoutError(f"request {req.request_id} timed out")
        if req.error:
            raise ValueError(req.error)
        return {
            "request_id": req.request_id,
            "token_ids": list(req.output),
            "logprobs": list(req.output_logprobs),
            "weights_version": req.weights_version,
            "finish_reason": req.finish_reason,
            "ttft_s": (req.first_token_at or 0) - req.submitted_at,
            "latency_s": (req.finished_at or 0) - req.submitted_at,
        }

    def open_stream(
        self,
        prompt: List[int],
        max_tokens: int = 32,
        temperature: float = 0.0,
        request_id: Optional[str] = None,
        timeout_s: float = 600.0,
        top_p: float = 1.0,
        top_k: int = 0,
        stop: Optional[List[List[int]]] = None,
        received_ns: Optional[int] = None,
    ):
        """-> (Request, token stream). The request object exposes
        finish_reason/error/timing after the stream is exhausted."""
        import uuid

        req = Request(
            request_id=request_id or uuid.uuid4().hex,
            prompt=list(prompt),
            max_tokens=max_tokens,
            temperature=temperature,
            top_p=top_p,
            top_k=top_k,
            stop=stop,
            stream_q=queue.Queue(),
            received_ns=received_ns,
        )
        self.add_request(req)
        return req, TokenStream(req, timeout_s)

    def generate_stream(
        self,
        prompt: List[int],
        max_tokens: int = 32,
        temperature: float = 0.0,
        request_id: Optional[str] = None,
        timeout_s: float = 600.0,
        top_p: float = 1.0,
        top_k: int = 0,
        stop: Optional[List[List[int]]] = None,
        received_ns: Optional[int] = None,
    ):
        """Yield token ids as they are generated (first at TTFT, not at
        completion). Raises the request's error, if any, after the stream."""
        _, stream = self.open_stream(
            prompt, max_tokens=max_tokens, temperature=temperature,
            request_id=request_id, timeout_s=timeout_s,
            top_p=top_p, top_k=top_k, stop=stop, received_ns=received_ns,
        )
        return stream

    def update_params(self, params, version: Optional[int] = None) -> int:
        """Live weight swap without stopping the engine. Transfers the new
        tree to device (re-sharded onto the engine mesh when there is one),
        waits for the transfer, then has the decode thread rebind
        `self.params` between two iterations: it first reads back and
        commits the span in flight, so every token that the old weights
        computed is committed while `weights_version` still names them, and
        every span dispatched after the rebind serves the new generation.
        The caller waits for that, up to one iteration of the loop. Returns
        the new weights_version."""
        if self.mesh is not None:
            new = jax.device_put(params, self._param_shardings())
        else:
            new = jax.tree_util.tree_map(jnp.asarray, params)
        jax.block_until_ready(new)
        swap = types.SimpleNamespace(params=new, version=version,
                                     bound=threading.Event())
        self._swaps.append(swap)
        while True:
            with self._lock:
                thread = self._loop_thread
                if thread is None or not thread.is_alive() or self._loop_done:
                    self._swap_params()  # nobody else is stepping the loop
            self._work.set()
            if swap.bound.wait(1.0):  # asks again: the thread may have died
                return swap.version

    def _swap_params(self) -> None:
        """Drain, then bind the weights that `update_params` posted."""
        self._drain()
        while self._swaps:
            swap = self._swaps.pop(0)
            self.params = swap.params
            self.weights_version = swap.version = (
                int(swap.version) if swap.version is not None
                else self.weights_version + 1)
            _m_weights_version.set(float(swap.version),
                                   tags={"role": self.slo_role})
            swap.bound.set()

    def stats(self) -> Dict[str, Any]:
        with self._ready_lock:
            ready = len(self._ready)
        with self._chunk_lock:
            chunk_queue = len(self._chunk_queue)
            head = self._chunk_queue[0] if chunk_queue else None
            # the head prompt's progress, in chunks of prefill_chunk tokens
            chunking = ([head.done // self.ecfg.prefill_chunk,
                         -(-head.true_len // self.ecfg.prefill_chunk)]
                        if head is not None else None)
        with self._alloc_lock:
            waiting = len(self._waiting)
            free_pages = self.allocator.num_free
            free_window = self._ring and self._window_allocator.num_free
            prefix = self.prefix.stats() if self.prefix is not None else {}
        # free_pages counts SERVEABLE capacity: zero-ref cached pages are
        # reclaimed on demand (_alloc_with_reclaim), so they are free in
        # every sense that matters to admission
        spec = self._spec.stats() if self._spec is not None else {}
        return {
            "active": len(self._active()),
            "pending": self.pending.qsize(),
            "ready": ready,
            "chunk_queue": chunk_queue,
            "chunking": chunking,
            "waiting_for_pages": waiting,
            # pages of THE pool (EngineConfig.pages_per_seq says whose)
            "page_pool": ("every layer"
                          if self.cfg.count("attn") == self.cfg.n_layers
                          else "attn layers" if self.cfg.count("attn")
                          else "full-attention layers"),
            **({"window_ring_pages": stack.ring_pages(
                self.cfg, self.ecfg.page_size)}
               if self.cfg.count("window") else {}),
            **({"window_ring_pages": self._ring,
                "free_window_pages": free_window} if self._ring else {}),
            "free_pages": free_pages + prefix.get("reusable_pages", 0),
            **({"state_bytes": self._state_bytes,
                "state_room": self._state_room} if self.state else {}),
            **prefix,
            "steps": self._step_count,
            "startup_trace_id": self.startup_trace_id,
            "weights_version": self.weights_version,
            "tokens_per_decode_step": (
                self._tps_committed / self._tps_steps
                if self._tps_steps else 0.0),
            **spec,
        }

    def prefix_digest(self) -> Dict[str, Any]:
        """Compact prefix-cache fingerprint for router gossip: truncated
        chain hashes of every cached full prompt page. A router matches
        prompt_page_fingerprints(prompt, page_size) against this set to
        count warm leading pages per replica (prefix-aware role routing
        in serve/disagg.py)."""
        if self.prefix is None:
            return {"page_size": self.ecfg.page_size, "hashes": []}
        with self._alloc_lock:
            hashes = [h[:8].hex() for h in self.prefix.by_hash]
        return {"page_size": self.ecfg.page_size, "hashes": hashes}

    def stop(self):
        """The decode thread ends after the iteration it is in, with the
        span in flight read back and committed (`_loop`)."""
        self._stop.set()
        self._work.set()  # wake the decode thread so it observes _stop


def _kept(get, name: str):
    """What a program's list names: `get("x")`, or its i-th part ("x.i")."""
    name, _, part = name.partition(".")
    return get(name)[int(part)] if part else get(name)


def _join_carry(carry, slot, token, position):
    """The loop's carry with `slot`'s row set to a sequence's first token
    and the position it is written at."""
    tokens, positions = carry
    return tokens.at[slot].set(token), positions.at[slot].set(position)


def tree_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def _device_free_bytes(tree) -> Optional[int]:
    """What the device that holds `tree` has left (the fullest of them, for
    a sharded tree), or None where the backend keeps no count."""
    memory = profiler.device_memory(jax.tree.leaves(tree)[0].devices())
    return None if memory is None else memory[0] - memory[1]


def _kv_layer_groups(L: int, groups: int = 4) -> List[tuple]:
    """Near-even [l0, l1) layer slabs for layer-major KV framing. Four
    groups is the sweet spot measured on the bench box: enough to hide
    most of the device->host pull behind the wire, few enough that the
    per-frame overhead stays invisible. Models with fewer layers than
    groups degrade gracefully to one layer per slab."""
    G = max(1, min(int(L), int(groups)))
    base, rem = divmod(int(L), G)
    out, l0 = [], 0
    for gi in range(G):
        ln = base + (1 if gi < rem else 0)
        out.append((l0, l0 + ln))
        l0 += ln
    return out


# NOT donating the gather: the pools stay live for the decode loop. It
# compiles per distinct page count — fine for the (host-bound) migration
# path.
_gather_pages_jit = jax.jit(gather_pages, static_argnums=(3,))
_scatter_pages_jit = jax.jit(scatter_pages, donate_argnums=(0, 1))


def prompt_page_fingerprints(prompt, page_size: int) -> List[str]:
    """Router-side half of InferenceEngine.prefix_digest: the truncated
    chain-hash fingerprints of every full page of `prompt`, in the same
    wire format the digest advertises."""
    n = len(prompt) // page_size
    if n <= 0:
        return []
    return [h[:8].hex()
            for h in PrefixCache(page_size).page_hashes(prompt, n)]


def _normalize_stops(stop) -> Optional[List[List[int]]]:
    """Accept [[ids...]...] or the flat [id...] form (vLLM stop_token_ids,
    each id a stop on its own); reject anything else with a clear error
    instead of letting a bad shape reach the decode thread."""
    if stop is None:
        return None
    if not isinstance(stop, (list, tuple)):
        raise ValueError(f"stop must be a list, got {type(stop).__name__}")
    out: List[List[int]] = []
    for s in stop:
        if isinstance(s, (int, np.integer)):
            out.append([int(s)])
        elif isinstance(s, (list, tuple)) and s and all(
                isinstance(t, (int, np.integer)) for t in s):
            out.append([int(t) for t in s])
        else:
            raise ValueError(
                "stop entries must be token ids or non-empty token-id "
                f"lists, got {s!r}"
            )
    return out or None


def _match_stop(output: List[int],
                stops: Optional[List[List[int]]]) -> int:
    """Length of the stop sequence `output` currently ends with, or 0."""
    if not stops:
        return 0
    for s in stops:
        n = len(s)
        if n and len(output) >= n and output[-n:] == list(s):
            return n
    return 0


def _device_sample_topk_topp(logits, temps, top_ps, top_ks, key):
    """Per-row temperature + top-k + nucleus (top-p) sampling on device.
    top_k<=0 disables the rank cut; top_p>=1 disables the nucleus cut;
    temp<=0 is greedy. One descending sort serves both filters."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    order = jnp.argsort(-scaled, axis=-1)                      # [B,V] desc
    sorted_logits = jnp.take_along_axis(scaled, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    ranks = jnp.arange(logits.shape[-1])[None, :]
    # nucleus keeps every token whose preceding mass is under top_p (the
    # first token crossing the boundary stays in, matching vLLM)
    keep = (cum - probs) < top_ps[:, None]
    keep &= jnp.where(top_ks[:, None] > 0, ranks < top_ks[:, None], True)
    keep = keep.at[:, 0].set(True)  # never mask everything
    masked = jnp.where(keep, sorted_logits, -jnp.inf)
    choice = jax.random.categorical(key, masked, axis=-1)      # sorted index
    sampled = jnp.take_along_axis(order, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


def _sample_plain(logits, temps, key):
    """Per-row temperature sampling on device, no cut: temp<=0 is greedy."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


def _how_to_sample(temperature: float, top_p: float, top_k: int):
    """A request's sampling parameters as a chunk program takes them: one
    placement (a rank cut is exact in float32 wherever a token id is)."""
    return jnp.asarray(np.array([temperature, top_p, top_k], np.float32))


def _sample_first(logits, how, key):
    """A prompt's first token, drawn on the device from its last row's
    logits [V] as a decode step draws (`_device_sample_topk_topp` over a
    batch of one): `how` [3] float32 holds the request's temperature (<= 0:
    the argmax, and the sort never runs), top_p and top_k. -> the token
    (int32) and its log-probability under the RAW distribution, as the
    decode step reports it."""
    temp, top_p, top_k = how[0], how[1], how[2].astype(jnp.int32)
    tok = jax.lax.cond(
        temp > 0,
        lambda: _device_sample_topk_topp(
            logits[None], temp[None], top_p[None], top_k[None], key)[0],
        lambda: jnp.argmax(logits).astype(jnp.int32))
    return tok, jax.nn.log_softmax(logits)[tok]


def _host_logprob(logits: np.ndarray, tok: int) -> float:
    """log P(tok) under the raw (temperature-free) softmax of `logits` —
    the same quantity the decode program surfaces, so prefill-site and
    decode-site logprobs are directly comparable in one trajectory."""
    x = np.asarray(logits, np.float64)
    m = float(x.max())
    return float(x[tok] - m - np.log(np.exp(x - m).sum()))


def _sample_host(logits: np.ndarray, temperature: float,
                 top_p: float = 1.0, top_k: int = 0) -> int:
    if temperature <= 0:
        return int(np.argmax(logits))
    logits = logits / temperature
    logits -= logits.max()
    p = np.exp(logits)
    p /= p.sum()
    if top_k > 0 or top_p < 1.0:
        order = np.argsort(-p)
        sp = p[order]
        cum = np.cumsum(sp)
        keep = (cum - sp) < top_p
        if top_k > 0:
            keep &= np.arange(len(sp)) < top_k
        keep[0] = True
        sp = np.where(keep, sp, 0.0)
        sp /= sp.sum()
        return int(order[np.random.choice(len(sp), p=sp)])
    return int(np.random.choice(len(p), p=p))
