"""Speculative decoding for the serving engine: propose-k, verify-once.

A decode step normally yields one token per sequence per forward. Here a
cheap PROPOSER guesses k continuation tokens per slot, and ONE batched
verify forward scores all k+1 positions against the paged KV cache
(ops.paged_attention_verify — the decode kernel widened to a span). The
longest accepted draft prefix commits, plus one "bonus" token sampled
from the first non-accepted position, so every step commits between 1
and k+1 tokens and never fewer than the plain path. On TPU the verify
forward costs barely more than a single decode step, so accepted drafts
are nearly free throughput.

Correctness contract (the greedy-equivalence test pins it): both
proposers are DETERMINISTIC (point-mass proposals), which makes exact
rejection sampling simple —

- greedy rows (temp<=0): draft d at row s accepts iff
  argmax(verify_logits[s]) == d, and the bonus is that argmax, so the
  committed stream is bit-identical to speculation-off greedy decode.
- sampling rows (temp>0): d accepts with probability p(d) under the
  temperature/top-k/top-p-filtered verify distribution; on rejection the
  bonus is drawn from that distribution with d zeroed out and
  renormalized. For a point-mass proposal this is exactly Leviathan-style
  speculative sampling: the output distribution equals the target's.

Two proposers behind one duck-typed interface
(on_install/on_evict/propose/warmup):

- NGramProposer: suffix-match lookup over the request's own
  prompt+output (vLLM's ngram mode) — no extra model, wins on
  repetitive/extractive continuations. The lookup is VECTORIZED across
  the whole continuous batch (one sliding-window pass per suffix length
  over a persistent [B, max_seq_len] context buffer maintained
  incrementally per slot), so propose costs microseconds instead of a
  per-request Python loop. When NO slot has a draft, run_step signals
  the engine to fall back to a plain decode span for that iteration —
  the spec engine is never slower than the plain engine by more than
  the lookup.
- DraftModelProposer: a small transformer from models/ sharing the
  tokenizer, with its OWN paged KV pool mirroring each slot's positions
  (fixed per-slot page runs — no allocator). Prompts chunk-prefill into
  the draft pool at install; each step runs k greedy draft-decode steps
  in one jitted scan, preceded by a catch-up write for the token at
  position-1 (on a fully-accepted round the last draft token was never
  fed, leaving a KV hole that silently degraded acceptance). With
  spec_overlap (the default), the NEXT round's propose scan is
  dispatched at the end of run_step — right after the commit readback —
  so the draft forward overlaps the engine's host-side commit loop and
  bookkeeping instead of serializing in front of verify. Per-slot
  (request_id, position) stamps invalidate a prefetched row whenever
  the slot was evicted, reused, or cancelled in between: a stale row
  simply proposes nothing (n_draft=0 commits exactly the plain token).

KV bookkeeping: the verify forward writes span KV at positions
p..p+n_draft per slot (rows past a slot's draft count are routed to the
trash page). After committing a drafts + bonus, the slot advances a+1;
the bonus token's KV is written by the NEXT round's row 0, and
stale rejected-draft KV above the new position is invisible (attention
is position-bounded) until overwritten.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import config
from ..core.logging import get_logger
from ..core.metrics import Gauge
from ..models import get_config, init_params, stack
from ..models.transformer import _head_logits
from ..util import tracing
from ..ops import pool_shape
from .config import SpeculationConfig
from .program import Arg, Program

logger = get_logger("serve.spec_decode")

_m_spec_accept_rate = Gauge(
    "serve_spec_acceptance_rate",
    "Cumulative accepted/proposed draft-token ratio.")


# ---------------------------------------------------------------------------
# Device-side accept + commit
# ---------------------------------------------------------------------------


def _topk_topp_keep(scaled, top_ps, top_ks):
    """Per-row keep mask in TOKEN space for the temperature-scaled logits,
    matching engine._device_sample_topk_topp's sorted-domain semantics
    (first token crossing the nucleus boundary stays; top-1 always kept)."""
    order = jnp.argsort(-scaled, axis=-1)
    sorted_logits = jnp.take_along_axis(scaled, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    ranks = jnp.arange(scaled.shape[-1])[None, :]
    keep = (cum - probs) < top_ps[:, None]
    keep &= jnp.where(top_ks[:, None] > 0, ranks < top_ks[:, None], True)
    keep = keep.at[:, 0].set(True)
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(keep, inv, axis=-1)


def _accept_commit(logits, tokens, n_draft, temps, top_ps, top_ks, key,
                   advanced):
    """logits [B,S,V] f32 (verify forward, row s scores position p+s+1);
    tokens [B,S] = [last committed, d_1..d_K]; n_draft [B] valid drafts.
    -> (committed [B,S] int32, n_committed [B] int32). Columns past
    n_committed are padding the host ignores."""
    B, S, V = logits.shape
    K = S - 1
    greedy = jnp.argmax(logits, axis=-1)  # [B,S] == plain greedy decode
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None, None]
    if advanced:
        flat = scaled.reshape(B * S, V)
        keep = _topk_topp_keep(
            flat, jnp.repeat(top_ps, S), jnp.repeat(top_ks, S))
        scaled = jnp.where(keep, flat, -jnp.inf).reshape(B, S, V)
    probs = jax.nn.softmax(scaled, axis=-1)
    drafts = tokens[:, 1:]  # [B,K]
    p_draft = jnp.take_along_axis(
        probs[:, :K], drafts[:, :, None], axis=-1)[..., 0]
    key_u, key_b = jax.random.split(key)
    u = jax.random.uniform(key_u, (B, K))
    # point-mass proposal (q(d)=1): accept w.p. min(1, p(d)/q(d)) = p(d)
    ok = jnp.where(temps[:, None] > 0, u < p_draft, greedy[:, :K] == drafts)
    ok &= jnp.arange(K)[None, :] < n_draft[:, None]
    a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)  # [B]
    # bonus from row a: greedy rows reuse the raw-logit argmax (exact
    # equality with the plain path); sampling rows draw from the residual
    # (filtered distribution with the rejected draft zeroed out)
    row_a = jnp.take_along_axis(scaled, a[:, None, None], axis=1)[:, 0]
    rejected = a < n_draft
    rej_tok = jnp.take_along_axis(
        drafts, jnp.minimum(a, K - 1)[:, None], axis=1)[:, 0]
    resid = jnp.where(
        rejected[:, None] & (jnp.arange(V)[None, :] == rej_tok[:, None]),
        -jnp.inf, row_a)
    sampled = jax.random.categorical(key_b, resid, axis=-1)
    greedy_bonus = jnp.take_along_axis(greedy, a[:, None], axis=1)[:, 0]
    bonus = jnp.where(temps > 0, sampled, greedy_bonus).astype(jnp.int32)
    cols = jnp.arange(S)[None, :]
    drafts_pad = jnp.pad(drafts, ((0, 0), (0, 1)))
    committed = jnp.where(
        cols < a[:, None], drafts_pad,
        jnp.where(cols == a[:, None], bonus[:, None], 0))
    return committed.astype(jnp.int32), (a + 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Proposers
# ---------------------------------------------------------------------------


def _ngram_lookup(ctx: np.ndarray, nmin: int, nmax: int, k: int) -> np.ndarray:
    """Longest suffix of length in [nmin, nmax] matched against earlier
    context; the continuation after the MOST RECENT match is the draft."""
    T = int(ctx.shape[0])
    for n in range(min(nmax, T - 1), nmin - 1, -1):
        suffix = ctx[T - n:]
        win = np.lib.stride_tricks.sliding_window_view(ctx[:T - 1], n)
        hits = np.flatnonzero((win == suffix).all(axis=1))
        if hits.size:
            j = int(hits[-1])
            return ctx[j + n: j + n + k]
    return np.empty((0,), np.int32)


def _batch_ngram_lookup(ctx: np.ndarray, lens: np.ndarray,
                        active: np.ndarray, nmin: int, nmax: int, k: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """`_ngram_lookup` for the whole batch: one sliding-window pass per
    suffix length n (at most nmax-nmin+1 passes, each a single vectorized
    comparison over [rows, windows, n]) instead of a per-request Python
    loop. Row semantics are identical to `_ngram_lookup(ctx[i, :lens[i]])`:
    longest suffix length wins, most recent match wins, continuation
    truncated at the row's real length."""
    B = ctx.shape[0]
    drafts = np.zeros((B, k), np.int32)
    n_out = np.zeros((B,), np.int32)
    unresolved = active.copy()
    for n in range(nmax, nmin - 1, -1):
        rows = np.flatnonzero(unresolved & (lens >= n + 1))
        if rows.size == 0:
            continue
        sub = ctx[rows]
        L = lens[rows].astype(np.int64)
        idx = (L[:, None] - n) + np.arange(n)[None, :]
        suffix = np.take_along_axis(sub, idx, axis=1)
        win = np.lib.stride_tricks.sliding_window_view(sub, n, axis=1)
        hit = (win == suffix[:, None, :]).all(axis=2)
        # window j matches real context only if a continuation exists
        # inside the row's live tokens: j + n < L (window fully inside
        # ctx[:L-1], exactly the scalar lookup's search range)
        hit &= (np.arange(hit.shape[1])[None, :] + n) < L[:, None]
        got = hit.any(axis=1)
        if not got.any():
            continue
        last_j = hit.shape[1] - 1 - np.argmax(hit[:, ::-1], axis=1)
        for ri in np.flatnonzero(got):
            r = int(rows[ri])
            j = int(last_j[ri])
            m = min(k, int(L[ri]) - (j + n))
            drafts[r, :m] = ctx[r, j + n: j + n + m]
            n_out[r] = m
            unresolved[r] = False
    return drafts, n_out


class NGramProposer:
    """Draft tokens from the request's own prompt+output (no model).

    Keeps a persistent [B, max_seq_len] context buffer mirroring each
    slot's prompt+output, appended incrementally per step (only the new
    committed tokens copy), and runs ONE vectorized suffix lookup across
    the batch. A request_id stamp per row means a reused slot can never
    see its predecessor's context."""

    name = "ngram"
    cheap = True  # host-side: a zero-draft round should fall back to plain

    def __init__(self, spec: SpeculationConfig):
        self.k = spec.num_speculative_tokens
        self.nmin = spec.ngram_min
        self.nmax = spec.ngram_max
        self._ctx: Optional[np.ndarray] = None  # [B, max_seq_len] int32
        self._len: Optional[np.ndarray] = None  # [B] live tokens per row
        self._rid: list = []

    def _ensure(self, engine) -> None:
        if self._ctx is None:
            B = engine.ecfg.max_batch_size
            self._ctx = np.zeros((B, engine.ecfg.max_seq_len), np.int32)
            self._len = np.zeros((B,), np.int64)
            self._rid = [None] * B

    def on_install(self, engine, slot_idx: int, request) -> None:
        self._ensure(engine)
        seq = request.prompt + request.output
        m = min(len(seq), self._ctx.shape[1])
        self._ctx[slot_idx, :m] = seq[:m]
        self._len[slot_idx] = m
        self._rid[slot_idx] = request.request_id

    def on_evict(self, engine, slot_idx: int) -> None:
        if self._ctx is not None:
            self._len[slot_idx] = 0
            self._rid[slot_idx] = None

    def warmup(self, engine) -> None:
        pass

    def propose(self, engine, tokens, positions
                ) -> Tuple[np.ndarray, np.ndarray]:
        self._ensure(engine)
        B = engine.ecfg.max_batch_size
        active = np.zeros((B,), bool)
        cap = self._ctx.shape[1]
        for i, s in enumerate(engine.slots):
            req = s.request
            if req is None:
                continue
            if self._rid[i] != req.request_id:
                self.on_install(engine, i, req)
            else:
                P = len(req.prompt)
                total = min(P + len(req.output), cap)
                have = int(self._len[i])
                if total > have:
                    self._ctx[i, have:total] = req.output[have - P: total - P]
                    self._len[i] = total
            active[i] = True
        return _batch_ngram_lookup(self._ctx, self._len, active,
                                   self.nmin, self.nmax, self.k)


class DraftModelProposer:
    """Draft tokens from a small transformer with its own paged KV pool.

    The draft pool mirrors the target's position bookkeeping exactly
    (draft position == slot.position at every propose), with FIXED
    per-slot page runs — pages_per_seq plus a small spill margin so the
    k-step lookahead near max_seq_len never writes into a neighbour's
    pages. Prompts chunk-prefill into the pool at install time; per step
    one jitted scan runs k greedy draft-decode steps for the whole batch.
    """

    name = "draft"
    cheap = False  # zero-draft rounds keep current behavior (verify span)
    supports_prefetch = True

    def __init__(self, engine, spec: SpeculationConfig, draft_params=None):
        import dataclasses as _dc

        # next-round propose dispatched at the end of run_step (overlap
        # mode): {"drafts" device [B,K], "pos" np [B], "rids" list} —
        # consumed (or discarded on any per-row stamp mismatch) by the
        # next take_prefetch
        self._pf: Optional[Dict[str, Any]] = None

        self.k = spec.num_speculative_tokens
        ecfg = engine.ecfg
        if spec.draft_model is None:
            # self-speculation: share the target's weights. Acceptance is
            # ~1.0 by construction — an upper-bound plumbing smoke, not a
            # deployment config (name a real small model for that).
            self.cfg = engine.cfg
            self.params = engine.params
        else:
            self.cfg = get_config(
                spec.draft_model, **dict(spec.draft_model_overrides or {}))
            if self.cfg.vocab_size != engine.cfg.vocab_size:
                raise ValueError(
                    "draft model must share the target tokenizer: vocab "
                    f"{self.cfg.vocab_size} != {engine.cfg.vocab_size}")
            if self.cfg.max_seq_len < ecfg.max_seq_len:
                self.cfg = _dc.replace(
                    self.cfg, max_seq_len=ecfg.max_seq_len)
            self.params = (draft_params if draft_params is not None
                           else init_params(self.cfg, jax.random.PRNGKey(0)))
        B = ecfg.max_batch_size
        ps = ecfg.page_size
        self.ps = ps
        self.chunk = ecfg.prefill_chunk
        # spill pages: propose positions reach max_seq_len - 1 + k
        self.pps = ecfg.pages_per_seq + (-(-self.k // ps))
        # table length additionally covers padded chunk rows at install
        # (entries past the real run are 0 — the draft pool's trash page)
        tbl_len = max(self.pps, -(-(ecfg.max_seq_len + self.chunk) // ps))
        tables = np.zeros((B, tbl_len), np.int32)
        for i in range(B):
            tables[i, : self.pps] = 1 + i * self.pps + np.arange(self.pps)
        self._tables = jnp.asarray(tables)
        pool = pool_shape(self.cfg.n_layers, 1 + B * self.pps, ps,
                          self.cfg.kv_heads, self.cfg.hdim)
        dtype = jnp.dtype(ecfg.cache_dtype)
        self.k_pages = jnp.zeros(pool, dtype)
        self.v_pages = jnp.zeros(pool, dtype)
        self._chunk_fn = self._build_chunk()
        self._propose_fn = self._build_propose()

    # -------------------------------------------------------- compiled

    def _build_chunk(self):
        """Draft-prompt prefill: the engine's chunk program minus the LM
        head (only the KV writes matter)."""
        cfg = self.cfg
        ps = self.ps

        def chunk_step(params, k_pages, v_pages, tokens, start, page_table):
            _, new_k, new_v, _ = stack.run_paged(
                params, tokens[None, :], cfg,
                stack.Seq(cfg, chunk=(start, page_table), page_size=ps),
                (k_pages, v_pages))
            return new_k, new_v

        cache: Dict[int, Any] = {}

        def for_chunk(C: int):
            if C not in cache:
                cache[C] = jax.jit(chunk_step, donate_argnums=(1, 2))
            return cache[C]

        return for_chunk

    def _build_propose(self):
        """k greedy decode steps over the draft pool in one jitted scan."""
        cfg = self.cfg
        ps = self.ps
        K = self.k

        def one_step(params, k_pages, v_pages, tokens, positions,
                     page_tables):
            x, new_k, new_v, _ = stack.run_paged(
                params, tokens[:, None], cfg,
                stack.Decode(cfg, positions, page_tables, ps),
                (k_pages, v_pages))
            logits = _head_logits(x, lambda x: x[:, 0], params, cfg,
                                  "bd,dv->bv")
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_k, new_v

        def propose(params, k_pages, v_pages, prev_tokens, tokens, positions,
                    page_tables):
            # catch-up: on a fully-accepted round the token now at
            # position-1 (the last draft) was never FED to the draft
            # model, so its KV is a hole that poisons every later step's
            # attention. One extra decode step writes it; when the hole
            # doesn't exist this rewrites identical KV (idempotent), and
            # XLA prunes the unused logits head. Inactive rows clamp to
            # position 0 (their writes land in the slot's own pages at
            # positions no live request can see before on_install
            # rebuilds them).
            _, k_pages, v_pages = one_step(
                params, k_pages, v_pages, prev_tokens,
                jnp.maximum(positions - 1, 0), page_tables)

            def sub(carry, _):
                toks, pos, kp, vp = carry
                nxt, kp, vp = one_step(params, kp, vp, toks, pos, page_tables)
                return (nxt, pos + 1, kp, vp), nxt

            (_, _, kp, vp), seq = jax.lax.scan(
                sub, (tokens, positions, k_pages, v_pages), None, length=K)
            return seq.T, kp, vp  # [B,K]

        return jax.jit(propose, donate_argnums=(1, 2))

    # -------------------------------------------------------- interface

    def on_install(self, engine, slot_idx: int, request) -> None:
        """Chunk-prefill the prompt into the slot's draft pages (the
        target's pages may have come from the prefix cache or chunked
        prefill — the draft pool always rebuilds from the tokens)."""
        T = len(request.prompt)
        C = self.chunk
        table = self._tables[slot_idx]
        for c0 in range(0, T, C):
            toks = request.prompt[c0:c0 + C]
            padded = np.zeros((C,), np.int32)
            padded[: len(toks)] = toks
            self.k_pages, self.v_pages = self._chunk_fn(C)(
                self.params, self.k_pages, self.v_pages,
                jnp.asarray(padded), jnp.int32(c0), table)

    def on_evict(self, engine, slot_idx: int) -> None:
        # a prefetched row computed for the evicted request must never
        # surface for the slot's next occupant
        if self._pf is not None:
            self._pf["rids"][slot_idx] = None

    def warmup(self, engine) -> None:
        B = engine.ecfg.max_batch_size
        C = self.chunk
        with tracing.region("engine.warmup.program",
                            program="draft.chunk_step", rows=C):
            self.k_pages, self.v_pages = jax.block_until_ready(
                self._chunk_fn(C)(
                    self.params, self.k_pages, self.v_pages,
                    jnp.zeros((C,), jnp.int32), jnp.int32(0),
                    self._tables[0]))
        with tracing.region("engine.warmup.program",
                            program="draft.propose", steps=self.k):
            drafts, self.k_pages, self.v_pages = self._propose_fn(
                self.params, self.k_pages, self.v_pages,
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32), self._tables)
            np.asarray(drafts)

    def _prev_tokens(self, engine, tokens) -> np.ndarray:
        """The token at position-1 per slot (catch-up feed)."""
        prev = np.asarray(tokens, np.int32).copy()
        for i, s in enumerate(engine.slots):
            req = s.request
            if req is None:
                continue
            if len(req.output) >= 2:
                prev[i] = req.output[-2]
            elif req.prompt:
                prev[i] = req.prompt[-1]
        return prev

    def propose(self, engine, tokens, positions
                ) -> Tuple[jax.Array, np.ndarray]:
        prev = self._prev_tokens(engine, tokens)
        drafts, self.k_pages, self.v_pages = self._propose_fn(
            self.params, self.k_pages, self.v_pages, jnp.asarray(prev),
            jnp.asarray(tokens), jnp.asarray(positions), self._tables)
        n = np.full((engine.ecfg.max_batch_size,), self.k, np.int32)
        return drafts, n  # drafts stay on device: verify concats there

    def prefetch(self, engine, tokens, positions, committed, n_comm) -> None:
        """Dispatch the NEXT round's propose right after this round's
        commit readback: the inputs (next fed token, next position, the
        catch-up token) are pure functions of the committed tokens, so
        the draft forward runs on device while the engine does its
        host-side commit loop. Stamped per row with (request_id,
        position); take_prefetch drops any row whose stamp no longer
        matches."""
        B = engine.ecfg.max_batch_size
        rows = np.arange(B)
        nc = np.asarray(n_comm, np.int64)
        tokens = np.asarray(tokens, np.int32)
        last = committed[rows, np.maximum(nc - 1, 0)]
        next_tok = np.where(nc > 0, last, tokens).astype(np.int32)
        prev_tok = np.where(
            nc >= 2, committed[rows, np.maximum(nc - 2, 0)],
            tokens).astype(np.int32)
        next_pos = (np.asarray(positions, np.int64) + nc).astype(np.int32)
        drafts, self.k_pages, self.v_pages = self._propose_fn(
            self.params, self.k_pages, self.v_pages, jnp.asarray(prev_tok),
            jnp.asarray(next_tok), jnp.asarray(next_pos), self._tables)
        rids = [s.request.request_id if s.request is not None else None
                for s in engine.slots]
        self._pf = {"drafts": drafts, "pos": next_pos, "rids": rids}

    def take_prefetch(self, engine, positions
                      ) -> Optional[Tuple[jax.Array, np.ndarray]]:
        pf, self._pf = self._pf, None
        if pf is None:
            return None
        B = engine.ecfg.max_batch_size
        n = np.zeros((B,), np.int32)
        for i, s in enumerate(engine.slots):
            req = s.request
            if (req is not None and pf["rids"][i] == req.request_id
                    and int(pf["pos"][i]) == int(positions[i])):
                n[i] = self.k
        return pf["drafts"], n


# ---------------------------------------------------------------------------
# The decoder
# ---------------------------------------------------------------------------


class SpecDecoder:
    """Owns the proposer, the jitted verify forward (accept/commit on
    device — the readback is [B,S] committed tokens + [B] counts), and
    the acceptance accounting. The engine drives it from step()."""

    def __init__(self, engine, spec: SpeculationConfig):
        """Its program and its counts, and no array yet (`start`): an
        engine that is described and never runs builds this much
        (`InferenceEngine.abstract`)."""
        self.engine = engine
        self.spec = spec
        self.k = spec.num_speculative_tokens
        self._verify = self._build_verify()
        self.proposed_total = 0
        self.accepted_total = 0

    def start(self, draft_params=None) -> None:
        """The proposer, which may hold a model and a pool of its own."""
        spec = self.spec
        if spec.mode == "ngram":
            self.proposer = NGramProposer(spec)
        elif spec.mode == "draft":
            self.proposer = DraftModelProposer(self.engine, spec, draft_params)
        else:
            raise ValueError(f"speculation mode {spec.mode!r} is not a "
                             "proposer mode")
        overlap = (spec.overlap if spec.overlap is not None
                   else bool(config.spec_overlap))
        self.overlap = overlap and getattr(
            self.proposer, "supports_prefetch", False)

    def _build_verify(self):
        """Jit the span forward: embed the S=k+1 fed tokens, write their
        KV at positions p..p+n_draft (rows past a slot's draft count go
        to the trash page), attend with the span kernel, and run
        accept/commit on device."""
        eng = self.engine
        cfg = eng.cfg
        ps = eng.ecfg.page_size

        def verify(params, k_pages, v_pages, tokens, positions, page_tables,
                   n_draft, temps, top_ps, top_ks, key, advanced=False):
            """tokens [B,S]; positions/n_draft/temps/... [B]. S is taken
            from the tokens shape: run_step narrows the span to the
            round's max draft count + 1 (the jit cache re-specializes per
            width), so a round where every slot drafted short never pays
            the full k+1-wide forward."""
            x, new_k, new_v, _ = stack.run_paged(
                params, tokens, cfg,
                stack.Verify(cfg, positions, page_tables, ps, n_draft,
                             eng.mesh),
                (k_pages, v_pages))
            logits = _head_logits(x, lambda x: x, params, cfg, "bsd,dv->bsv")
            committed, n_comm = _accept_commit(
                logits, tokens, n_draft, temps, top_ps, top_ks, key,
                advanced)
            return committed, n_comm, new_k, new_v

        cache: Dict[bool, Any] = {}

        def for_mode(advanced: bool):
            if advanced not in cache:
                cache[advanced] = eng._under_mesh(jax.jit(
                    tracing.named(
                        functools.partial(verify, advanced=advanced),
                        f"verify_{self.k}" + ("_adv" if advanced else "")),
                    donate_argnums=(1, 2)))
            return cache[advanced]

        return for_mode

    # -------------------------------------------------------- engine API

    def on_install(self, slot_idx: int, request) -> None:
        self.proposer.on_install(self.engine, slot_idx, request)

    def on_evict(self, slot_idx: int) -> None:
        ev = getattr(self.proposer, "on_evict", None)
        if ev is not None:
            ev(self.engine, slot_idx)

    def _programs(self):
        """The round's programs, one a sampler, as `InferenceEngine._programs`
        lists the engine's own: the widest span, k + 1 tokens a slot. (A
        draft proposer's two programs take the draft's own pool and stay in
        its `warmup`.)"""
        eng = self.engine
        B, pps, S = eng.ecfg.max_batch_size, eng.ecfg.pages_per_seq, self.k + 1
        i32, f32 = jnp.int32, jnp.float32
        for advanced in (False, True):
            yield Program.under(
                eng.mesh, f"verify_{self.k}" + ("_adv" if advanced else ""),
                self._verify(advanced),
                ("params", "k_pages", "v_pages",
                 Arg((B, S), i32), Arg((B,), i32),  # tokens, positions
                 Arg((B, pps), i32), Arg((B,), i32),  # tables, n_draft
                 # temperatures, top_p, top_k, the key
                 Arg((B,), f32), Arg((B,), f32, 1), Arg((B,), i32),
                 Arg((2,), jnp.uint32)),
                back=(None, None, "k_pages", "v_pages"), rows=B * S)

    # verify cost model: one S-wide forward ~ ALPHA + S in single-row
    # units (ALPHA covers dispatch + the fixed host share of a round).
    # Used by _pick_span to trade truncating the deepest rows' drafts
    # against running a narrower program for the whole batch.
    _SPAN_ALPHA = 1.0

    def _pick_span(self, n_draft, caps) -> int:
        """Choose how many draft rows the verify forward should carry.

        One slot with k drafts would force the full k+1-wide program on
        the whole batch even when every other slot drafted 0-1 tokens —
        and a draft only pays off while its acceptance holds up. Using
        the proposer's measured acceptance rate `a`, a row with d drafts
        verified at width w expects (a - a^(min(d,w)+1)) / (1-a) + 1
        committed tokens; pick the w maximizing expected commits per
        unit verify cost (ALPHA + w + 1). Rows deeper than w are simply
        truncated — their tail drafts were the least likely to commit."""
        m = int(n_draft.max())
        if m <= 1:
            return m
        a = (self.accepted_total / self.proposed_total
             if self.proposed_total >= 256 else 0.8)
        a = min(max(a, 0.05), 0.98)
        nd = n_draft[np.asarray(caps) > 0].astype(np.float64)
        best_w, best_v = m, -1.0
        for w in range(1, m + 1):
            run = np.minimum(nd, w)
            exp_commits = np.sum((a - a ** (run + 1)) / (1.0 - a) + 1.0)
            v = exp_commits / (self._SPAN_ALPHA + w + 1)
            if v > best_v:
                best_w, best_v = w, v
        return best_w

    def run_step(self, tokens, positions, tables, caps, temps, top_ps,
                 top_ks, advanced, key):
        """One speculative round over the built batch arrays. caps [B] is
        the per-slot draft cap (min of k, remaining budget - 1, sequence
        room; 0 for inactive slots). Returns committed [B,S] np,
        n_committed [B] np, n_draft [B] np, and per-phase wall times
        (propose split into the wait-on-prefetch and compute shares).

        Fallback: a CHEAP proposer (ngram) with zero drafts everywhere
        returns (None, None, n_draft, times) — the engine should run a
        plain decode span instead, which commits span tokens at plain
        cost where the S-wide verify would commit exactly one."""
        eng = self.engine
        decode_phase = eng.phase
        wait = compute = 0.0
        with decode_phase("propose_wait") as ph:
            pf = (self.proposer.take_prefetch(eng, positions)
                  if self.overlap else None)
        if pf is not None:
            drafts, n_prop = pf
            wait = ph.elapsed_s
        else:
            with decode_phase("propose") as ph:
                drafts, n_prop = self.proposer.propose(eng, tokens,
                                                       positions)
            compute = ph.elapsed_s
        n_draft = np.minimum(n_prop, caps).astype(np.int32)
        if getattr(self.proposer, "cheap", False) and not n_draft.any():
            return None, None, n_draft, {
                "propose_wait": wait, "propose_compute": compute,
                "propose": wait + compute}
        # adaptive span: the verify forward only needs max(n_draft)+1
        # rows — a round of short drafts runs a narrow program (at most k
        # compiled widths) instead of always paying the k+1-wide one.
        # Floor of 1 draft row: K=0 would make the accept op's rejected-
        # draft gather degenerate (an all-zero-cap round still verifies
        # one draft row it then ignores via n_draft=0)
        with decode_phase("build"):
            m = max(1, self._pick_span(n_draft, caps))
            n_draft = np.minimum(n_draft, m)
            if isinstance(drafts, np.ndarray):
                toks_bs = jnp.asarray(
                    np.concatenate([tokens[:, None], drafts[:, :m]], axis=1))
            else:
                toks_bs = jnp.concatenate(
                    [jnp.asarray(tokens)[:, None], drafts[:, :m]], axis=1)
        span = eng._open_span(1)
        with decode_phase("dispatch", **span.attrs) as verify:
            committed, n_comm, eng.k_pages, eng.v_pages = self._verify(
                advanced)(
                eng.params, eng.k_pages, eng.v_pages, toks_bs,
                jnp.asarray(positions), jnp.asarray(tables),
                jnp.asarray(n_draft), jnp.asarray(temps),
                jnp.asarray(top_ps), jnp.asarray(top_ks), key)
        span.dispatched_ns = verify.end_ns
        with decode_phase("readback") as readback:
            committed = np.asarray(committed)
            n_comm = np.asarray(n_comm)
        eng._span_read(span, readback)
        if self.overlap:
            # dispatch next round's propose NOW: it executes on device
            # while the engine runs its host-side commit loop
            with decode_phase("propose") as ph:
                self.proposer.prefetch(eng, tokens, positions, committed,
                                       n_comm)
            compute += ph.elapsed_s
        return committed, n_comm, n_draft, {
            "propose_wait": wait, "propose_compute": compute,
            "propose": wait + compute,
            "verify": verify.elapsed_s, "sample": readback.elapsed_s}

    def record(self, proposed: int, accepted: int) -> None:
        self.proposed_total += int(proposed)
        self.accepted_total += int(accepted)
        if self.proposed_total:
            _m_spec_accept_rate.set(
                self.accepted_total / self.proposed_total)

    def stats(self) -> Dict[str, Any]:
        return {
            "spec_mode": self.spec.mode,
            "spec_num_speculative_tokens": self.k,
            "spec_proposed_tokens": self.proposed_total,
            "spec_accepted_tokens": self.accepted_total,
            "spec_acceptance_rate": (
                self.accepted_total / self.proposed_total
                if self.proposed_total else 0.0),
        }
