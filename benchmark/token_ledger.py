"""The engine's token ledger, as the readers under benchmark/metrics/ share
it: where the time between a sequence's tokens went, from counters the
engine adds to at the end of every iteration of its decode loop
(`ray_tpu/serve/engine.py` `_account`), over the window and its drain.

`serve_token_wait_seconds{part}` holds seconds weighed by the sequences
that waited: its parts tile the decode thread's time while sequences are
live, so their sum is what `serve_request_stage_seconds{stage="decode"}`
sums for the same requests. Over the decode tokens committed in the same
window they are milliseconds of the mean token. A tree from before the
ledger has none of these series: every reader here then returns None."""

from __future__ import annotations

from typing import Iterable, Optional

from . import common, program_spans

DEVICE_WAIT = ("device_wait", "chunk_device_wait")
HOST = ("host", "dispatch", "chunk_host", "loop")


def _has(ctx, name: str) -> bool:
    """Whether the window's closing snapshot holds a sample of `name`."""
    return bool(ctx.get("counters")) and any(
        n == name for n, _tags in ctx["counters"][1])


def decode_tokens(ctx) -> float:
    """Decode tokens committed in the window: every token the engine
    emitted less the first tokens, which the prefill programs sample
    (`_note_first_token` observes `serve_ttft_seconds` once for each)."""
    before, after = ctx["counters"]
    return common.counter_delta(before, after, "serve_tokens_generated") \
        - common.counter_delta(before, after, "serve_ttft_seconds_count")


def per_decode_token(ctx, name: str, **tags: str) -> Optional[float]:
    """The window's `name{tags}` over its decode tokens."""
    if not _has(ctx, name) or decode_tokens(ctx) <= 0:
        return None
    return common.counter_delta(*ctx["counters"], name, **tags) \
        / decode_tokens(ctx)


def wait_ms(ctx, parts: Iterable[str]) -> Optional[float]:
    """Milliseconds of the mean decode token spent in `parts` of
    `serve_token_wait_seconds`."""
    each = [per_decode_token(ctx, "serve_token_wait_seconds", part=p)
            for p in parts]
    return None if None in each else 1000.0 * sum(each)


def ready_ms(ctx) -> Optional[float]:
    """First token -> decode slot, per decode token."""
    seconds = per_decode_token(ctx, "serve_request_stage_seconds_sum",
                               stage="ready")
    return None if seconds is None else 1000.0 * seconds


def client_tpot_mean_ms(ctx) -> Optional[float]:
    """`tpot_mean_ms` of the requests that completed, from the load
    generator's records: every gap between two tokens of one request."""
    streamed = [r for r in ctx["run"].get("records", [])
                if r["ok"] and r["tokens"] > 1]
    gaps = sum(r["tokens"] - 1 for r in streamed)
    if not gaps:
        return None
    return 1000.0 * sum(r["last_s"] - r["first_s"] for r in streamed) / gaps


def step_wall_ms(ctx, prefill: str) -> Optional[float]:
    """Wall time of a decode step (dispatch + readback of its span over the
    span's steps), over the spans that shared the device's queue with
    prefill programs (`prefill="1"`) or did not (`"0"`), all occupancies."""
    name = "serve_decode_span_steps"
    if not _has(ctx, name):
        return None
    steps = common.counter_delta(*ctx["counters"], name, prefill=prefill)
    if not steps:
        return None
    return 1000.0 * common.counter_delta(
        *ctx["counters"], "serve_decode_span_seconds", prefill=prefill) / steps


def traced_live_slots(ctx) -> Optional[float]:
    """Mean of the `live` attribute over the trace's `engine.dispatch`
    regions, each weighed by its `steps`."""
    spans = program_spans.read(ctx["cell"]["name"])
    attrs = [r.attrs for r in spans.named("engine.dispatch")
             if "live" in r.attrs and "steps" in r.attrs] if spans else []
    steps = sum(float(a["steps"]) for a in attrs)
    if not steps:
        return None
    return sum(float(a["live"]) * float(a["steps"]) for a in attrs) / steps
