"""Mean time a request waited before its prefill began, per first token:
`serve_request_stage_seconds` sums of `pending` (queued for the prefill
thread), `waiting_for_pages` (parked, pool full) and `chunk_wait` (on the
chunk queue behind other prompts), over the first tokens counted in the
same window. With `request_prefill_ms` it adds up to the engine's mean
time to first token."""

from benchmark import program_spans


def read(ctx):
    return program_spans.stage_ms_per_first_token(
        ctx, ("pending", "waiting_for_pages", "chunk_wait"))
