"""Mean time from a request's first prefill dispatch to its first token,
per first token: `serve_request_stage_seconds{stage="prefill"}`. For a
chunked prompt the decode spans that run between its chunks are inside."""

from benchmark import program_spans


def read(ctx):
    return program_spans.stage_ms_per_first_token(ctx, ("prefill",))
