"""Milliseconds of the mean decode token that a sequence waited between
its first token and its decode slot: the sum of
`serve_request_stage_seconds{stage="ready"}` over the decode tokens
committed in the window and its drain."""

from benchmark import token_ledger


def read(ctx):
    return token_ledger.ready_ms(ctx)
