"""Prefill tokens of other prompts (padded, as the programs compute them)
that a decoded token waited behind: `serve_decode_interleaved_prefill_tokens`
(at each span, live slots x the prefill tokens dispatched since the last
span) over the decode tokens committed in the window and its drain."""

from benchmark import token_ledger


def read(ctx):
    return token_ledger.per_decode_token(
        ctx, "serve_decode_interleaved_prefill_tokens")
