"""Milliseconds of the mean decode token that passed in host code of the
decode thread: `serve_token_wait_seconds` parts `host` (install,
cancel_check, build, commit), `dispatch`, `chunk_host` (another prompt's
chunk less its readback) and `loop` (between phases and iterations), each
second weighed by the sequences that waited through it, over the decode
tokens committed in the window and its drain."""

from benchmark import token_ledger


def read(ctx):
    return token_ledger.wait_ms(ctx, token_ledger.HOST)
