"""Milliseconds of the mean decode token that the decode thread spent
blocked on the device: `serve_token_wait_seconds` parts `device_wait`
(`engine.readback`, a span's results) and `chunk_device_wait`
(`engine.chunk.readback`, the logits of another prompt's last chunk), each
second weighed by the sequences that waited through it, over the decode
tokens committed from the window's start to the end of its drain."""

from benchmark import token_ledger


def read(ctx):
    return token_ledger.wait_ms(ctx, token_ledger.DEVICE_WAIT)
