"""Wall time of a decode step with the device's queue to itself:
`serve_decode_span_seconds{prefill="0"}` (dispatch + readback of the spans
before which no prefill program went out since the last span) over
`serve_decode_span_steps{prefill="0"}`, all occupancies, over the window and
its drain."""

from benchmark import token_ledger


def read(ctx):
    return token_ledger.step_wall_ms(ctx, "0")
