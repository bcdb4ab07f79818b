"""Wall time of a decode step that shared the device's queue with prefill:
`serve_decode_span_seconds{prefill="1"}` over
`serve_decode_span_steps{prefill="1"}` (this iteration dispatched a chunk,
or the prefill thread a bucket program since the last span). Minus
`decode_step_wall_ms.clean` it is what prefill between spans costs a step."""

from benchmark import token_ledger


def read(ctx):
    return token_ledger.step_wall_ms(ctx, "1")
