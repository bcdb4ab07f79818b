"""What the engine's ledger does not name of the client's mean gap between
tokens: `tpot_mean_ms` recomputed from the load generator's records, less
`tpot_device_wait_ms`, `tpot_host_ms` and `tpot_ready_ms`. In it are the
front's streaming, steps decoded past a sequence's end (the client's
denominator has tokens the engine did not count, and the reverse), and
anything the ledger misses."""

from benchmark import token_ledger


def read(ctx):
    parts = [token_ledger.client_tpot_mean_ms(ctx),
             token_ledger.wait_ms(ctx, token_ledger.DEVICE_WAIT),
             token_ledger.wait_ms(ctx, token_ledger.HOST),
             token_ledger.ready_ms(ctx)]
    if None in parts:
        return None
    return parts[0] - sum(parts[1:])
