"""Share of the KV pages the engine holds for requests that hold at least
one cached token: `serve_kv_page_steps` written over reserved, summed once
an engine iteration. A request reserves prompt + max_tokens at admission
and fills them token by token."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    before, after = ctx["counters"]
    reserved = common.counter_delta(before, after, "serve_kv_page_steps",
                                    state="reserved")
    written = common.counter_delta(before, after, "serve_kv_page_steps",
                                   state="written")
    if not reserved:
        return None
    return 100.0 * written / reserved
