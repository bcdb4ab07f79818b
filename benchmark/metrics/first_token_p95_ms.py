"""The tail of the time to the first token, as the client saw it: the
window's nearest-rank p95 over the requests due in it of first streamed
token minus the instant the request was DUE (`serve_driver.summarize`; a
failed request counts as window + drain cap). Until PR 27 this was the
end-to-end metric `ttft_p95_ms` of the Mixtral cell; the driver's check
read its sets 9.7% and 2.7% wide there, too wide for any bound the
contract allows, so it is reported here, unjudged (PERF.md section 2)."""


def read(ctx):
    return ctx["run"].get("ttft_p95_ms")
