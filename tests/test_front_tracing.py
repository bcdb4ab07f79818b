"""The serve front's two legs, measured inside the program: the proxy stamps
the receipt of a POST into the payload, the engine observes
`serve_front_seconds{leg="inbound"}` at add_request, and the proxy observes
`{leg="outbound"}` when a stream's first chunk is on the wire."""

import json
import urllib.request

import pytest

from ray_tpu import serve
from ray_tpu.core.metrics import registry
from ray_tpu.util import tracing


@pytest.fixture
def serve_session(ray_start_regular):
    yield
    serve.shutdown()


def _front(leg):
    h = registry.get("serve_front_seconds")
    return h.count({"leg": leg}), h.sum({"leg": leg})


def test_both_legs_are_observed_once_a_request(serve_session):
    app = serve.LLMServer.options(name="llm-front").bind(
        model_name="tiny-llama",
        engine_config=dict(max_batch_size=2, page_size=8, max_pages=32,
                           max_seq_len=64, prefill_buckets=(16,)))
    handle = serve.run(app, name="front")
    handle.options("stats").remote({}).result(timeout=300)
    url = f"http://127.0.0.1:{serve.http_port()}/front"
    body = {"prompt_ids": [1, 2, 3], "max_tokens": 4, "request_id": "r-1"}
    inbound0, outbound0 = _front("inbound"), _front("outbound")
    t0 = tracing.now_ns()

    def post(path, payload):
        req = urllib.request.Request(
            url + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            return resp.read().decode()

    plain = json.loads(post("", body))["result"]
    assert len(plain["token_ids"]) == 4
    assert _front("inbound")[0] == inbound0[0] + 1
    assert _front("outbound") == outbound0  # no stream, no outbound leg
    events = [line[6:] for line in post(
        "/stream", dict(body, request_id="r-2")).splitlines()
        if line.startswith("data: ")]
    assert [json.loads(e) for e in events[:-1]] == plain["token_ids"]
    assert events[-1] == "[DONE]"
    elapsed = (tracing.now_ns() - t0) * 1e-9
    for leg, before in (("inbound", inbound0), ("outbound", outbound0)):
        count, total = _front(leg)
        assert count == before[0] + (2 if leg == "inbound" else 1)
        assert 0 < total - before[1] < elapsed  # one clock on both sides
    # a handle call that never met the proxy has no receipt to count from
    handle.remote({"prompt_ids": [1, 2, 3], "max_tokens": 2}).result(
        timeout=300)
    assert _front("inbound")[0] == inbound0[0] + 2
