"""OpenAI-compatible serving surface over the inference engine.

Reference analogue: `ray.serve.llm :: build_openai_app` (A4 in SURVEY.md
§2.3), which fronts vLLM with /v1/completions + /v1/chat/completions.
Here the app is one deployment whose methods map to proxy routes:

    app = build_openai_app(model_name=..., tokenizer="byte")
    serve.run(app, name="v1")
    # POST /v1/completions        {"prompt": "...", "max_tokens": 8}
    # POST /v1/chat_completions   {"messages": [{"role": "user", ...}]}
    # POST /v1/models
    # "stream": true -> server-sent events through the HTTP proxy

Tokenizers: "byte" (utf-8 bytes, zero deps — any model with vocab >= 256)
or a HuggingFace tokenizer name (lazy transformers import).
"""

from __future__ import annotations

import time
import uuid
from typing import Any, Dict, Iterable, List, Optional

from ..util import tracing
from .deployment import deployment
from .llm import default_params, start_engine


class SSEStream:
    """Iterator wrapper for streaming responses that carries the request
    id alongside the chunks, so the HTTP proxy can emit an X-Request-Id
    header (which doubles as the trace id) before the first event."""

    def __init__(self, request_id: str, gen):
        self.request_id = request_id
        self._gen = gen

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def close(self):
        self._gen.close()


class ByteTokenizer:
    """utf-8 bytes as token ids. No vocab files, no downloads — the test
    and smoke-path tokenizer (models only need vocab_size >= 256)."""

    eos_token_id: Optional[int] = None

    def encode(self, text: str) -> List[int]:
        return list(text.encode("utf-8"))

    def decode(self, ids: Iterable[int]) -> str:
        return bytes(i for i in ids if 0 <= i < 256).decode("utf-8", "replace")


class HFTokenizer:
    """HuggingFace tokenizer wrapper (lazy import; needs local files or a
    warm cache — this image has no egress)."""

    def __init__(self, name: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(name)
        self.eos_token_id = self._tok.eos_token_id

    def encode(self, text: str) -> List[int]:
        return list(self._tok.encode(text))

    def decode(self, ids: Iterable[int]) -> str:
        return self._tok.decode(list(ids))


def _make_tokenizer(spec) -> Any:
    if spec is None or spec == "byte":
        return ByteTokenizer()
    if isinstance(spec, str):
        return HFTokenizer(spec)
    return spec  # duck-typed: encode/decode/eos_token_id


def _chat_prompt(messages: List[Dict[str, str]]) -> str:
    """Minimal chat template: role-tagged lines, assistant turn opened."""
    lines = [f"{m.get('role', 'user')}: {m.get('content', '')}" for m in messages]
    lines.append("assistant:")
    return "\n".join(lines)


@deployment(name="openai", max_ongoing_requests=64)
class OpenAIServer:
    """OpenAI-shaped routes over one continuously-batched engine."""

    def __init__(
        self,
        model_name: str = "tiny-llama",
        engine_config: Optional[Dict[str, Any]] = None,
        params_fn=None,
        model_overrides: Optional[Dict[str, Any]] = None,
        tokenizer: Any = "byte",
        tensor_parallel: int = 1,
        speculation: Any = None,
        draft_params_fn=None,
        disagg: Any = None,
        disagg_deployments: Optional[List[str]] = None,
    ):
        self.model_name = model_name
        self.tokenizer = _make_tokenizer(tokenizer)
        if disagg_deployments is not None:
            # coordinator mode (build_openai_app(disagg=...)): no local
            # engine — requests prefill/decode on the role deployments
            from .disagg import DisaggCoordinator

            prefill_name, decode_name = disagg_deployments
            self._coordinator = DisaggCoordinator.from_deployments(
                prefill_name, decode_name, disagg)
            self.engine = None
            return
        self._coordinator = None
        ecfg_kw = dict(engine_config or {})
        ecfg_kw.setdefault("eos_token_id", self.tokenizer.eos_token_id)
        if speculation is not None:
            if ecfg_kw.get("speculation") is not None:
                raise ValueError(
                    "pass speculation either as the OpenAIServer kwarg or "
                    "inside engine_config, not both")
            ecfg_kw["speculation"] = speculation
        self.engine = start_engine(
            params_fn or default_params(model_name, model_overrides),
            ecfg_kw, tensor_parallel, draft_params_fn)

    # ------------------------------------------------------------- routes

    def _stop_ids(self, body) -> "Optional[list]":
        """OpenAI `stop`: string or list of strings -> token-id sequences
        via this app's tokenizer (plus stop_token_ids passthrough).

        Contract: matching is TOKEN-level on the encoded stop string —
        exact for the byte tokenizer (1 byte = 1 token always), while a
        merging tokenizer (HF) only fires when the model emits the stop
        text on the same token boundaries. Full detokenized string
        matching (vLLM's behavior) would need decode-per-token in the
        engine loop; use stop_token_ids for exact token-level control."""
        stops = []
        raw = body.get("stop")
        if isinstance(raw, str):
            raw = [raw]
        for s in raw or []:
            ids = self.tokenizer.encode(str(s))
            if ids:
                stops.append(ids)
        for tid in body.get("stop_token_ids") or []:
            stops.append([int(tid)])
        return stops or None

    def shutdown(self) -> None:
        """Replica retirement: see LLMServer.shutdown."""
        engine, self.engine = self.engine, None
        if engine is not None:  # coordinator mode has no local engine
            engine.stop()

    def _generate(self, ids, max_tokens, temperature, top_p, stop):
        if self._coordinator is not None:
            return self._coordinator.generate(
                ids, max_tokens=max_tokens, temperature=temperature,
                top_p=top_p, stop=stop)
        return self.engine.generate(ids, max_tokens=max_tokens,
                                    temperature=temperature, top_p=top_p,
                                    stop=stop)

    def completions(self, body: Dict[str, Any]):
        prompt = body.get("prompt", "")
        ids = (
            list(prompt)
            if isinstance(prompt, (list, tuple))
            else self.tokenizer.encode(str(prompt))
        )
        max_tokens = int(body.get("max_tokens", 16))
        temperature = float(body.get("temperature", 0.0))
        top_p = float(body.get("top_p", 1.0))
        stop = self._stop_ids(body)
        root = tracing.maybe_begin("request:completions")
        # the trace id IS the request id when sampled, so the response's
        # X-Request-Id can be looked up at /api/v0/traces/<id>
        rid = (f"cmpl-{root.trace_id}" if root is not None
               else f"cmpl-{uuid.uuid4().hex[:24]}")
        want_lp = bool(body.get("logprobs"))
        if body.get("stream"):
            return SSEStream(rid, self._stream_sse(
                rid, "text_completion", ids, max_tokens, temperature, top_p,
                stop, root=root, want_logprobs=want_lp,
            ))
        try:
            with tracing.activate(root):
                out = self._generate(ids, max_tokens, temperature, top_p,
                                     stop)
        finally:
            if root is not None:
                root.finish()
        text = self.tokenizer.decode(out["token_ids"])
        choice = {"index": 0, "text": text,
                  "finish_reason": out["finish_reason"] or "length"}
        if want_lp:
            choice["logprobs"] = self._completion_logprobs(out)
        return {
            "id": rid,
            "object": "text_completion",
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [choice],
            "usage": {
                "prompt_tokens": len(ids),
                "completion_tokens": len(out["token_ids"]),
                "total_tokens": len(ids) + len(out["token_ids"]),
            },
        }

    def chat_completions(self, body: Dict[str, Any]):
        messages = body.get("messages", [])
        ids = self.tokenizer.encode(_chat_prompt(messages))
        max_tokens = int(body.get("max_tokens", 16))
        temperature = float(body.get("temperature", 0.0))
        top_p = float(body.get("top_p", 1.0))
        stop = self._stop_ids(body)
        root = tracing.maybe_begin("request:chat_completions")
        rid = (f"chatcmpl-{root.trace_id}" if root is not None
               else f"chatcmpl-{uuid.uuid4().hex[:24]}")
        want_lp = bool(body.get("logprobs"))
        if body.get("stream"):
            return SSEStream(rid, self._stream_sse(
                rid, "chat.completion", ids, max_tokens, temperature, top_p,
                stop, root=root, want_logprobs=want_lp))
        try:
            with tracing.activate(root):
                out = self._generate(ids, max_tokens, temperature, top_p,
                                     stop)
        finally:
            if root is not None:
                root.finish()
        text = self.tokenizer.decode(out["token_ids"])
        choice = {
            "index": 0,
            "message": {"role": "assistant", "content": text},
            "finish_reason": out["finish_reason"] or "length",
        }
        if want_lp:
            lps = out.get("logprobs") or []
            choice["logprobs"] = {"content": [
                {"token": self.tokenizer.decode([t]), "logprob": lp}
                for t, lp in zip(out["token_ids"], lps)]}
        return {
            "id": rid,
            "object": "chat.completion",
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [choice],
            "usage": {
                "prompt_tokens": len(ids),
                "completion_tokens": len(out["token_ids"]),
                "total_tokens": len(ids) + len(out["token_ids"]),
            },
        }

    def models(self, _body: Any = None):
        return {
            "object": "list",
            "data": [
                {"id": self.model_name, "object": "model", "owned_by": "ray_tpu"}
            ],
        }

    def stats(self, _body: Any = None):
        if self._coordinator is not None:
            return self._coordinator.stats()
        return self.engine.stats()

    def check_health(self) -> None:
        pass

    # ------------------------------------------------------------ helpers

    def _completion_logprobs(self, out: Dict[str, Any]) -> Dict[str, Any]:
        """OpenAI text-completion `logprobs` block from an engine result.
        Sampled-token logprobs only (top_logprobs alternatives would need
        a top-k readback the decode program doesn't do); entries are None
        where the engine has no logprob (spec-decode commits, migration
        seeds)."""
        toks = [self.tokenizer.decode([t]) for t in out["token_ids"]]
        offsets, pos = [], 0
        for t in toks:
            offsets.append(pos)
            pos += len(t)
        return {
            "tokens": toks,
            "token_logprobs": list(out.get("logprobs") or []),
            "top_logprobs": None,
            "text_offset": offsets,
        }

    def _stream_sse(self, rid, obj, ids, max_tokens, temperature, top_p=1.0,
                    stop=None, root=None, want_logprobs=False):
        """Generator of OpenAI stream chunks; the HTTP proxy emits each as
        a server-sent event (in-process runtime: generators cross the
        handle live). `root` is the sampled request span — admission runs
        under it, and it finishes with the stream (covering every decode
        step through stream teardown)."""
        tokenizer, model = self.tokenizer, self.model_name
        engine, coordinator = self.engine, self._coordinator

        def gen():
            # admission happens on FIRST PULL, inside the generator: a
            # client that disconnects before consuming anything never
            # admits a request at all (a never-started generator's
            # finally cannot run, so nothing may need cancelling either)
            with tracing.activate(root):
                if coordinator is not None:
                    ds = coordinator.open_stream(
                        ids, max_tokens=max_tokens, temperature=temperature,
                        top_p=top_p, stop=stop,
                    )
                    stream = ds.tokens()
                    finish, cancel = (lambda: ds.finish_reason), ds.cancel
                    lp_at = getattr(ds, "logprob_at", lambda i: None)
                else:
                    req, stream = engine.open_stream(
                        ids, max_tokens=max_tokens, temperature=temperature,
                        top_p=top_p, stop=stop,
                    )
                    finish = lambda: req.finish_reason  # noqa: E731
                    cancel = lambda: engine.cancel(req.request_id)  # noqa: E731
                    # commit appends the logprob before the token is
                    # emitted, so by the time chunk i is yielded the
                    # engine-path logprob for it is already in place
                    lp_at = lambda i: (  # noqa: E731
                        req.output_logprobs[i]
                        if i < len(req.output_logprobs) else None)
            try:
                yield from body(stream, finish, lp_at)
            finally:
                # consumer gone (GeneratorExit on client disconnect) or
                # exhausted — cancel is a no-op on a finished request, and
                # frees the slot/pages of an abandoned one (reference:
                # serve's disconnect-driven cancellation)
                cancel()
                if root is not None:
                    root.finish()

        def body(stream, finish, lp_at):
            created = int(time.time())
            for i, tok in enumerate(stream):
                piece = tokenizer.decode([tok])
                if obj == "chat.completion":
                    delta = {"delta": {"content": piece}, "index": 0}
                    if want_logprobs:
                        delta["logprobs"] = {"content": [
                            {"token": piece, "logprob": lp_at(i)}]}
                else:
                    delta = {"text": piece, "index": 0}
                    if want_logprobs:
                        delta["logprobs"] = {
                            "tokens": [piece],
                            "token_logprobs": [lp_at(i)]}
                yield {
                    "id": rid,
                    "object": obj + ".chunk",
                    "created": created,
                    "model": model,
                    "choices": [delta],
                }
            # terminal chunk carries the real finish_reason (OpenAI wire)
            if obj == "chat.completion":
                last = {"delta": {}, "index": 0,
                        "finish_reason": finish() or "length"}
            else:
                last = {"text": "", "index": 0,
                        "finish_reason": finish() or "length"}
            yield {
                "id": rid,
                "object": obj + ".chunk",
                "created": created,
                "model": model,
                "choices": [last],
            }

        return gen()


def build_openai_app(disagg: Any = None, disagg_app_name: str = "llm",
                     **kwargs):
    """-> bound OpenAIServer deployment; serve.run(app, name='v1') exposes
    POST /v1/completions, /v1/chat_completions, /v1/models.

    With `disagg={...}` (DisaggConfig shape), the builder first deploys
    role-aware `{disagg_app_name}-prefill` / `{disagg_app_name}-decode`
    LLMServer apps (engine-bearing kwargs flow to them) and binds the
    OpenAIServer in coordinator mode: routes prefill on one role, stream
    tokens from the other, with KV migrating over the object plane."""
    if disagg is None:
        return OpenAIServer.bind(**kwargs)
    from .config import DisaggConfig
    from .disagg import deploy_disagg

    cfg = DisaggConfig.parse(disagg)
    tok = _make_tokenizer(kwargs.pop("tokenizer", "byte"))
    model_name = kwargs.pop("model_name", "tiny-llama")
    engine_config = dict(kwargs.pop("engine_config", None) or {})
    engine_config.setdefault("eos_token_id", tok.eos_token_id)
    deploy_disagg(model_name=model_name, disagg=cfg, name=disagg_app_name,
                  engine_config=engine_config, **kwargs)
    return OpenAIServer.bind(
        model_name=model_name, tokenizer=tok, disagg=cfg,
        disagg_deployments=[f"{disagg_app_name}-prefill",
                            f"{disagg_app_name}-decode"])
