"""HTTP proxy: JSON requests routed to deployment handles.

Reference: `python/ray/serve/_private/proxy.py :: ProxyActor` (uvicorn).
Here: a threaded stdlib HTTP server per proxy (no external deps), JSON
body in / JSON out, one route per application:
  POST /<app_name>           -> handle.remote(body)
  POST /<app_name>/<method>  -> handle.<method>.remote(body)
  GET  /-/healthz, /-/routes
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

from ..core.logging import get_logger
from ..util import tracing

logger = get_logger("serve.proxy")


def resolve_route(parts, routes):
    """Longest-prefix route match -> (handle, rest) or (None, []).

    i=0 tests the empty candidate so route_prefix "/" (route key "") is
    reachable — the reference's DEFAULT prefix (ADVICE r3). Shared by the
    HTTP and gRPC ingresses so resolution can never diverge."""
    for i in range(len(parts), -1, -1):
        candidate = "/".join(parts[:i])
        if candidate in routes:
            return routes[candidate], parts[i:]
    return None, []


class HTTPProxy:
    def __init__(self, host: str = "127.0.0.1", port: int = 8000):
        self.host = host
        self.port = port
        self.routes: Dict[str, Any] = {}  # app name -> DeploymentHandle
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def add_route(self, name: str, handle) -> None:
        self.routes[name] = handle

    def remove_route(self, name: str) -> None:
        self.routes.pop(name, None)

    def start(self) -> int:
        proxy = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # quiet
                logger.debug("http: " + fmt, *args)

            def _send(self, code: int, payload: Any,
                      request_id: Optional[str] = None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if request_id:
                    # doubles as the trace id: /api/v0/traces/<this>
                    self.send_header("X-Request-Id", str(request_id))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/-/healthz":
                    return self._send(200, {"status": "ok"})
                if self.path == "/-/routes":
                    return self._send(200, sorted(proxy.routes))
                return self._send(404, {"error": f"no route {self.path}"})

            def do_POST(self):
                received_ns = tracing.now_ns()
                parts = [p for p in self.path.split("/") if p]
                # longest-prefix route match (route prefixes may span
                # several segments, e.g. /api/v9); remaining segments map
                # to underscored methods, so the OpenAI wire path
                # /v1/chat/completions hits chat_completions
                handle, rest = resolve_route(parts, proxy.routes)
                if handle is None:
                    return self._send(404, {"error": f"no app at {self.path}"})
                if rest:
                    handle = handle.options("_".join(rest))
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length) if length else b"{}"
                try:
                    payload = json.loads(raw) if raw.strip() else {}
                except json.JSONDecodeError as e:
                    return self._send(400, {"error": f"bad json: {e}"})
                rid = ""
                if isinstance(payload, dict):
                    rid = str(payload.get("request_id") or "")
                    # reserved key: the receipt instant rides with the
                    # request, so the engine can say what the front added
                    # before add_request (serve_front_seconds, inbound)
                    payload["_received_ns"] = received_ns
                with tracing.region("front.request", route=self.path,
                                    request_id=rid):
                    try:
                        result = handle.remote(payload).result(timeout=300.0)
                        if _is_stream(result):
                            return self._send_sse(
                                result, getattr(result, "request_id", None))
                        rid = (result.get("id")
                               if isinstance(result, dict) else None)
                        return self._send(200, {"result": _jsonable(result)},
                                          request_id=rid)
                    except Exception as e:
                        logger.warning("request failed", exc_info=True)
                        return self._send(500, {"error": str(e)})

            def _send_sse(self, chunks, request_id: Optional[str] = None):
                """Server-sent events: one `data:` line per chunk, then
                [DONE] (the OpenAI streaming wire format)."""
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                if request_id:
                    self.send_header("X-Request-Id", str(request_id))
                self.end_headers()
                first = True
                try:
                    try:
                        for chunk in chunks:
                            data = json.dumps(_jsonable(chunk))
                            self.wfile.write(f"data: {data}\n\n".encode())
                            self.wfile.flush()
                            if first:
                                first = False
                                _note_first_chunk(chunks)
                    except (BrokenPipeError, ConnectionResetError):
                        raise  # client went away: outer handler, no spam
                    except Exception as e:  # noqa: BLE001
                        # Headers are already on the wire; a second response
                        # would corrupt the stream, so surface the failure as
                        # a terminal SSE event instead (ADVICE r2).
                        logger.warning("SSE stream failed", exc_info=True)
                        err = json.dumps({"error": str(e)})
                        self.wfile.write(f"data: {err}\n\n".encode())
                    self.wfile.write(b"data: [DONE]\n\n")
                    self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError):
                    logger.debug("SSE client disconnected")
                finally:
                    # close the chunk generator NOW (not at GC): its
                    # finally-blocks cancel abandoned upstream work (e.g.
                    # the LLM engine request) promptly on disconnect
                    close = getattr(chunks, "close", None)
                    if callable(close):
                        try:
                            close()
                        except Exception:  # noqa: BLE001
                            pass

        self._server = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever, daemon=True)
        self._thread.start()
        logger.info("HTTP proxy on %s:%d", self.host, self.port)
        return self.port

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None


def _note_first_chunk(chunks: Any) -> None:
    """serve_front_seconds{leg="outbound"}: a stream that knows its
    engine's first-token instant (engine.TokenStream) says how long the
    first chunk took from there to the wire."""
    first_token_ns = getattr(chunks, "first_token_ns", None)
    if first_token_ns is not None:
        from .engine import observe_front_outbound

        observe_front_outbound(first_token_ns)


def _is_stream(x: Any) -> bool:
    """Generators/iterators stream as SSE; don't mistake JSON containers."""
    return hasattr(x, "__next__")


def _jsonable(x: Any) -> Any:
    try:
        json.dumps(x)
        return x
    except TypeError:
        import numpy as np

        if isinstance(x, np.ndarray):
            return x.tolist()
        if isinstance(x, (np.integer, np.floating)):
            return x.item()
        if isinstance(x, dict):
            return {k: _jsonable(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return [_jsonable(v) for v in x]
        return repr(x)
