"""LLM serving deployment: the serve-level wrapper over InferenceEngine.

Reference analogue: `ray.serve.llm :: LLMServer / build_openai_app` (A4).
One replica = one engine (= one chip/slice); serve's router spreads
requests over replicas, the engine continuously batches within a replica.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Dict, List, Optional

import jax

from ..core.metrics import Counter
from ..models import get_config, init_params
from ..util import tracing
from .deployment import deployment
from .engine import EngineConfig, InferenceEngine, tree_bytes

_m_replica_start = Counter(
    "serve_replica_start_seconds",
    "Seconds of a replica's start (`start_engine`), by `phase`: params "
    "(the weights' loader), engine (InferenceEngine.__init__: pools, "
    "state, slot tables, jit wrappers) and warmup (the whole of "
    "`warmup`): the `replica.start` trace's regions, summed.")


def start_engine(load_params, engine_config: Dict[str, Any],
                 tensor_parallel: int = 1, draft_params_fn=None,
                 role: str = "colocated") -> InferenceEngine:
    """A replica's start, as ONE trace: the root span `replica.start`
    (always, one a replica life: not sampled) and under it the regions
    that tile it, `replica.start.params` (`load_params() -> (params,
    model_cfg)`, and the draft's weights), `replica.start.engine`
    (`InferenceEngine.__init__`) and `engine.warmup`, in which `warmup`
    opens one `engine.warmup.program` a program; the `xla.trace` /
    `xla.lower` / `xla.compile` spans of what each compiled are its
    children (`tracing.watch_compiles`). The regions' seconds add up in
    `serve_replica_start_seconds{phase}`. The trace's id is
    `engine.stats()["startup_trace_id"]`; the finished tree stays on the
    engine (`engine.startup_trace`), where the span ring's turning over
    does not reach it."""
    tracing.watch_compiles()
    with tracing.start_span("replica.start", {"role": role}) as root:
        with tracing.region("replica.start.params") as r:
            params, cfg = load_params()
            draft_params = (draft_params_fn()
                            if draft_params_fn is not None else None)
            r.note(bytes=tree_bytes(params))
        _m_replica_start.inc(r.elapsed_s, tags={"phase": "params"})
        if role != "colocated" and cfg.is_stack:
            raise ValueError(
                f"role={role!r}: {cfg.name!r} keeps state beside its KV "
                "pages (conv tails, scan state, window rings) that the KV "
                "wire does not carry; serve it colocated")
        ecfg = EngineConfig(**engine_config)
        mesh = None
        if tensor_parallel > 1:
            from ..comm.mesh import MeshSpec, build_mesh

            devices = jax.devices()
            if len(devices) < tensor_parallel:
                raise ValueError(
                    f"tensor_parallel={tensor_parallel} needs that many local "
                    f"devices, have {len(devices)}"
                )
            mesh = build_mesh(
                MeshSpec.create(tp=tensor_parallel),
                devices=devices[:tensor_parallel],
            )
        with tracing.region("replica.start.engine") as r:
            engine = InferenceEngine(params, cfg, ecfg, mesh=mesh,
                                     draft_params=draft_params)
            r.note(pool_bytes=tree_bytes((engine.k_pages, engine.v_pages)),
                   state_bytes=engine._state_bytes)
        _m_replica_start.inc(r.elapsed_s, tags={"phase": "engine"})
        engine.startup_trace_id = root.trace_id
        with tracing.region("engine.warmup") as r:
            engine.warmup(buckets=[])
        _m_replica_start.inc(r.elapsed_s, tags={"phase": "warmup"})
    engine.startup_trace = tracing.get_trace(root.trace_id)
    return engine


def default_params(model_name: str, model_overrides: Optional[Dict[str, Any]]):
    """-> the loader `start_engine` takes: random-init weights of the
    named config."""

    def load():
        cfg = get_config(model_name, **(model_overrides or {}))
        return init_params(cfg, jax.random.PRNGKey(0)), cfg

    return load


@deployment(name="llm", max_ongoing_requests=32)
class LLMServer:
    """Token-level LLM server.

    Request: {"prompt_ids": [int], "max_tokens": int, "temperature": float,
              "top_p": float, "top_k": int, "stop_token_ids": [[int]]}
    Response: {"token_ids": [...], "ttft_s": ..., "latency_s": ...}

    params_fn: optional () -> (params, model_cfg) to load real weights;
    default builds random-init weights for the named config.

    speculation: speculative-decoding config (SpeculationConfig or its
    dict form) — shorthand for engine_config["speculation"]; the two must
    not both be set. draft_params_fn loads the draft model's weights for
    mode="draft" (default: random init of the named draft config).

    role: "colocated" (default — the classic one-replica-does-both path),
    or "prefill"/"decode" for disaggregated serving (serve/disagg.py):
    prefill replicas run prompt-only passes and export KV, decode
    replicas import KV and stream tokens. The engine is identical either
    way; the role only gates which request methods make sense here.
    """

    ROLES = ("colocated", "prefill", "decode")

    def __init__(
        self,
        model_name: str = "tiny-llama",
        engine_config: Optional[Dict[str, Any]] = None,
        params_fn=None,
        model_overrides: Optional[Dict[str, Any]] = None,
        tensor_parallel: int = 1,
        speculation: Any = None,
        draft_params_fn=None,
        role: str = "colocated",
    ):
        if role not in self.ROLES:
            raise ValueError(
                f"role must be one of {self.ROLES}, got {role!r}")
        self.role = role
        self._kv_inbox = None  # decode role: created on first kv_ingest
        self._kv_inbox_lock = threading.Lock()
        # multi-model LoRA hot-swap: resident adapter weights, small LRU
        # (move-to-end on touch, evict-oldest past capacity); the fleet
        # distributes adapters over the broadcast relay tree and requests
        # naming a non-resident adapter pull it lazily via adapter_ref
        self._adapters: "collections.OrderedDict[str, Any]" = \
            collections.OrderedDict()
        self._adapter_capacity = 8
        self._adapter_lock = threading.Lock()
        self._adapter_hits: Dict[str, int] = {}
        engine_config = dict(engine_config or {})
        if speculation is not None:
            if engine_config.get("speculation") is not None:
                raise ValueError(
                    "pass speculation either as the LLMServer kwarg or "
                    "inside engine_config, not both")
            engine_config["speculation"] = speculation
        self.engine = start_engine(
            params_fn or default_params(model_name, model_overrides),
            engine_config, tensor_parallel, draft_params_fn, role)
        # SLO digests group by serving role (colocated/prefill/decode):
        # the head answers "p95 TTFT per role" from the merged sketches
        self.engine.slo_role = role

    def shutdown(self) -> None:
        """Replica retirement: stop the engine's threads and let go of it,
        or they keep its weights and KV pool on the device for good."""
        engine, self.engine = self.engine, None
        engine.stop()

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        return self.engine.generate(
            prompt=list(request["prompt_ids"]),
            max_tokens=int(request.get("max_tokens", 32)),
            temperature=float(request.get("temperature", 0.0)),
            top_p=float(request.get("top_p", 1.0)),
            top_k=int(request.get("top_k", 0)),
            stop=request.get("stop_token_ids"),
            request_id=request.get("request_id"),
            received_ns=request.get("_received_ns"),
        )

    def stream(self, request: Dict[str, Any]):
        """Token iterator: first token arrives at TTFT, not completion.
        (In-process runtime: the generator crosses the handle live.)"""
        return self.engine.generate_stream(
            prompt=list(request["prompt_ids"]),
            max_tokens=int(request.get("max_tokens", 32)),
            temperature=float(request.get("temperature", 0.0)),
            top_p=float(request.get("top_p", 1.0)),
            top_k=int(request.get("top_k", 0)),
            stop=request.get("stop_token_ids"),
            request_id=request.get("request_id"),
            received_ns=request.get("_received_ns"),
        )

    # ---------------------------------------------------------- disagg
    # Thin delegations to serve/disagg.py replica helpers; the
    # coordinator addresses these directly on the replica actor (not via
    # a DeploymentHandle) so channel KV lands where the decode runs.

    def prefill_request(self, request: Dict[str, Any]) -> Dict[str, Any]:
        from .disagg import replica_prefill

        return replica_prefill(self.engine, request)

    def decode_request(self, request: Dict[str, Any]) -> Dict[str, Any]:
        from .disagg import replica_decode

        self._ensure_adapter(request)
        return replica_decode(self.engine, request, self._kv_inbox)

    def decode_stream(self, request: Dict[str, Any]):
        from .disagg import replica_decode_stream

        self._ensure_adapter(request)
        return replica_decode_stream(self.engine, request, self._kv_inbox)

    def generate_request(self, request: Dict[str, Any]) -> Dict[str, Any]:
        from .disagg import replica_generate

        self._ensure_adapter(request)
        return replica_generate(self.engine, request)

    def generate_stream(self, request: Dict[str, Any]):
        from .disagg import replica_generate_stream

        self._ensure_adapter(request)
        return replica_generate_stream(self.engine, request)

    # --------------------------------------------------- LoRA hot-swap

    def load_adapter(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """Pin a LoRA adapter resident: {"adapter_id", "weights"|"ref"}.
        An ObjectRef resolves through the object plane's pull-through
        GET — host-local when the broadcast relay tree pre-seeded it."""
        from .. import api

        adapter_id = str(request["adapter_id"])
        weights = request.get("weights")
        if weights is None and request.get("ref") is not None:
            weights = api.get(request["ref"],
                              timeout=float(request.get("timeout_s", 60.0)))
        with self._adapter_lock:
            self._adapters[adapter_id] = weights
            self._adapters.move_to_end(adapter_id)
            evicted = []
            while len(self._adapters) > self._adapter_capacity:
                old, _w = self._adapters.popitem(last=False)
                self._adapter_hits.pop(old, None)
                evicted.append(old)
        return {"adapter_id": adapter_id, "resident": True,
                "evicted": evicted}

    def list_adapters(self, _request: Any = None) -> List[str]:
        with self._adapter_lock:
            return sorted(self._adapters)

    def _ensure_adapter(self, request: Dict[str, Any]) -> None:
        adapter_id = request.get("adapter_id")
        if not adapter_id:
            return
        with self._adapter_lock:
            if adapter_id in self._adapters:
                self._adapters.move_to_end(adapter_id)
                self._adapter_hits[adapter_id] = \
                    self._adapter_hits.get(adapter_id, 0) + 1
                return
        if request.get("adapter_ref") is None:
            raise ValueError(
                f"adapter {adapter_id!r} not resident and the request "
                f"carries no adapter_ref to pull it from")
        self.load_adapter({"adapter_id": adapter_id,
                           "ref": request["adapter_ref"],
                           "timeout_s": request.get("timeout_s", 60.0)})
        with self._adapter_lock:
            self._adapter_hits[adapter_id] = \
                self._adapter_hits.get(adapter_id, 0) + 1

    # ---------------------------------------------- live weight re-sync

    def update_weights(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """Swap the engine's base weights live (no drain): {"weights"|
        "ref", "version"?}. An ObjectRef resolves through the object
        plane's pull-through GET — host-local when the broadcast relay
        tree pre-seeded it (the fleet's sync_weights path)."""
        from .. import api

        weights = request.get("weights")
        if weights is None and request.get("ref") is not None:
            weights = api.get(request["ref"],
                              timeout=float(request.get("timeout_s", 60.0)))
        if weights is None:
            raise ValueError("update_weights needs 'weights' or 'ref'")
        v = self.engine.update_params(weights,
                                      version=request.get("version"))
        return {"weights_version": v, "role": self.role}

    def weights_version(self, _request: Any = None) -> int:
        return self.engine.weights_version

    def prefix_digest(self, _request: Any = None) -> Dict[str, Any]:
        """Compact prefix-cache fingerprint for the coordinator's
        prefix-aware role routing."""
        return self.engine.prefix_digest()

    def kv_ingest(self, request: Any = None):
        """Lazily create this replica's KV inbox and return its
        DistChannel handle (picklable: prefill replicas put into it)."""
        from .disagg import KvInbox

        # concurrent first requests race here (in-process replicas
        # dispatch handle_request from many threads); without the lock
        # each caller mints its own inbox and all but the last-written
        # channel are orphans no drainer ever reads
        with self._kv_inbox_lock:
            if self._kv_inbox is None:
                ttl = float((request or {}).get("kv_inbox_ttl_s", 120.0)) \
                    if isinstance(request, dict) else 120.0
                self._kv_inbox = KvInbox(ttl_s=ttl)
            return self._kv_inbox.channel

    def cancel(self, request: Dict[str, Any]) -> bool:
        hit = self.engine.cancel(request["request_id"])
        if self._kv_inbox is not None:
            self._kv_inbox.cancel(request["request_id"])
        return hit

    def stats(self, _request: Any = None) -> Dict[str, Any]:
        out = self.engine.stats()
        out["role"] = self.role
        with self._adapter_lock:
            out["adapters"] = sorted(self._adapters)
            out["adapter_requests"] = dict(self._adapter_hits)
        return out

    def startup_trace(self, _request: Any = None) -> List[Dict[str, Any]]:
        """This replica's `replica.start` trace as a tree (the shape of
        `tracing.get_trace`), however long it has served since."""
        return self.engine.startup_trace

    def check_health(self) -> None:
        pass
