"""Logical-axis sharding rules → GSPMD NamedShardings.

The TPU-native replacement for everything the reference delegates to
DDP/FSDP/DeepSpeed wrappers (upstream ray `python/ray/train/torch/
train_loop_utils.py :: prepare_model` and the strategy plumbing in
`torch_trainer.py`): parallelism is expressed once, as a mapping from
*logical* array axes ("batch", "embed", "mlp", …) to *mesh* axes
("dp", "fsdp", "tp", …), and XLA inserts the collectives. Changing
DP → FSDP → TP → 3D is a rules change, not a code change (the
weight-update-sharding design of arxiv 2004.13336).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MeshAxes = Union[None, str, Tuple[str, ...]]
Rules = Dict[str, MeshAxes]

# Default transformer rules (scaling-book conventions):
#   batch over all data axes; params sharded over fsdp (ZeRO-3) and tp;
#   sequence over sp for long-context; experts over ep.
DEFAULT_RULES: Rules = {
    "batch": ("dcn_dp", "dp", "fsdp"),
    "seq": ("dcn_sp", "sp"),
    "embed": "fsdp",
    "heads": "tp",
    "kv": None,
    "mlp": "tp",
    "vocab": "tp",
    "expert": "ep",
    "expert_mlp": "tp",
    "stage": ("dcn_pp", "pp"),
    "norm": None,
}


def spec_for(axes: Sequence[Optional[str]], rules: Optional[Rules] = None) -> PartitionSpec:
    """Logical axes of one array → PartitionSpec. None = replicated dim."""
    rules = DEFAULT_RULES if rules is None else rules
    parts = []
    for ax in axes:
        if ax is None:
            parts.append(None)
            continue
        if ax not in rules:
            raise KeyError(f"no sharding rule for logical axis {ax!r}")
        parts.append(rules[ax])
    return PartitionSpec(*parts)


def _filter_spec_for_mesh(spec: PartitionSpec, mesh: Mesh) -> PartitionSpec:
    """Drop mesh axes the mesh doesn't have (size-1 semantics): lets one
    rule set serve dp-only, fsdp+tp, full 3D meshes unchanged.

    Also drops repeated mesh axes (first dimension wins): one rule set
    serves params AND activations — e.g. "batch"→(dp, fsdp) plus
    "embed"→fsdp on the same activation resolves to batch taking fsdp and
    embed replicating, which is exactly ZeRO semantics (weights sharded
    over fsdp at rest, activations batch-sharded in flight)."""
    parts = []
    used: set = set()
    for entry in spec:
        if entry is None:
            parts.append(None)
            continue
        cand = (entry,) if isinstance(entry, str) else tuple(entry)
        kept = tuple(a for a in cand if a in mesh.axis_names and a not in used)
        used.update(kept)
        if not kept:
            parts.append(None)
        elif isinstance(entry, str):
            parts.append(kept[0] if kept else None)
        else:
            parts.append(kept)
    return PartitionSpec(*parts)


def sharding_for(
    axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[Rules] = None,
) -> NamedSharding:
    return NamedSharding(mesh, _filter_spec_for_mesh(spec_for(axes, rules), mesh))


def tree_shardings(
    axes_tree: Any, mesh: Mesh, rules: Optional[Rules] = None
) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes: sharding_for(axes, mesh, rules),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )


import threading as _threading

_constrain_disabled = _threading.local()  # at import: lazy check-then-assign
# from two first-caller threads would orphan one thread's flag


def no_constrain():
    """Context manager: constrain() becomes identity while tracing inside.

    Needed for shard_map bodies (pipeline stages): with_sharding_constraint
    over manual mesh axes is illegal there, and per-shard code already IS
    the sharding. Thread-local, so concurrent traces don't interfere."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        prev = getattr(_constrain_disabled, "on", False)
        _constrain_disabled.on = True
        try:
            yield
        finally:
            _constrain_disabled.on = prev

    return ctx()


def constrain(x: jax.Array, axes: Sequence[Optional[str]], rules: Optional[Rules] = None) -> jax.Array:
    """In-jit sharding constraint by logical axes (activation annotations)."""
    if getattr(_constrain_disabled, "on", False):
        return x
    mesh = _current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding_for(axes, mesh, rules))


def split_ways(axes: Sequence[Optional[str]], mesh: Optional[Mesh] = None) -> int:
    """Into how many pieces `constrain(x, axes)` cuts x, one a device: 1
    without a mesh and inside per-shard code, whose arrays are the pieces."""
    mesh = mesh if mesh is not None else _current_mesh()
    if mesh is None or getattr(_constrain_disabled, "on", False):
        return 1
    ways = 1
    for part in sharding_for(axes, mesh).spec:
        for name in (part,) if isinstance(part, str) else part or ():
            ways *= mesh.shape[name]
    return ways


def per_shard(fn, in_axes: Sequence[Sequence[Optional[str]]],
              out_axes: Sequence[Optional[str]], *args,
              mesh: Optional[Mesh] = None):
    """fn(*args) with each device running fn on its own shard.

    GSPMD cannot partition a Pallas call ("Mosaic kernels cannot be
    automatically partitioned"), so on a multi-device mesh a kernel op runs
    under shard_map, its operands laid out by their logical axes — the
    layout constrain() already gives them. fn must be independent along
    every sharded axis (attention over batch and heads, a norm over rows).
    No mesh, one device, or already inside a shard_map body
    (no_constrain): plain call."""
    mesh = mesh if mesh is not None else _current_mesh()
    if (mesh is None or mesh.size == 1
            or getattr(_constrain_disabled, "on", False)):
        return fn(*args)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(sharding_for(a, mesh).spec for a in in_axes),
        out_specs=sharding_for(out_axes, mesh).spec,
        check_vma=False,
    )(*args)


def _current_mesh() -> Optional[Mesh]:
    try:
        env = jax._src.mesh.thread_resources.env  # set by `with mesh:`
        pm = env.physical_mesh
        if not pm.empty:
            return pm
    except Exception:
        pass
    from ..comm.mesh import registry

    # No auto-build: without an active or registered mesh, constrain() is a
    # no-op rather than pinning eager intermediates to a fabricated mesh.
    return registry.peek("default")


def shard_tree(params: Any, axes_tree: Any, mesh: Mesh, rules: Optional[Rules] = None) -> Any:
    """Device-put a pytree of host arrays to its sharded layout."""
    shardings = tree_shardings(axes_tree, mesh, rules)
    return jax.tree.map(lambda p, s: jax.device_put(p, s), params, shardings)


# ---------------------------------------------------------------------------
# Regex partition rules (fmengine/EasyLM lineage): map *parameter paths* to
# PartitionSpecs, first match wins. Complements the logical-axis rules above:
# logical axes need the model to annotate every array; path rules shard an
# existing checkpoint-shaped flat dict ("layers/wq", "embed", ...) without
# touching model code — which is what the pipeline StageWorker has in hand.
# ---------------------------------------------------------------------------

PathRules = Tuple[Tuple[str, PartitionSpec], ...]

# Stage-local mesh rules for the LM pipeline trainer: per-layer leaves carry a
# leading stacked-layer axis (always replicated — it is scanned over), then
# megatron-style column/row splits over tp with fsdp on the complementary dim.
STAGE_PARTITION_RULES: PathRules = (
    (r"(^|/)layers/(wq|wk|wv)$", PartitionSpec(None, "fsdp", "tp", None)),
    (r"(^|/)layers/wo$", PartitionSpec(None, "tp", None, "fsdp")),
    (r"(^|/)layers/(w_in|w_gate)$", PartitionSpec(None, "fsdp", "tp")),
    (r"(^|/)layers/w_out$", PartitionSpec(None, "tp", "fsdp")),
    (r"(^|/)layers/b_in$", PartitionSpec(None, "tp")),
    (r"(^|/)layers/", PartitionSpec()),  # norms, biases: replicated
    (r"(^|/)embed$", PartitionSpec("tp", "fsdp")),
    (r"(^|/)lm_head$", PartitionSpec("fsdp", "tp")),
    (r"(^|/)pos_emb$", PartitionSpec(None, "fsdp")),
    (r"(^|/)final_norm", PartitionSpec()),
)


def match_partition_rules(
    rules: PathRules, flat_params: Dict[str, Any]
) -> Dict[str, PartitionSpec]:
    """'/'-joined param path → PartitionSpec via regex search, first match wins.

    Scalars (ndim 0) short-circuit to a replicated spec; a non-scalar leaf no
    rule matches is an error — silent replication is how sharding plans rot.
    """
    import re

    out: Dict[str, PartitionSpec] = {}
    for path, leaf in flat_params.items():
        ndim = getattr(leaf, "ndim", 0)
        if ndim == 0:
            out[path] = PartitionSpec()
            continue
        for pat, spec in rules:
            if re.search(pat, path):
                out[path] = spec
                break
        else:
            raise ValueError(f"no partition rule matches param path {path!r}")
    return out


def parse_mesh_axes(text: str) -> Dict[str, int]:
    """Parse a 'dp=2,tp=2'-style mesh spec into {axis: size} (ordered)."""
    axes: Dict[str, int] = {}
    for part in (text or "").replace(" ", "").split(","):
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad mesh axis {part!r} in {text!r} (want name=size)")
        name, size = part.split("=", 1)
        axes[name] = int(size)
    return axes


def stage_param_shardings(
    flat_params: Dict[str, Any],
    mesh: Mesh,
    rules: Optional[PathRules] = None,
) -> Dict[str, NamedSharding]:
    """NamedSharding per stage-param path, degraded where shapes forbid it.

    Specs come from regex rules filtered to the axes this mesh actually has;
    any dim whose size is not divisible by its assigned axes falls back to
    replicated for that dim (tiny test models have odd head counts) rather
    than erroring inside device_put.
    """
    specs = match_partition_rules(rules or STAGE_PARTITION_RULES, flat_params)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out: Dict[str, NamedSharding] = {}
    for path, leaf in flat_params.items():
        spec = _filter_spec_for_mesh(specs[path], mesh)
        shape = getattr(leaf, "shape", ())
        parts = []
        for d, entry in enumerate(spec):
            if entry is None:
                parts.append(None)
                continue
            cand = (entry,) if isinstance(entry, str) else tuple(entry)
            n = 1
            for a in cand:
                n *= sizes.get(a, 1)
            if d >= len(shape) or shape[d] % n != 0:
                parts.append(None)
            else:
                parts.append(entry)
        out[path] = NamedSharding(mesh, PartitionSpec(*parts))
    return out
