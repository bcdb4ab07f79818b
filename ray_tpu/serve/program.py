"""A compiled program of a replica, described once: the jitted function and
ONE list of what it takes. `InferenceEngine.warmup` reads the list for the
values it compiles and runs the program with, `InferenceEngine.programs`
for the shapes a tool lowers it with (a compile for a described chip, a
test of its text); the loop's call sites are held to it by
tests/test_serve.py (`TestPrograms`).

An argument is an `Arg` (shape, dtype and the warm-up's fill), or the NAME
of something the engine keeps: `params`, `k_pages`, `state`, `_carry`,
... which the warm-up passes as it stands (the donated ones are handed
back: `Program.back`) and the description gives as its abstract form."""

from __future__ import annotations

import contextlib
from typing import Any, Dict, NamedTuple

import jax


class Arg(NamedTuple):
    shape: tuple
    dtype: Any
    fill: Any = 0  # what the warm-up passes: an array of this value,
    host: bool = False  # ... the host's (numpy) where the loop passes such


class Program(NamedTuple):
    name: str  # of its `engine.warmup.program` region
    attrs: Dict[str, int]  # that region's other attributes
    call: Any  # as the loop calls it: under the engine's mesh, a span bound
    # what lowers it for `args`: the `jax.jit` object beneath, one a
    # program (a decode program: its `_SpanOf`, which adds the step count)
    jitted: Any
    args: tuple  # positional; `InferenceEngine.programs`: ShapeDtypeStructs
    # where the warm-up keeps what the program hands back, a prefix of its
    # output's tree: an engine attribute's name, or None (dropped)
    back: Any = None
    mesh: Any = None

    @classmethod
    def under(cls, mesh, name, call, args, back=None, jitted=None,
              **attrs) -> "Program":
        """A program of an engine on `mesh`: `call` is the jitted program
        itself without a mesh and a wrapper that enters the mesh with one
        (`InferenceEngine._under_mesh`), unless `jitted` says otherwise."""
        if jitted is None:
            jitted = call if mesh is None else call.__wrapped__
        return cls(name, attrs, call, jitted, args, back, mesh)

    def bind(self, leaf) -> "Program":
        """This program with `leaf(a)` in the place of every `Arg` and
        name among its arguments."""
        return self._replace(args=jax.tree.map(
            leaf, self.args, is_leaf=lambda a: isinstance(a, Arg)))

    def lower(self):
        """Lowered for its (abstract) arguments, as the engine would trace
        it: under its mesh."""
        with self.mesh or contextlib.nullcontext():
            return self.jitted.lower(*self.args)
