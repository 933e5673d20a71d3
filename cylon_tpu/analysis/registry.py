"""Per-op builder declarations for the jaxpr pass + sentinel budgets.

Each program-builder module (``relational/*.py``, ``parallel/*.py``)
declares its builders here: a :class:`BuilderDecl` names the builder,
states the SPMD invariants the jaxpr pass must verify (which collectives
the traced program is allowed/required to contain, whether int32→int64
widening is intentional, the host-callback budget) and the sentinel's
retrace budget.  ``trace(mesh)`` returns a ClosedJaxpr of the builder's
program over small abstract inputs — tracing only, nothing compiles.

Declarations are registered at module import; :func:`collect` imports
every builder module so a checker (CLI or the slow pytest) sees the full
set.  This module must stay import-light (no jax, no cylon_tpu.relational
imports at module scope) — builder modules import it at their bottom.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

#: modules whose import populates the registry (every program-builder
#: module that declares invariants)
BUILDER_MODULES = (
    "cylon_tpu.parallel.collectives",
    "cylon_tpu.parallel.shuffle",
    "cylon_tpu.topo.exchange",
    "cylon_tpu.relational.join",
    "cylon_tpu.relational.piece",
    "cylon_tpu.relational.sort",
    "cylon_tpu.relational.groupby",
    "cylon_tpu.relational.setops",
    "cylon_tpu.relational.repart",
    "cylon_tpu.exec.pipeline",
    "cylon_tpu.exec.recovery",
    "cylon_tpu.exec.integrity",
    "cylon_tpu.stream.window",
    "cylon_tpu.series",
)

#: default bound on distinct compiled programs per builder per session
#: (RT302); pow2-bucketed capacities keep real families far below this
DEFAULT_RETRACE_BUDGET = 32

#: arrays at or above this many elements count as "row-scale" for the
#: JX203 widening check (sidecars — valid-count vectors, count matrices —
#: stay below it at the trace shapes the declarations use)
ROW_SCALE_ELEMS = 256


@dataclass(frozen=True)
class BuilderDecl:
    #: fully qualified builder name (module.func)
    builder: str
    #: trace(mesh) -> jax.core.ClosedJaxpr over abstract inputs
    trace: Callable
    #: collective primitives the program MUST contain (all of them,
    #: unconditionally) and may not exceed; frozenset() = pure-local
    #: program, any collective is a finding
    collectives: frozenset = frozenset()
    #: ops the op family tags itself with ("join", "sort", ...)
    tags: tuple = ()
    #: int32→int64 widening of row-scale arrays is intentional here
    allow_widen: bool = False
    #: host callbacks (pure/io/debug_callback) allowed in the program
    callback_budget: int = 0
    #: RT302: max distinct compiled programs per session
    retrace_budget: int = DEFAULT_RETRACE_BUDGET


def unwrap(fn):
    """Strip the retrace-sentinel tag wrapper off a built program so
    declarations trace the raw jit function (no sentinel noise)."""
    return getattr(fn, "__wrapped__", fn)


def decl_shapes(mesh, cap: int = 1024):
    """Shared trace-shape helper for declarations: ``(w, cap, S)`` with
    ``cap`` per-shard rows — large enough that row-scale arrays clear
    ROW_SCALE_ELEMS while (W,)/(W,W) sidecars stay below it."""
    import jax
    return int(mesh.devices.size), cap, jax.ShapeDtypeStruct


_DECLS: dict[str, BuilderDecl] = {}


def declare_builder(builder: str, trace: Callable, *,
                    collectives=frozenset(), tags=(), allow_widen=False,
                    callback_budget=0,
                    retrace_budget=DEFAULT_RETRACE_BUDGET) -> None:
    _DECLS[builder] = BuilderDecl(
        builder=builder, trace=trace, collectives=frozenset(collectives),
        tags=tuple(tags), allow_widen=allow_widen,
        callback_budget=callback_budget, retrace_budget=retrace_budget)


def all_declarations() -> list[BuilderDecl]:
    return list(_DECLS.values())


def get(builder: str) -> BuilderDecl | None:
    return _DECLS.get(builder)


def collect() -> list[BuilderDecl]:
    """Import every builder module (populating the registry) and return
    the declarations."""
    import importlib
    for mod in BUILDER_MODULES:
        importlib.import_module(mod)
    return all_declarations()
