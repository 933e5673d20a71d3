"""Pass 2 — jaxpr-level SPMD invariant verification.

For each registered program builder (:mod:`cylon_tpu.analysis.registry`)
the checker traces the builder's program over small abstract inputs
(``jax.make_jaxpr`` — no compilation) and walks the jaxpr, recursing
through every sub-jaxpr (``pjit``, ``shard_map``, ``cond`` branches,
``while`` cond/body, ``scan``), to assert:

* **collective unconditionality** (JX201/JX202): a collective primitive
  (``all_gather``/``all_to_all``/``psum``/``ppermute``/…) under a
  ``cond``/``switch`` branch or a data-dependent ``while`` body executes
  on a rank-dependent subset of the mesh — the classic mismatched-
  participation deadlock, invisible on CPU.  ``scan`` (static trip count,
  identical on every rank — e.g. the multi-round exchange's
  ``fori_loop``) is explicitly allowed;
* **declared collective set** (JX205): the program contains exactly the
  collectives its declaration names — a builder that silently grew an
  ``all_gather`` (or lost its ``all_to_all``) changed its communication
  contract;
* **no unintended i32→i64 widening** (JX203): under x64 a stray Python
  int or default reduction accumulator (``jnp.sum(bool_mat)``,
  ``cumsum``) promotes a row-scale int32 array to int64 — 2x the bytes
  through every gather and collective.  The rule sees
  ``convert_element_type`` only: an int64 array *born* wide (a
  default-dtype ``iota``) has no convert and must be caught by pinning
  iota dtypes at the source (see the masks in collectives/repart);
* **host-callback budget** (JX204): ``pure_callback``/``io_callback``/
  ``debug_callback`` primitives are device→host round-trips inside the
  program; each builder budgets them (default zero).
"""

from __future__ import annotations

from .registry import ROW_SCALE_ELEMS, BuilderDecl
from .rules import Finding

#: cross-device communication primitives (normalized names).  NOT listed:
#: ``pbroadcast`` — shard_map's check_rep machinery inserts it to coerce
#: replication types; it moves no data and lowers to nothing device-side.
COLLECTIVE_PRIMS = {
    "all_gather", "all_to_all", "psum", "pmin", "pmax", "ppermute",
    "reduce_scatter",
}

#: primitives that are host round-trips
CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback"}

#: control primitives recorded in the walk context
_CONTROL = {"cond", "while", "scan", "pjit", "shard_map", "closed_call",
            "core_call", "custom_jvp_call", "custom_vjp_call", "remat",
            "checkpoint"}


def _norm(prim_name: str) -> str:
    """Normalize primitive spelling drift across jax versions
    (``psum2``/``psum_invariant`` → ``psum``, ``all_gather_invariant`` →
    ``all_gather``)."""
    name = prim_name
    if name.endswith("2"):
        name = name[:-1]
    if name.endswith("_invariant"):
        name = name[: -len("_invariant")]
    return name


def _sub_jaxprs(eqn):
    """Yield every (sub)jaxpr referenced by an eqn's params."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for val in eqn.params.values():
        if isinstance(val, (ClosedJaxpr, Jaxpr)):
            yield val
        elif isinstance(val, (tuple, list)):
            for item in val:
                if isinstance(item, (ClosedJaxpr, Jaxpr)):
                    yield item


def iter_eqns(jaxpr, ctx=()):
    """Depth-first walk yielding ``(eqn, ctx)`` where ``ctx`` is the tuple
    of enclosing control-primitive names (outermost first)."""
    from jax.extend.core import ClosedJaxpr
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, ctx
        name = eqn.primitive.name
        inner = ctx + ((name,) if name in _CONTROL else ("call",))
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner)


def check_jaxpr(closed_jaxpr, decl: BuilderDecl) -> list[Finding]:
    """Walk a traced builder program and return JX findings."""
    import numpy as np
    findings = []
    where = decl.builder
    found = set()
    n_callbacks = 0
    for eqn, ctx in iter_eqns(closed_jaxpr):
        name = _norm(eqn.primitive.name)
        if name in COLLECTIVE_PRIMS:
            found.add(name)
            if "cond" in ctx:
                findings.append(Finding(
                    "JX201", where, 0,
                    f"collective '{name}' under cond/switch "
                    f"(context {'/'.join(ctx)}) — rank-divergent branches "
                    "deadlock the mesh"))
            if "while" in ctx:
                findings.append(Finding(
                    "JX202", where, 0,
                    f"collective '{name}' under a data-dependent while "
                    f"(context {'/'.join(ctx)}) — trip counts can diverge "
                    "across ranks"))
        elif name in CALLBACK_PRIMS:
            n_callbacks += 1
        elif name == "convert_element_type" and not decl.allow_widen:
            new = eqn.params.get("new_dtype")
            aval = eqn.invars[0].aval
            src = getattr(aval, "dtype", None)
            if (src is not None and new is not None
                    and np.dtype(src) in (np.dtype(np.int32),
                                          np.dtype(np.uint32))
                    and np.dtype(new) in (np.dtype(np.int64),
                                          np.dtype(np.uint64))
                    and int(np.prod(aval.shape, dtype=np.int64))
                    >= ROW_SCALE_ELEMS):
                findings.append(Finding(
                    "JX203", where, 0,
                    f"row-scale {aval.shape} array widened "
                    f"{np.dtype(src).name}→{np.dtype(new).name} under x64 — "
                    "2x bytes through every downstream gather/collective"))
    if n_callbacks > decl.callback_budget:
        findings.append(Finding(
            "JX204", where, 0,
            f"{n_callbacks} host callback(s) in the program "
            f"(budget {decl.callback_budget})"))
    if found != decl.collectives:
        extra = sorted(found - decl.collectives)
        missing = sorted(decl.collectives - found)
        parts = []
        if extra:
            parts.append(f"undeclared collective(s) {extra}")
        if missing:
            parts.append(f"declared collective(s) {missing} absent")
        findings.append(Finding("JX205", where, 0, "; ".join(parts)))
    return findings


def verify_builder(decl: BuilderDecl, mesh) -> list[Finding]:
    """Trace one declared builder over ``mesh`` and check it."""
    try:
        traced = decl.trace(mesh)
    except Exception as e:  # noqa: BLE001 — a broken trace IS a finding
        return [Finding("JX205", decl.builder, 0,
                        f"builder trace failed: {type(e).__name__}: {e}")]
    return check_jaxpr(traced, decl)


def verify_all(mesh, decls=None) -> list[Finding]:
    from . import registry
    if decls is None:
        decls = registry.collect()
    findings = []
    for decl in decls:
        findings.extend(verify_builder(decl, mesh))
    return findings
