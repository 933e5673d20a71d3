"""Compiled-program builder cache with mesh-scoped, globally bounded
entries.

Every shard_map/jit program factory in the framework is memoized on its
static arguments.  A plain ``functools.lru_cache`` keyed on the ``Mesh``
has two hazards the trace-safety analyzer (TS104) flags:

* **pinning** — the global cache holds the Mesh (and, through the jitted
  program's closure, every executable built for it) long after the
  owning ``CylonEnv`` is gone, and keeps doing so even on jax versions
  whose Mesh interning is weak;
* **cache-miss hazard** — two structurally identical meshes are distinct
  keys only by object identity quirks, so an innocently rebuilt mesh
  silently recompiles the whole program family.

:func:`program_cache` stores the per-mesh program table **on the mesh
object itself** (a descriptor-style key): structurally equal interned
meshes share one table, and this module adds no strong global reference
to any mesh.  Note the limit of that guarantee on current jax (0.4.x):
``Mesh.__new__`` interns instances in a strong module-level dict, so
meshes — and therefore their tables — live for the process regardless
of this cache.  To keep total retained executables bounded across
processes that cycle through many meshes, a module-level LRU of mesh
tables (:data:`MESH_TABLE_LIMIT`, weakly referenced) clears the
least-recently-used mesh's programs when the population overflows —
cleared entries rebuild on demand.

The wrapper also feeds the retrace sentinel
(:mod:`cylon_tpu.analysis.runtime`): each returned program is tagged
with its builder name, static key, and mesh identity so XLA compile
events can be attributed to the op that triggered them.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict

from .. import config

#: single per-mesh attribute holding {builder_qualname: OrderedDict}
_MESH_ATTR = "_cylon_tpu_program_cache"

#: max meshes with live program tables: jax interns meshes for the
#: process lifetime, so without this LRU a mesh-cycling process would
#: retain up to PROGRAM_CACHE_SIZE programs per builder PER MESH forever
MESH_TABLE_LIMIT = 8

#: id(mesh) -> (weakref-or-mesh, table); the LRU of live tables.  Holds
#: the mesh weakly (strongly only for exotic non-weakrefable mesh types,
#: where identity must be pinned to rule out id() reuse aliasing).
_TABLES: "OrderedDict[int, tuple]" = OrderedDict()

_lock = threading.RLock()

#: the builder whose factory is running on this thread (``program_cache``
#: sets it around ``fn(mesh, ...)``): the ``jit`` calls inside are that
#: builder's programs and take its name
_building = threading.local()


def short_name(qualified: str) -> str:
    """``join__count_fn`` of ``cylon_tpu.relational.join._count_fn``:
    ``<module's last part>_<function>``, the name a builder's programs
    carry on the device (HLO module ``jit_join__count_fn``, the trace's
    ``XLA Modules`` line) and in the ``launch.<builder>`` spans."""
    mod, _, fn = qualified.rpartition(".")
    return f"{mod.rpartition('.')[2]}_{fn}" if mod else fn


def named_for_device(fun, builder: str | None = None):
    """``fun`` behind a wrapper whose ``__name__`` is the builder's short
    name (a module-level kernel's own ``<module>_<function>`` where no
    builder is being built), run under its family's root stage
    (utils/stages.ROOT_OF_MODULE).  ``jax.jit`` names the HLO module after
    ``__name__``; ``functools.wraps`` leaves ``__wrapped__`` for signature
    inspection (static/donated argument names) and the inner function —
    ``per_shard`` — keeps its own name.  Idempotent."""
    if getattr(fun, "_cylon_named", False):
        return fun
    import functools
    import re
    from . import stages
    if builder is None:
        qual = getattr(fun, "__qualname__", None)
        if not qual:
            return fun
        builder = (f"{getattr(fun, '__module__', None) or ''}."
                   f"{qual.replace('.<locals>.', '_')}")
    name = re.sub(r"\W", "_", short_name(builder))
    root = stages.ROOT_OF_MODULE.get(builder.rpartition(".")[0]
                                     .rpartition(".")[2])

    if root is not None:
        named = stages.staged(root)(fun)
    else:
        @functools.wraps(fun)
        def named(*args, **kwargs):
            return fun(*args, **kwargs)

    named.__name__ = name
    named._cylon_named = True
    return named


def _track_table(mesh, table) -> None:
    """Register a mesh's table in the global LRU; evict the oldest mesh's
    programs past MESH_TABLE_LIMIT (its table empties; entries rebuild on
    demand)."""
    def _on_collect(_r, k=id(mesh)):
        with _lock:  # RLock: safe even if GC fires inside a locked section
            _TABLES.pop(k, None)

    try:
        ref = weakref.ref(mesh, _on_collect)
    except TypeError:
        ref = mesh  # not weakrefable: pin (also rules out id() aliasing)
    _TABLES[id(mesh)] = (ref, table)
    while len(_TABLES) > MESH_TABLE_LIMIT:
        _oldest, (_ref, old_table) = _TABLES.popitem(last=False)
        n_programs = sum(len(lru) for lru in old_table.values())
        old_table.clear()
        # previously silent: the compile ledger counts the cleared
        # programs (compile_mesh_table_evict_total, docs/robustness.md)
        from ..exec import compiler
        compiler.on_table_evict(_oldest, n_programs)


def _mesh_table(mesh) -> dict:
    entry = _TABLES.get(id(mesh))
    if entry is not None:
        ref, table = entry
        referent = ref() if isinstance(ref, weakref.ref) else ref
        if referent is mesh:
            _TABLES.move_to_end(id(mesh))
            return table
        _TABLES.pop(id(mesh), None)  # id reuse after a mesh died
    table = getattr(mesh, _MESH_ATTR, None)
    if table is None:
        table = {}
        try:
            object.__setattr__(mesh, _MESH_ATTR, table)
        except (AttributeError, TypeError):
            pass  # tracked via _TABLES only
    _track_table(mesh, table)
    return table


class _LazyJit:
    """Deferred facade program: ``jax.jit`` + lifecycle wrap happen on
    the FIRST call (or attribute access), not at decoration time — so
    module-level ``@partial(jit, ...)`` kernels (ops/) never import the
    exec package mid-bootstrap."""

    # __weakref__: jax weakrefs callables it is handed (jit cache keys,
    # shard_map trace bookkeeping) — a slotted class without it fails
    # deep inside tracing with "cannot create weak reference"
    __slots__ = ("_fun", "_kw", "_prog", "_builder", "__weakref__")

    def __init__(self, fun, kw):
        self._fun = fun
        self._kw = kw
        self._prog = None
        # the builder being built NOW: by the first call it is long gone
        self._builder = getattr(_building, "name", None)

    def _resolve(self):
        prog = self._prog
        if prog is None:
            from ..exec.compiler import jit as _jit
            prog = self._prog = _jit(
                named_for_device(self._fun, self._builder), **self._kw)
        return prog

    def __call__(self, *args, **kwargs):
        return self._resolve()(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._resolve(), name)


def jit(fun=None, **kw):
    """The compile-lifecycle facade's ``jax.jit``, re-exported at the
    cache layer: operator modules bind ``jit`` from HERE at import time
    (``from ..utils.cache import jit``) because importing
    ``cylon_tpu.exec.compiler`` at module scope would pull the whole
    exec package — which imports the relational layer back (a cycle).
    The facade wrap is deferred to the first call (:class:`_LazyJit`);
    by then the exec package is importable.  Raw ``jax.jit`` outside
    this module and exec/compiler.py is a lint finding (TS117): every
    compile must ride the facade so the ledger, journal, watchdog and
    quarantine see it.  Usable directly (``jit(fn, **kw)``) or as a
    ``@partial(jit, static_argnames=...)`` decorator."""
    if fun is None:
        import functools
        return functools.partial(jit, **kw)
    return _LazyJit(fun, kw)


def program_cache(maxsize: int | None = None):
    """LRU-memoize a program factory whose FIRST argument is the Mesh.

    Per-mesh, per-builder bounded LRU (default
    ``config.PROGRAM_CACHE_SIZE``) living on the mesh object, with a
    global :data:`MESH_TABLE_LIMIT`-mesh bound — see module docstring.
    Remaining arguments must be hashable (the same contract
    ``lru_cache`` had).  Lookups are lock-protected; a concurrent miss
    may build the same program twice (harmless — last insert wins), the
    same semantics ``lru_cache`` has for in-flight calls.  The cached
    value is wrapped by the retrace sentinel's builder tag so compiles
    are attributable.
    """

    def deco(fn):
        name = f"{fn.__module__}.{fn.__qualname__}"
        limit = maxsize if maxsize is not None else config.PROGRAM_CACHE_SIZE

        def wrapper(mesh, *args, **kwargs):
            from ..analysis import runtime
            from ..exec import compiler
            key = (args, tuple(sorted(kwargs.items())) if kwargs else ())
            with _lock:
                table = _mesh_table(mesh)
                lru = table.get(name)
                if lru is None:
                    lru = table[name] = OrderedDict()
                hit = lru.get(key)
                if hit is not None:
                    lru.move_to_end(key)
            if hit is not None:
                runtime.note_builder(name, key, miss=False)
                compiler.on_hit(mesh, name, key)
                return hit
            runtime.note_builder(name, key, miss=True)
            prev = getattr(_building, "name", None)
            _building.name = name
            try:
                built = fn(mesh, *args, **kwargs)
            finally:
                _building.name = prev
            # the retrace identity includes the mesh: the same static key
            # on another mesh (tests run 1/4/8-rank worlds side by side)
            # legitimately compiles once per mesh
            mesh_ident = (tuple(mesh.axis_names),
                          tuple(d.id for d in mesh.devices.flat))
            built = runtime.tag_program(name, built, (mesh_ident, key))
            popped = []
            with _lock:
                lru[key] = built
                while len(lru) > limit:
                    popped.append(lru.popitem(last=False)[0])
            # ledger hooks run OUTSIDE the cache lock (lock order:
            # cache._lock before compiler._lock; the budget vote may
            # ride the consensus wire and must never hold either lock)
            if popped:
                compiler.on_builder_evict(mesh, name, popped)
            compiler.on_insert(mesh, name, key, lru)
            return built

        def cache_clear(mesh=None):
            with _lock:
                if mesh is not None:
                    _mesh_table(mesh).pop(name, None)
                # without a mesh there is nothing global to clear —
                # tables live on the meshes themselves

        wrapper.cache_clear = cache_clear
        # lru_cache-compatible introspection: the per-mesh, per-builder LRU
        # bound (tests assert every factory in the package is bounded)
        wrapper.cache_parameters = lambda: {"maxsize": limit, "typed": False}
        wrapper.__wrapped__ = fn
        wrapper.__name__ = fn.__name__
        wrapper.__qualname__ = fn.__qualname__
        wrapper.__doc__ = fn.__doc__
        wrapper.__module__ = fn.__module__
        wrapper._is_program_cache = True
        return wrapper

    return deco
