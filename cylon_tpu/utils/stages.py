"""Stage names on device operations — the one vocabulary.

Every program runs under its builder family's root stage
(:data:`ROOT_OF_MODULE`, opened by ``utils/cache.named_for_device`` around
the jitted callable), and the row-scale steps inside open a finer one
(:func:`stage`, :func:`staged`).
``jax.named_scope`` puts the name into the HLO instruction's ``op_name``
(``jit(join__count_fn)/cylon.join/cylon.scan/jit(cumsum)/...``), which the
profiler hands back as the ``tf_op`` of each ``XLA Ops`` event; the
**innermost** ``cylon.<stage>`` is the operation's stage
(``benchmark/lib/xspace.py``).  A fusion carries the scope of its root
instruction.  Names are metadata only
(``jax_compilation_cache_include_metadata_in_key`` is False): a scope
changes neither a program nor its cache key.

The vocabulary is closed: :func:`stage` refuses a name that is not in
:data:`STAGES`, ``PERF.md`` §3 lists it, ``tests/test_stages.py`` holds
every registered builder to it.
"""

from __future__ import annotations

import functools

PREFIX = "cylon."

#: stage -> what runs under it.  Fine stages first (steps inside a
#: program), then the root stage of each builder family.
STAGES = {
    # steps
    "sort_keys": "the multi-operand lax.sort (keys, index and payload lanes)",
    "pack": "key operands, u32 lane packing, X64 split",
    "unpack": "u32 lanes back to typed columns, X64 combine",
    "boundaries": "run/group boundary flags of sorted keys, group ids",
    "liveness": "row-liveness masks and their gather to sorted positions",
    "scan": "cumsum / cummin / cummax geometry scans",
    "segment_starts": "each group's first row position: a one-operand sort",
    "segment_gather": "the lane-matrix gather at the segment starts "
                      "(XLA's, or the windowed Pallas kernel)",
    "segment_reduce": "prefix differences, per-segment sums/extrema, finalize",
    "join_count": "match counts, output offsets and total of a join",
    "join_expand": "output-slot ownership and the join's row gathers",
    "gather_rows": "row gathers by a permutation or take index",
    "compact": "shrink / slice / compaction by flag, valid counts",
    "hash": "row hashes and partition targets",
    "exchange_place": "the exchange's send-block fill and receive placement",
    # roots, one per builder family
    "join": "relational/join.py programs (outside the steps above)",
    "groupby": "relational/groupby.py and fused.py programs",
    "sort": "relational/sort.py programs",
    "setops": "relational/setops.py programs",
    "repart": "relational/repart.py programs",
    "skew": "relational/skew.py programs",
    "sample": "key / hash sampling programs",
    "exchange": "parallel/shuffle.py, parallel/collectives.py, "
                "topo/exchange.py programs",
    "piece": "relational/piece.py programs (outside the three below)",
    "piece_pack": "relational/piece._piece_pack_fn",
    "piece_pad": "relational/piece._pad_rows_fn",
    "piece_slice": "relational/piece._piece_slice_fn",
    "pipeline": "exec/pipeline.py programs",
    "integrity": "exec/integrity.py programs",
    "consensus": "exec/recovery.py's consensus wire",
    "stream_window": "stream/window.py programs",
    "series_reduce": "series.py reductions",
}

#: last part of a builder's (or module-level kernel's) module -> the root
#: stage its programs run under; utils/cache.named_for_device opens it
#: around the jitted callable, so no builder has to
ROOT_OF_MODULE = {
    "join": "join", "fused": "groupby", "groupby": "groupby",
    "sort": "sort", "setops": "setops", "repart": "repart", "skew": "skew",
    "common": "sample", "shuffle": "exchange", "collectives": "exchange",
    "exchange": "exchange", "piece": "piece", "pipeline": "pipeline",
    "integrity": "integrity", "recovery": "consensus",
    "window": "stream_window", "series": "series_reduce",
}


def stage(name: str):
    """``jax.named_scope("cylon.<name>")`` for a name of :data:`STAGES`."""
    if name not in STAGES:
        raise ValueError(f"{name!r} is not a stage (utils/stages.STAGES)")
    import jax
    return jax.named_scope(PREFIX + name)


def staged(name: str):
    """Decorator: run the function under :func:`stage`.  ``functools.wraps``
    keeps ``__name__`` (``per_shard`` stays ``per_shard``: the Pallas custom
    call's instruction name and the benchmark's patterns depend on it)."""
    if name not in STAGES:
        raise ValueError(f"{name!r} is not a stage (utils/stages.STAGES)")

    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with stage(name):
                return fn(*args, **kwargs)
        return scoped

    return deco


# Added BELOW the functions: Mosaic's serialized kernel bodies embed the line
# numbers of the traced Python frames - ``scoped`` above among them - so a
# line moved up there is a cold compile of every windowed program (PERF.md,
# PR 30's lesson).
STAGES["expr"] = ("elementwise column expressions: arithmetic, compares, "
                  "mask logic (series._expr_fn)")
STAGES["setop_flags"] = ("set operations' row flags, read off the rank "
                         "sort's order: neighbour comparisons, liveness, "
                         "side, source address (ops/setops.py)")
