"""Per-rank phase aggregation — the min/median/max skew report.

ROADMAP item 2 (skew-proof joins) needs per-rank imbalance VISIBILITY
before any heavy-hitter mechanism can be judged: a mesh bounded by its
hottest chip shows up here as one rank's ``pipe.piece_join`` seconds
towering over the median.  This module gathers every rank's phase table
(utils/timing.snapshot) at END OF RUN and reduces it to, per phase::

    {"min_s": ..., "median_s": ..., "max_s": ..., "skew": max/median}

**Arming contract** (same as the checkpoint tier): unarmed —
``CYLON_TPU_RANK_REPORT`` unset and no :func:`arm` call — the report
never runs: zero extra collectives, zero host syncs, zero allocations
on the happy path (a caller consults :func:`armed` before calling).
Armed, the gather is ONE ``process_allgather`` of a packed float64
vector over an agreed phase-name set (name agreement verified by crc —
a rank whose phase table diverged structurally surfaces as a typed
:class:`~cylon_tpu.status.RankDesyncError`, never a silently misaligned
report).  Single-process sessions (including multi-chip
single-controller meshes, where every device is driven by one host
loop and there is no per-rank host table to diverge) reduce over one
rank without touching the network.
"""

from __future__ import annotations

import os
import zlib

__all__ = ["arm", "armed", "report"]

_ARMED: list = [False]


def arm(on: bool = True) -> None:
    _ARMED[0] = bool(on)


def armed() -> bool:
    return _ARMED[0] or os.environ.get("CYLON_TPU_RANK_REPORT") == "1"


def _local_phases() -> dict[str, float]:
    from ..utils import timing
    return {k: float(v["s"]) for k, v in timing.snapshot().items()}


def report() -> dict:
    """Build the skew report NOW (the caller decides end-of-run).  The
    gather rides the PROCESS group (``multihost_utils`` over every
    rank of the jax.distributed world — per-rank phase tables are
    per-process host state, so there is no narrower mesh to scope to);
    the caller is responsible for honoring :func:`armed` so unarmed
    runs stay collective-free."""
    import numpy as np

    local = _local_phases()
    names = sorted(local)
    vec = np.asarray([local[n] for n in names], np.float64)

    import jax
    nproc = jax.process_count()
    if nproc > 1:
        from jax.experimental import multihost_utils
        from ..status import RankDesyncError
        crc = np.float64(zlib.crc32("|".join(names).encode()))
        wire = np.concatenate([[crc], vec])
        gathered = np.asarray(
            multihost_utils.process_allgather(wire)).reshape(nproc, -1)
        if len({float(r[0]) for r in gathered}) != 1:
            raise RankDesyncError(
                "per-rank phase report: phase-name sets differ across "
                "ranks — the ranks timed different programs",
                site="obs.rank_report")
        table = gathered[:, 1:]
    else:
        table = vec.reshape(1, -1)

    phases = {}
    for i, n in enumerate(names):
        col = table[:, i]
        med = float(np.median(col))
        phases[n] = {
            "min_s": round(float(col.min()), 4),
            "median_s": round(med, 4),
            "max_s": round(float(col.max()), 4),
            "skew": round(float(col.max()) / med, 3) if med > 0 else None,
        }
    return {"ranks": int(table.shape[0]), "phases": phases}
