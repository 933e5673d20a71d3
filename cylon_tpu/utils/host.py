"""Host materialization that works in BOTH execution modes.

Single-controller (one process drives the whole mesh): ``np.asarray`` sees
every shard.  Multi-controller (``jax.distributed`` SPMD — the reference's
``mpirun -np N`` launch model, README.md:69-73): each process only
addresses its local shards, so sidecar pulls (count matrices, valid-count
vectors, splitter samples) must cross-gather with
``multihost_utils.process_allgather`` before they are host-visible.  Every
host pull of a possibly-sharded device array in the framework goes through
:func:`host_array` so the same operator code runs in either mode.
"""

from __future__ import annotations

import contextlib

import numpy as np

from . import timing


def _nbytes(xs) -> int:
    return sum(int(getattr(x, "nbytes", 0) or 0) for x in xs)


@contextlib.contextmanager
def _sanctioned_pull(kind: str, nbytes: int = 0):
    """The DOCUMENTED device→host boundary: every framework host pull runs
    inside this scope, so test sessions can run under
    ``jax.transfer_guard_device_to_host("disallow")``
    (``CYLON_TPU_TRACECHECK=1``) and still permit the sidecar pulls this
    module funnels — any implicit D2H transfer *outside* this funnel is a
    trace-safety violation.  Also feeds the per-op transfer ledger
    (:func:`cylon_tpu.analysis.runtime.note_transfer`, rule RT303), and is
    the ``pull.<kind>`` span (``bytes=``): the host time a query spends
    WAITING for the device inside the program, and how often."""
    import jax
    from ..analysis import runtime
    runtime.note_transfer(kind)
    with timing.span("pull." + kind, bytes=int(nbytes)), \
            jax.transfer_guard_device_to_host("allow"):
        yield


def host_array(x) -> np.ndarray:
    """Materialize a (possibly multi-host row-sharded) array on this host."""
    if isinstance(x, np.ndarray):
        return x
    import jax
    if jax.process_count() > 1 and not getattr(x, "is_fully_addressable",
                                               True):
        from jax.experimental import multihost_utils
        with _sanctioned_pull("host_array", _nbytes((x,))):
            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    with _sanctioned_pull("host_array", _nbytes((x,))):
        return np.asarray(x)


def host_arrays(xs) -> list:
    """Batched :func:`host_array`: ONE overlapped fetch for many device
    arrays.  A sequential pull pays the device-to-host latency once per
    buffer; ``jax.device_get`` issues every copy async before blocking,
    collapsing N round-trips into about one.
    Entries may be numpy arrays or None (passed through)."""
    import jax
    if jax.process_count() > 1:
        return [None if x is None else host_array(x) for x in xs]
    devs = [x for x in xs if x is not None and not isinstance(x, np.ndarray)]
    with _sanctioned_pull("host_arrays", _nbytes(devs)):
        fetched = iter(jax.device_get(devs))
    return [x if x is None or isinstance(x, np.ndarray) else next(fetched)
            for x in xs]


def host_shard_blocks(x, world: int) -> list:
    """Per-shard host blocks of a row-sharded array WITHOUT any
    cross-process collective: each process pulls only its ADDRESSABLE
    shards (entries for remote shards stay None).  This is the spill
    tier's eviction transport (cylon_tpu.exec.memory): collective-free
    by construction, so a rank whose eviction candidates momentarily
    diverge from its peers' (GC timing) cannot hang the mesh the way a
    ``process_allgather``-based pull would.  Numpy inputs pass through
    as a single block."""
    if isinstance(x, np.ndarray):
        return [x]
    per = x.shape[0] // world
    blocks: list = [None] * world
    with _sanctioned_pull("host_shards", _nbytes((x,))):
        for sh in x.addressable_shards:
            i = (sh.index[0].start or 0) // per
            blocks[i] = np.asarray(sh.data)
    return blocks


def sync_pull(arr) -> None:
    """Force execution of everything feeding ``arr`` and wait.

    The ONE barrier of the bench drivers (benchmark/, scripts/*,
    chip_smoke.py): ``jax.block_until_ready``, which the directly
    attached runtime honours — no tiny host pull rides behind it."""
    import jax
    with timing.span("pull.sync"):
        jax.block_until_ready(arr)
