"""HBM budget ledger + host spill tier — graceful degradation under
memory pressure.

The paper's answer to a distributed operator outgrowing device memory is
abort-and-rerun; PR 3's consensus retry ladder improved that to
*recompute at higher chunk counts* or *halve piece caps* — both throw
away completed device work, and neither knows how much HBM is actually
held by resident state.  This module closes that gap with the same
mechanism a training stack uses for activation offload:

1. **HBM budget ledger** (:class:`Ledger`): every long-lived resident
   allocation — packed lane matrices and f64 side arrays
   (:class:`~cylon_tpu.relational.piece.PieceSource`), GroupBySink
   partials, exchange receive buffers — registers its byte count under a
   deterministic owner name.  The ledger is consulted by the exchange
   receive-budget guard (:mod:`cylon_tpu.parallel.shuffle`) and by the
   pipelined join's piece working-set sizing, against a budget from
   ``CYLON_TPU_HBM_BUDGET`` (total bytes across the mesh) with a
   platform-detected default (per-chip ``bytes_limit`` × device count on
   accelerators; unlimited on CPU).

2. **Host spill tier**: cold spillable registrations evict to host RAM
   — LRU by last piece-loop access (:func:`touch`), per-shard pulls
   through the sanctioned :mod:`cylon_tpu.utils.host` funnel
   (``host_shard_blocks``: each process reads only its addressable
   shards, so the transport is collective-free) — and re-enter the
   device *per window*
   (:func:`upload_window`): a host-resident
   :class:`~cylon_tpu.relational.piece.PieceSource` uploads only the
   current range piece's rows, and the pipelined join's range loop
   double-buffers so piece r+1's upload overlaps piece r's compute.
   Spill round-trips are bit-exact (u32/f64 arrays move unchanged).

3. **Collective coherence**: eviction is a COLLECTIVE decision.  A
   rank-local eviction would change that rank's guard predicates and
   retry branches while its peers proceed — the same desync a
   rank-local retry causes — and the eviction's own host pulls are
   collectives in a multiprocess session.  Registrations and LRU order
   advance at uniform program points, but a raw balance READ is uniform
   only up to GC release timing, so no multiprocess decision gates on
   it: admission polls whenever a budget is configured, agrees on the
   eviction COUNT (max of each rank's deterministic
   :meth:`Ledger.evict_count_for`) over the PR 3 consensus wire
   (:func:`cylon_tpu.exec.recovery.count_consensus`), and every rank
   then evicts that many oldest owners — same owners, same order
   (asserted cross-rank by ``tests/multihost_driver.py``).  The
   ladder's spill rung agrees its take-the-rung decision the same way
   (:func:`~cylon_tpu.exec.recovery.spill_consensus`), and rank-local
   shortcuts (:func:`try_free`) are single-controller only.

4. **Ladder integration**: ``run_with_recovery`` gains a new FIRST rung
   — *spill-then-retry at the same chunk count*
   (:func:`spill_for_retry`) — so a
   :class:`~cylon_tpu.status.PredictedResourceExhausted` first tries to
   free resident bytes without discarding any completed work; chunk
   escalation remains the backstop (docs/robustness.md).

5. **Disk tier** (the residency ladder's FINAL rung — docs/robustness.md
   "Disk tier & scan pushdown"): a second, HOST-side budget
   (``CYLON_TPU_HOST_BUDGET``) bounds the host-resident spill pages.
   When device→host evictions push the host balance past it, cold host
   pages DEMOTE to per-rank spill files under ``CYLON_TPU_SPILL_DIR``
   (one ``.spill.npy`` page per array per addressable shard, sha256 over
   the page content — the same bit-exact round-trip contract as
   checkpoints, except spill pages are PROCESS-transient: hashes live in
   memory and a fresh process never reads a predecessor's files).
   Promotion is ON-TOUCH: a piece access of a disk-resident source
   verifies the owner's pages once (full sequential read, streamed —
   never the whole working set in RAM) and then windows read straight
   off memory-mapped pages through the same :func:`upload_window`
   double-buffering the host tier uses, so piece r+1's disk reads
   overlap piece r's compute.  Demote decisions ride the SAME
   rank-coherent count-consensus wire as evictions (same owners, same
   order on every rank).  Robustness: page writes/reads take the bounded
   IO retry (:func:`cylon_tpu.exec.recovery.retry_io`); a failed or
   ENOSPC'd demotion degrades to keeping the page host-resident (typed
   recovery event, never a crash); a corrupt page on promote surfaces as
   a typed :class:`~cylon_tpu.status.CheckpointCorruptError` at site
   ``disk.read`` and the ladder recomputes that owner's stage (never a
   wrong answer); a stalled page transfer surfaces via the exchange
   watchdog as a typed RankDesyncError.  Injector sites ``disk.write``
   (kinds ``corrupt``/``stall``/``enospc``/``kill``) and ``disk.read``
   (``corrupt``/``stall``) make every path testable on the CPU rig.

Escape hatches: ``CYLON_TPU_SPILL=0`` disables eviction entirely (the
ledger keeps accounting); ``CYLON_TPU_HBM_BUDGET`` overrides the
detected budget.  With spill disabled and no faults armed, the happy
path through :func:`ensure_headroom` is a couple of dict lookups — no
collectives, no host syncs; with ``CYLON_TPU_HOST_BUDGET`` unset the
disk tier adds ZERO filesystem writes (asserted in tests/test_memory.py
and the chaos ``--oocore`` happy-path leg).

Trace-safety notes: this module is the ONE sanctioned place that
changes residency of lane-sized arrays (TS106) — a bare
``jax.device_put``/``jax.device_get`` in ``relational/`` or
``parallel/`` bypasses the ledger and is a lint finding — AND the one
sanctioned place that constructs spill-file paths or does raw spill
page IO (TS114): a direct ``open``/``np.save`` of a spill page
elsewhere would skip the sha contract, the bounded IO retry and the
demote/promote accounting.
"""

from __future__ import annotations

import errno
import hashlib
import os
import re
import threading
import weakref

import numpy as np

from .. import config
from ..status import CheckpointCorruptError
from ..utils import timing

#: injector kinds at the spill sites that RAISE as typed faults (the
#: rest — ``predicted`` = simulated pressure, ``spill_stall``/``stall``
#: = simulated transfer hang — steer the spill machinery instead)
_RAISE_KINDS = ("device_oom", "capacity", "desync")


def _spill_enabled() -> bool:
    return config.SPILL_ENABLED


def _session_tag() -> str | None:
    """The serving session tagged on this thread (exec/recovery holds the
    thread-local identity the scheduler sets), or None outside one."""
    from . import recovery
    return recovery.current_session()


# ---------------------------------------------------------------------------
# budget
# ---------------------------------------------------------------------------

_BUDGET_CACHE: list = []  # [int] once detected; empty = not yet probed

#: per-chip HBM for devices whose ``memory_stats()`` has no
#: ``bytes_limit`` (Google Cloud documentation, "TPU v5e": 16 GB).  A
#: device that is not here is an error, never a default.
_HBM_BYTES_BY_KIND = {"TPU v5 lite": 16 * 1024**3}


def budget_bytes() -> int:
    """The ledger's budget in TOTAL bytes across the mesh: the
    ``CYLON_TPU_HBM_BUDGET`` override when set, else per-chip
    ``bytes_limit`` (or the ``_HBM_BYTES_BY_KIND`` entry) × device count
    on accelerators — an unknown device kind raises — else 0 (unlimited:
    CPU rigs where host RAM, not HBM, is the ceiling).  Detected lazily
    (the backend must already be initialized) and cached."""
    if config.HBM_BUDGET_BYTES > 0:
        return config.HBM_BUDGET_BYTES
    if _BUDGET_CACHE:
        return _BUDGET_CACHE[0]
    import jax
    total = 0
    try:
        devs = jax.devices()
    except Exception:  # noqa: BLE001 — no backend yet: stay unlimited
        return 0
    if devs and devs[0].platform != "cpu":
        per = int((devs[0].memory_stats() or {}).get("bytes_limit", 0))
        if not per:
            per = _HBM_BYTES_BY_KIND.get(devs[0].device_kind, 0)
        if not per:
            raise RuntimeError(
                f"device kind {devs[0].device_kind!r} reports no "
                "bytes_limit and is not in exec/memory._HBM_BYTES_BY_KIND:"
                " set CYLON_TPU_HBM_BUDGET or add it to the table")
        total = per * len(devs)
    _BUDGET_CACHE.append(total)
    return total


# ---------------------------------------------------------------------------
# registrations + ledger
# ---------------------------------------------------------------------------

def _nbytes(arrays) -> int:
    return sum(int(np.prod(a.shape, dtype=np.int64))
               * int(np.dtype(a.dtype).itemsize) for a in arrays
               if a is not None)


class Registration:
    """One resident allocation's ledger entry — also the owner's HANDLE
    to its arrays: spillable owners read their device arrays through
    :attr:`arrays` (None while spilled) so eviction can actually drop
    the device references.  ``host`` (while spilled) is a tuple of
    PER-SHARD host block lists (``utils.host.host_shard_blocks``): each
    process holds only its addressable shards, which keeps both the
    eviction pull and the re-upload collective-free."""

    __slots__ = ("owner", "nbytes", "spillable", "seq", "arrays", "host",
                 "disk", "disk_ok", "disk_views", "sharding", "world",
                 "live", "session", "__weakref__")

    def __init__(self, owner: str, arrays, spillable: bool, sharding,
                 seq: int):
        self.owner = owner
        self.nbytes = _nbytes(arrays)
        self.spillable = bool(spillable)
        # the serving session whose turn allocated this (None outside a
        # scheduler): eviction under another tenant's admission pressure
        # is a CROSS-tenant eviction, counted separately in stats()
        self.session = _session_tag()
        # only a SPILLABLE entry holds its arrays (it must be able to
        # drop the device references on eviction); a bookkeeping-only
        # entry keeping them would pin its own anchor and never drain
        self.arrays = tuple(arrays) if spillable else ()
        self.sharding = sharding
        self.world = (int(sharding.mesh.devices.size)
                      if sharding is not None else 1)
        self.seq = seq
        self.host: tuple | None = None
        #: disk-tier page table while demoted (per-array tuples of
        #: per-shard ``{"path", "sha", "nbytes"}`` entries, None for
        #: remote shards); ``disk_ok`` records the one on-touch sha
        #: verification per demote cycle (windows mmap after it), and
        #: ``disk_views`` caches the post-verification mmap views so a
        #: P-piece loop opens each page once, not P times
        self.disk: tuple | None = None
        self.disk_ok = False
        self.disk_views: tuple | None = None
        self.live = True

    @property
    def spilled(self) -> bool:
        """Off-device: host-resident (spill tier) OR disk-resident."""
        return self.host is not None or self.disk is not None

    @property
    def on_disk(self) -> bool:
        return self.disk is not None


class Ledger:
    """Owner-named byte accounting for resident device allocations, with
    LRU host eviction of spillable entries.  All state transitions are
    deterministic functions of the (rank-uniform) registration and
    access sequence, so a multiprocess session's ledgers stay identical
    across ranks by construction."""

    def __init__(self):
        self._live: dict[str, Registration] = {}
        self._lock = threading.RLock()
        self._seq = 0
        self._names = 0
        self.peak = 0

    # -- accounting --------------------------------------------------------
    def balance(self) -> int:
        with self._lock:
            return sum(r.nbytes for r in self._live.values()
                       if not r.spilled)

    def spillable_bytes(self) -> int:
        with self._lock:
            return sum(r.nbytes for r in self._live.values()
                       if r.spillable and not r.spilled)

    def host_balance(self) -> int:
        """Bytes of live registrations currently HOST-resident (spilled
        to RAM, not yet demoted to disk) — the disk tier's budget
        predicate (``CYLON_TPU_HOST_BUDGET``)."""
        with self._lock:
            return sum(r.nbytes for r in self._live.values()
                       if r.host is not None)

    def owners(self) -> list[str]:
        with self._lock:
            return sorted(self._live, key=lambda o: self._live[o].seq)

    # -- registration lifecycle --------------------------------------------
    def register(self, base: str, arrays, spillable: bool = False,
                 sharding=None, anchor=None) -> Registration:
        """Register a resident allocation under a deterministic owner
        name ``base#<n>`` (the counter advances identically on every
        rank).  ``anchor``: auto-release when this object is collected
        (the registration must not outlive — or leak past — its owner)."""
        with self._lock:
            self._names += 1
            self._seq += 1
            reg = Registration(f"{base}#{self._names}", arrays, spillable,
                               sharding, self._seq)
            self._live[reg.owner] = reg
            self.peak = max(self.peak, self.balance())
        if anchor is not None:
            try:
                weakref.finalize(anchor, self.release, reg)
            except TypeError:
                pass  # not weakrefable: caller releases explicitly
        return reg

    def touch(self, reg: Registration | None) -> None:
        """LRU bump: record a piece-loop access of this registration."""
        if reg is None or not reg.live:
            return
        with self._lock:
            self._seq += 1
            reg.seq = self._seq

    def release(self, reg: Registration | None) -> None:
        """Drop a registration (idempotent): device, host and disk
        copies are unpinned (spill page files deleted best-effort) and
        the balance drains — never below zero."""
        if reg is None or not reg.live:
            return
        with self._lock:
            reg.live = False
            self._live.pop(reg.owner, None)
            reg.arrays = ()
            reg.host = None
            disk, reg.disk = reg.disk, None
            reg.disk_ok = False
            reg.disk_views = None
        if disk is not None:
            _remove_disk_pages(disk)

    # -- spill tier --------------------------------------------------------
    def evict(self, reg: Registration, stall: bool = False) -> int:
        """Move one spillable registration's arrays to host RAM — a
        PER-SHARD, collective-free pull (each process reads only its
        addressable shards; ``utils.host.host_shard_blocks``) under the
        exchange watchdog — and drop the device references.  Returns the
        bytes freed (0 if not evictable).  Bit-exact: the arrays are raw
        u32 lane matrices / f64 side channels."""
        if not (reg.live and reg.spillable and not reg.spilled
                and reg.arrays):
            return 0
        from . import recovery
        from ..utils.host import host_shard_blocks
        devs, w = list(reg.arrays), reg.world
        with timing.region("spill.evict"):
            # stalled is passed explicitly (never probed): a spill-site
            # eviction must not consume `exchange.stall` injections meant
            # for the exchange path
            host = recovery.exchange_watchdog(
                "spill.evict",
                lambda: tuple(host_shard_blocks(a, w) for a in devs),
                timeout_s=_stall_timeout(stall), stalled=stall)
        with self._lock:
            reg.host = host
            reg.arrays = ()
        _note_spill("spill.evict", reg)
        return reg.nbytes

    def readmit(self, reg: Registration, stall: bool = False) -> tuple:
        """Re-upload a spilled registration's FULL arrays to the device
        (the whole-matrix complement of the per-window
        :func:`upload_window` path) and return them.  A DISK-resident
        registration first promotes its pages back to host (sha-verified
        full read, :meth:`promote_host`).  Not on the overlap-critical
        path, so with ``CYLON_TPU_WATCHDOG_S`` armed the readiness check
        blocks under the watchdog — a hung transfer surfaces typed at
        ``spill.upload``."""
        if not (reg.live and reg.spilled):
            return reg.arrays
        if reg.host is None:
            self.promote_host(reg, stall=stall)
        arrs = _upload(list(reg.host), reg.sharding, stall=stall)
        if config.EXCHANGE_WATCHDOG_S > 0 and not stall:
            import jax
            from . import recovery
            recovery.exchange_watchdog(
                "spill.upload", lambda: jax.block_until_ready(list(arrs)),
                stalled=False)
        with self._lock:
            reg.arrays = tuple(arrs)
            reg.host = None
            self._seq += 1
            reg.seq = self._seq
            self.peak = max(self.peak, self.balance())
        _STATS["readmit_events"] += 1
        _STATS["bytes_readmitted"] += reg.nbytes
        timing.add_bytes("spill.upload", reg.nbytes)
        return reg.arrays

    # -- disk tier (host → spill files → back) -----------------------------
    def demote(self, reg: Registration, stall: bool = False) -> int:
        """Move one HOST-resident registration's pages to per-rank spill
        files — the residency ladder's final rung.  One ``.spill.npy``
        page per array per addressable shard, sha256 over the page
        content recorded in the (in-memory) page table; writes take the
        bounded IO retry.  Returns the bytes moved off host RAM.

        Degrades, never crashes: a write that still fails after the
        retry budget (ENOSPC, quota, a dead disk) abandons the demotion
        — partial pages are deleted, the registration STAYS
        host-resident, and a typed ``disk.write`` recovery event records
        the degrade.  An injected ``stall`` (or a real hang surfaced the
        same way) raises typed through the exchange watchdog; ``corrupt``
        flips a byte of the first page AFTER hashing so the promote-side
        verification catches it; ``kill`` is the chaos harness's
        mid-demote crash."""
        if not (reg.live and reg.host is not None):
            return 0
        from . import recovery
        kind = recovery.maybe_inject(
            "disk.write", intercept=("corrupt", "stall", "enospc"))
        root = _rank_spill_dir()
        safe = _safe_owner(reg.owner)
        written: list[str] = []
        first = [True]

        def write_all():
            out = []
            for j, blocks in enumerate(reg.host):
                per = []
                for k, blk in enumerate(blocks):
                    if blk is None:
                        per.append(None)
                        continue
                    path = os.path.join(root, f"{safe}.a{j}.s{k}.spill.npy")
                    if kind == "enospc" and first[0]:
                        raise OSError(errno.ENOSPC,
                                      "injected ENOSPC mid-demote")
                    sha = _sha_arr(blk)
                    recovery.retry_io(lambda p=path, b=blk: np.save(p, b),
                                      "disk.write", on_retry=_note_retry)
                    written.append(path)
                    if kind == "corrupt" and first[0]:
                        # flip a DATA byte after hashing: the promote
                        # verification must catch it (the acceptance
                        # path for corrupt-on-promote → recompute)
                        _flip_last_byte(path)
                    first[0] = False
                    per.append({"path": path, "sha": sha,
                                "nbytes": int(blk.nbytes)})
                out.append(tuple(per))
            return tuple(out)

        try:
            with timing.region("disk.write"):
                if stall or kind == "stall":
                    meta = recovery.exchange_watchdog(
                        "disk.write", write_all,
                        timeout_s=_stall_timeout(True), stalled=True)
                else:
                    meta = write_all()
        except OSError as e:
            _remove_paths(written)
            is_enospc = e.errno == errno.ENOSPC
            _DSTATS["write_degrades"] += 1
            recovery._record("disk.write",
                             "enospc" if is_enospc else "os_error",
                             "degrade_in_memory")
            from ..utils.logging import log
            log.warning("memory: demotion of %s to disk failed (%s); page "
                        "stays host-resident — degraded, not crashed",
                        reg.owner, e)
            return 0
        except BaseException:
            # typed stall/desync (or anything else) propagates — but the
            # pages already written must not strand on disk (best-effort:
            # a watchdogged writer thread may still be mid-write; the
            # first-use purge above is the backstop)
            _remove_paths(list(written))
            raise
        with self._lock:
            reg.disk = meta
            reg.host = None
            reg.disk_ok = False
            reg.disk_views = None
        moved = sum(e["nbytes"] for per in meta for e in per
                    if e is not None)
        _DSTATS["events"] += 1
        _DSTATS["bytes_demoted"] += moved
        # counted only on SUCCESS: a degraded demotion wrote no durable
        # pages the accounting should claim
        _DSTATS["pages_demoted"] += sum(1 for per in meta for e in per
                                        if e is not None)
        _DEMOTION_LOG.append(reg.owner)
        timing.add_bytes("disk.write", moved)
        timing.bump("memory.disk.demote")
        from ..utils.logging import log
        log.info("memory: %s -> disk (%d B, %s)", reg.owner, moved, root)
        return moved

    def verify_disk(self, reg: Registration, stall: bool = False) -> None:
        """The on-touch promotion gate: sha-verify EVERY page of a
        disk-resident registration once per demote cycle (streamed —
        one page in RAM at a time), after which window reads mmap the
        pages directly.  A mismatch (or an injected ``corrupt`` at site
        ``disk.read``) retires the poisoned owner (released, files
        deleted) and raises a typed :class:`CheckpointCorruptError` —
        the recovery ladder recomputes that owner's stage; corruption
        degrades to recompute, never to a wrong answer."""
        if reg.disk is None or reg.disk_ok:
            return
        from . import recovery
        kind = recovery.maybe_inject("disk.read",
                                     intercept=("corrupt", "stall"))

        def check():
            if kind == "corrupt":
                raise CheckpointCorruptError(
                    "injected spill-page corruption on promote",
                    site="disk.read")
            for per in reg.disk:
                for ent in per:
                    if ent is None:
                        continue
                    arr = _read_page(ent["path"])
                    if _sha_arr(arr) != ent["sha"]:
                        raise CheckpointCorruptError(
                            f"spill page {ent['path']} failed its "
                            "content-hash check (torn write or on-disk "
                            "corruption)", site="disk.read")

        try:
            with timing.region("disk.read"):
                if stall or kind == "stall":
                    recovery.exchange_watchdog(
                        "disk.read", check,
                        timeout_s=_stall_timeout(True), stalled=True)
                else:
                    check()
        except CheckpointCorruptError:
            _DSTATS["corrupt_degrades"] += 1
            recovery._record("disk.read", "corrupt", "recompute_owner")
            self.release(reg)
            raise
        reg.disk_ok = True

    def promote_host(self, reg: Registration, stall: bool = False) -> None:
        """Full disk → host promotion (sha-verified): read every page
        back into host block lists and delete the spill files — the
        whole-owner complement of the per-window mmap reads."""
        if reg.disk is None:
            return
        self.verify_disk(reg, stall=stall)
        moved = 0
        with timing.region("disk.read"):
            hosts = []
            for per in reg.disk:
                blocks: list = []
                for ent in per:
                    if ent is None:
                        blocks.append(None)
                        continue
                    arr = _read_page(ent["path"])
                    blocks.append(arr)
                    moved += int(arr.nbytes)
                    _DSTATS["pages_promoted"] += 1
                hosts.append(blocks)
        with self._lock:
            disk, reg.disk = reg.disk, None
            reg.host = tuple(hosts)
            reg.disk_ok = False
            reg.disk_views = None
        _remove_disk_pages(disk)
        _DSTATS["events"] += 1
        _DSTATS["bytes_promoted"] += moved
        timing.add_bytes("disk.read", moved)
        timing.bump("memory.disk.promote")

    def _demote_cands(self) -> list[Registration]:
        """Host-resident entries, oldest ``seq`` first — the
        deterministic LRU demotion order (mirrors :meth:`_spill_cands`
        one rung down)."""
        with self._lock:
            return sorted((r for r in self._live.values()
                           if r.host is not None), key=lambda r: r.seq)

    def demote_count_for(self, budget: int) -> int:
        """How many LRU demotions bring the host balance under the host
        budget — the number, not the balance, is what multiprocess
        sessions agree on (max across ranks), exactly like
        :meth:`evict_count_for` one rung up."""
        if budget <= 0:
            return 0
        bal = self.host_balance()
        if bal <= budget:
            return 0
        n = 0
        for r in self._demote_cands():
            n += 1
            bal -= r.nbytes
            if bal <= budget:
                break
        return n

    def demote_n(self, n: int) -> list[str]:
        """Demote the ``n`` oldest host-resident entries (fewer if the
        ledger has fewer candidates).  Returns the demoted owner names
        in demotion order — identical on every rank by construction."""
        out: list[str] = []
        for reg in self._demote_cands()[:max(int(n), 0)]:
            if self.demote(reg):
                out.append(reg.owner)
        return out

    def _spill_cands(self) -> list[Registration]:
        """Spillable, still-resident entries, oldest ``seq`` first — the
        deterministic LRU eviction order."""
        with self._lock:
            return sorted((r for r in self._live.values()
                           if r.spillable and not r.spilled),
                          key=lambda r: r.seq)

    def evict_count_for(self, need: int, budget: int) -> int:
        """How many LRU evictions bring ``balance + need`` under the
        budget (0 when already under or no budget; all candidates when
        even that is insufficient).  A pure function of the ledger — the
        number, not the balance, is what multiprocess sessions agree on
        (max across ranks) before anyone evicts."""
        if budget <= 0:
            return 0
        bal = self.balance()
        if bal + need <= budget:
            return 0
        n = 0
        for r in self._spill_cands():
            n += 1
            bal -= r.nbytes
            if bal + need <= budget:
                break
        return n

    def evict_n(self, n: int, stall: bool = False) -> list[str]:
        """Evict the ``n`` oldest spillable entries (fewer if the ledger
        has fewer candidates).  Returns the evicted owner names in
        eviction order — identical on every rank by construction."""
        evicted: list[str] = []
        for reg in self._spill_cands()[:max(int(n), 0)]:
            if self.evict(reg, stall=stall):
                evicted.append(reg.owner)
        return evicted

    def evict_until(self, need: int, budget: int,
                    stall: bool = False) -> list[str]:
        """Deterministic LRU eviction until ``balance + need`` fits the
        budget (single-controller convenience for
        :func:`evict_count_for` + :func:`evict_n`)."""
        return self.evict_n(self.evict_count_for(need, budget),
                            stall=stall)


_LEDGER = Ledger()


def ledger() -> Ledger:
    return _LEDGER


# ---------------------------------------------------------------------------
# module-level conveniences (the public surface operators use)
# ---------------------------------------------------------------------------

def register(base: str, arrays, spillable: bool = False, sharding=None,
             anchor=None) -> Registration:
    return _LEDGER.register(base, arrays, spillable=spillable,
                            sharding=sharding, anchor=anchor)


def register_table(base: str, table, anchor=None) -> Registration | None:
    """Account a materialized Table's columns (data + validity) under one
    owner; ``anchor`` defaults to the table itself so GC drains the
    ledger (tests assert balance returns to zero after release).
    Unmaterialized DeferredTables are skipped — forcing their thunk here
    would defeat the fused pushdown they exist for."""
    from ..core.table import DeferredTable
    if isinstance(table, DeferredTable) and not table.materialized:
        return None
    arrays = []
    for c in table.columns.values():
        arrays.append(c.data)
        if c.validity is not None:
            arrays.append(c.validity)
    return _LEDGER.register(base, arrays,
                            anchor=table if anchor is None else anchor)


def release(reg) -> None:
    _LEDGER.release(reg)


def touch(reg) -> None:
    _LEDGER.touch(reg)


def device_arrays(reg: Registration) -> tuple | None:
    """The registration's device arrays, or None while spilled."""
    return reg.arrays if not reg.spilled else None


def evict(reg) -> int:
    return _LEDGER.evict(reg)


def readmit(reg) -> tuple:
    return _LEDGER.readmit(reg)


def balance() -> int:
    return _LEDGER.balance()


def over_budget(need: int) -> bool:
    """Would admitting ``need`` more resident bytes exceed the budget?
    Rank-uniform: balance, need and budget are identical across ranks."""
    b = budget_bytes()
    return b > 0 and _LEDGER.balance() + int(need) > b


def try_free(need: int) -> int:
    """Best-effort eviction of ``need`` bytes of headroom at a guard
    call site.  SINGLE-CONTROLLER only: a multiprocess session returns 0
    and defers all eviction to the consensus'd admission path
    (:func:`ensure_headroom`) — the local balance read that would gate a
    rank-local eviction here is only uniform up to GC timing, and the
    eviction's host pulls are themselves collectives, so a rank evicting
    alone would hang its peers.  Returns bytes freed."""
    if not _spill_enabled():
        return 0
    import jax
    if jax.process_count() > 1:
        return 0
    before = _LEDGER.balance()
    _LEDGER.evict_until(int(need), budget_bytes())
    return before - _LEDGER.balance()


def spillable_bytes() -> int:
    return _LEDGER.spillable_bytes()


def host_balance() -> int:
    return _LEDGER.host_balance()


def demote(reg) -> int:
    return _LEDGER.demote(reg)


def promote_host(reg) -> None:
    _LEDGER.promote_host(reg)


# ---------------------------------------------------------------------------
# disk tier plumbing (TS114: the ONE sanctioned spill-file IO site)
# ---------------------------------------------------------------------------

def _disk_armed() -> bool:
    """The disk tier engages only when a host budget is configured (and
    spilling is on) — rank-uniform by construction (config, not a
    balance read), so consensus-poll gating may key on it."""
    return config.SPILL_ENABLED and config.HOST_BUDGET_BYTES > 0


_SPILL_ROOT: list[str] = []  # [path] once resolved; empty = not yet


def spill_root() -> str:
    """The spill-file root: ``CYLON_TPU_SPILL_DIR``, else a private temp
    directory created lazily on the first demote (so an unarmed run
    never touches the filesystem)."""
    if config.SPILL_DIR:
        return config.SPILL_DIR
    if not _SPILL_ROOT:
        import tempfile
        _SPILL_ROOT.append(tempfile.mkdtemp(prefix="cylon_tpu_spill_"))
    return _SPILL_ROOT[0]


_PURGED_DIRS: set = set()


def _rank_spill_dir() -> str:
    """This process's per-rank spill directory (created on demand).  On
    FIRST use of a given directory this process purges any ``.spill.npy``
    orphans a crashed/killed predecessor left behind: spill pages are
    process-transient by contract (hashes live in memory — a fresh
    process never reads a predecessor's files), so without the purge a
    fixed ``CYLON_TPU_SPILL_DIR`` volume would accumulate orphans run
    over run until a real ENOSPC degrades every future demotion.
    (Concurrent processes of the SAME rank must use distinct spill
    roots — the default private temp dir does — since owner names
    repeat across processes.)"""
    import glob as _glob
    import jax
    d = os.path.join(spill_root(), f"rank{jax.process_index()}")
    os.makedirs(d, exist_ok=True)
    if d not in _PURGED_DIRS:
        _PURGED_DIRS.add(d)
        _remove_paths(_glob.glob(os.path.join(d, "*.spill.npy")))
    return d


_SAFE_OWNER_RE = re.compile(r"[^A-Za-z0-9_.-]")


def _safe_owner(owner: str) -> str:
    return _SAFE_OWNER_RE.sub("_", owner)


def _sha_arr(a) -> str:
    """sha256 over an array's raw content bytes — the spill pages' half
    of the checkpoint tier's bit-exact round-trip contract."""
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def _flip_last_byte(path: str) -> None:
    """Corrupt a written page in place (injection support): XOR the LAST
    file byte — data, not the npy header — after the content hash was
    computed over the good bytes."""
    with open(path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))


def _read_page(path: str):
    """One page file → array, under the bounded IO retry; a page that is
    still unreadable after the budget surfaces as the same typed
    corruption the hash check raises (an absent page IS corruption of
    the owner's disk state).  ValueError/EOFError cover the TORN-page
    shapes np.load raises itself (truncated data → reshape mismatch,
    truncated npy header) — a torn write must end typed → recompute,
    never an unhandled crash."""
    from . import recovery
    try:
        return recovery.retry_io(lambda: np.load(path), "disk.read",
                                 on_retry=_note_retry)
    except (OSError, ValueError, EOFError) as e:
        raise CheckpointCorruptError(
            f"spill page {path} unreadable or torn: {e}",
            site="disk.read") from e


def _mmap_page(path: str):
    """Memory-mapped page view for window reads (post-verification):
    row slices touch only the pages the window covers — the disk tier's
    out-of-core read path.  Same torn-page conversion as
    :func:`_read_page` (a too-short file fails the mmap length check
    with ValueError)."""
    from . import recovery
    try:
        return recovery.retry_io(lambda: np.load(path, mmap_mode="r"),
                                 "disk.read", on_retry=_note_retry)
    except (OSError, ValueError, EOFError) as e:
        raise CheckpointCorruptError(
            f"spill page {path} unreadable or torn: {e}",
            site="disk.read") from e


def _remove_paths(paths) -> None:
    for p in paths:
        try:
            os.remove(p)
        except OSError:
            pass  # best-effort cleanup; a leftover file is never re-read


def _remove_disk_pages(disk) -> None:
    _remove_paths(e["path"] for per in disk for e in per if e is not None)


def _note_retry() -> None:
    _DSTATS["retries"] += 1


def _maybe_demote(env, multi: bool) -> None:
    """Host-budget admission (the disk tier's analog of the eviction
    poll above): when the host-resident spill balance exceeds
    ``CYLON_TPU_HOST_BUDGET``, demote the agreed COUNT of LRU host pages
    to spill files.  The count rides the same one-int32 consensus wire
    as evictions in multiprocess sessions (the poll gate —
    :func:`_disk_armed` — is config, rank-uniform by construction), so
    every rank demotes the same owners in the same order.  Unarmed: one
    attribute read, no filesystem, no collectives."""
    if not _disk_armed():
        return
    want = _LEDGER.demote_count_for(config.HOST_BUDGET_BYTES)
    if multi:
        from . import recovery
        mesh = getattr(env, "mesh", env)
        want = recovery.count_consensus(mesh, want)
    if want <= 0:
        return
    demoted = _LEDGER.demote_n(want)
    if demoted:
        from ..utils.logging import log
        log.warning("memory: demoted %s to disk under host pressure "
                    "(host %d B, host budget %d B)", demoted,
                    _LEDGER.host_balance(), config.HOST_BUDGET_BYTES)


def ensure_headroom(env, need: int, scratch: int = 0,
                    site: str = "spill.evict", reuse: int = 0) -> None:
    """Admission control for a new resident allocation of ``need`` bytes
    (plus ``scratch`` transient working-set bytes — e.g. the piece
    join's sort-operand footprint, :func:`cylon_tpu.ops.pack.
    sort_operand_nbytes`): when the ledger would exceed the budget, cold
    spillable owners evict (LRU) first.

    ``reuse``: bytes of caller-owned buffers DONATED into the allocating
    program (``donate_argnums`` — docs/pipeline.md donation rules): XLA
    frees/aliases them during the allocation, so peak demand is ``need -
    reuse``, not ``need`` — counting both would double-charge donated
    bytes and evict spillable owners that still fit.  Rank-uniform: the
    donation decision is a config flag plus static shapes, identical on
    every rank.

    Coherence protocol (docs/robustness.md "why eviction is
    collective"): what multiprocess ranks agree on is the eviction
    COUNT — the max over each rank's deterministic
    :meth:`Ledger.evict_count_for` — through the one-int32 consensus
    wire, and every rank then evicts that many oldest candidates.  The
    poll's gating inputs are rank-uniform BY CONSTRUCTION (the armed
    flag and the configured budget; never a raw balance read, whose
    release timing is only uniform up to GC), so in a multiprocess
    session the poll runs whenever a budget is configured at all —
    admissions are rare (per packed source), and a 1-int pmax is noise
    next to the pack it guards.  Single-controller sessions (and any
    session with no budget and no armed injector) skip consensus
    entirely: no collective, no host sync."""
    from . import recovery
    kind, armed = recovery.probe(site)
    if kind in _RAISE_KINDS:
        raise recovery.make_fault(kind, site)
    if reuse:
        _STATS["donated_bytes_reused"] += int(reuse)
    if not _spill_enabled():
        return
    need = max(int(need) + int(scratch) - int(reuse), 0)
    b = budget_bytes()
    import jax
    multi = jax.process_count() > 1
    # rank-uniform poll gate: armed / budget-configured only
    if not (armed or b > 0):
        return
    want = _LEDGER.evict_count_for(need, b)
    if kind is not None and want == 0:
        want = 1  # injected pressure with no real deficit: probe one LRU
    if multi:
        mesh = getattr(env, "mesh", env)
        want = recovery.count_consensus(mesh, want)
    if want > 0:
        stall = kind in ("stall", "spill_stall")
        evicted = _LEDGER.evict_n(want, stall=stall)
        if evicted:
            from ..utils.logging import log
            log.warning("memory: evicted %s to host under pressure "
                        "(balance %d B, budget %d B)", evicted,
                        _LEDGER.balance(), b)
    # disk-tier rung: evictions above may have pushed the HOST balance
    # past CYLON_TPU_HOST_BUDGET — demote cold host pages to spill files
    # (count-consensus'd like the evictions; no-op unarmed)
    _maybe_demote(env, multi)


def spill_for_retry() -> int:
    """The retry ladder's spill rung (docs/robustness.md): evict EVERY
    spillable resident registration to host, freeing the maximum bytes
    without discarding completed work, and report the total freed.  The
    caller (``run_with_recovery``) takes the rung only after BOTH the
    fault type and the spill decision itself have been agreed across
    ranks (``spill_consensus``), so every rank spills the same owners in
    the same order — up to entries a straggling GC already released on
    one rank, which is harmless: the spill transport is collective-free
    (per-shard pulls), so a missing candidate shortens that rank's loop
    without desyncing any collective."""
    if not _spill_enabled():
        return 0
    freed = 0
    with _LEDGER._lock:
        cands = sorted((r for r in _LEDGER._live.values()
                        if r.spillable and not r.spilled),
                       key=lambda r: r.seq)
    for reg in cands:
        freed += _LEDGER.evict(reg)
    # the rung's evictions can overrun the HOST budget too: demote the
    # deterministic LRU overflow to disk.  No extra consensus — the
    # take-the-rung decision was already agreed (spill_consensus) and
    # the demote set is a pure function of the rank-uniform ledger (a
    # straggling-GC shortfall only shortens a rank-local file write,
    # never a collective).
    if _disk_armed():
        _LEDGER.demote_n(_LEDGER.demote_count_for(config.HOST_BUDGET_BYTES))
    return freed


# ---------------------------------------------------------------------------
# window-lifetime residency (cylon_tpu/stream): buffered event-time window
# state lives exactly from first append to watermark close
# ---------------------------------------------------------------------------

def register_window(base: str, arrays, sharding=None,
                    anchor=None) -> Registration:
    """Register one event-time window buffer's arrays as a SPILLABLE
    resident allocation — the streaming tier's window-lifetime eviction
    class: a cold (not-yet-closable) window is a first-class LRU spill
    candidate exactly like a cold tenant's packed source, and the
    watermark close retires it through :func:`evict_release`.  Only the
    stream package (and this module) may call this — lint rule TS110
    (docs/trace_safety.md): window state mutated elsewhere would bypass
    the close lifecycle's accounting."""
    return _LEDGER.register(base, arrays, spillable=True,
                            sharding=sharding, anchor=anchor)


def evict_release(reg: Registration | None) -> int:
    """The window-close lifecycle: device → host → released.  A closed
    window's buffered state is first EVICTED through the spill tier — a
    bit-exact per-shard host pull through the same sanctioned,
    watchdogged transport as any other eviction — then the registration
    is RELEASED and the host copy freed with it; the ledger balance
    drains by the window's full byte count (asserted via
    ``memory.stats()`` deltas in tests/test_stream.py).  The host hop is
    the DELIBERATE cost of the lifecycle contract (docs/streaming.md): a
    closed window's final state takes the identical audited exit path as
    every other residency transition — one ``spill_events`` +
    ``window_evictions`` record with the watchdog covering the pull —
    rather than a silent drop (``release`` alone would also free the
    device references, without the audit record).  A window that ledger
    pressure already spilled skips straight to release.  Returns the
    bytes retired.  TS110-guarded like :func:`register_window`."""
    if reg is None or not reg.live:
        return 0
    nbytes = reg.nbytes
    if not reg.spilled:
        _LEDGER.evict(reg)
    _LEDGER.release(reg)
    _STATS["window_evictions"] += 1
    timing.bump("stream.window_evicted")
    return nbytes


def prefetch_depth(window_pair_bytes: int) -> int:
    """Double-buffer depth for the pipelined join's spilled-window
    uploads: 2 (upload piece r+1 while piece r computes) when the
    budget has headroom for a second window pair, else 1.  Deterministic
    from rank-uniform inputs."""
    b = budget_bytes()
    if b <= 0 or _LEDGER.balance() + 2 * int(window_pair_bytes) <= b:
        return 2
    return 1


def spec_row_bytes(spec) -> int:
    """Resident bytes per row of a packed source: 4 per u32 lane plus 8
    per laneless f64 side column (ops/lanes layout)."""
    n_f64 = sum(1 for c in spec.cols if not c.lanes)
    return 4 * int(spec.n_lanes) + 8 * n_f64


# ---------------------------------------------------------------------------
# host <-> device movement (the TS106-sanctioned residency boundary)
# ---------------------------------------------------------------------------

def _stall_timeout(stall: bool) -> float | None:
    """Watchdog deadline for a spill transfer: the configured exchange
    watchdog, or a short synthetic one when a stall is injected with the
    watchdog off (so the injected hang still surfaces typed)."""
    if stall:
        return config.EXCHANGE_WATCHDOG_S or 0.2
    return None  # exchange_watchdog falls back to the config value


def _put_blocks(blocks: list, sharding):
    """Per-shard host blocks -> one row-sharded device array, the
    TS106-sanctioned upload boundary of the spill tier.  Collective-free
    in multiprocess sessions: ``make_array_from_callback`` asks each
    process only for its ADDRESSABLE shards, which are exactly the
    blocks this process holds (remote entries are None and never
    touched).  Unsharded (test) registrations device_put directly."""
    import jax
    have = [b for b in blocks if b is not None]
    n = have[0].shape[0]
    if sharding is None:
        return jax.device_put(np.concatenate(have))
    if jax.process_count() > 1:
        shape = (len(blocks) * n,) + have[0].shape[1:]

        def cb(idx):
            start = idx[0].start or 0
            i = start // n
            stop = shape[0] if idx[0].stop is None else idx[0].stop
            return blocks[i][start - i * n: stop - i * n]

        return jax.make_array_from_callback(shape, sharding, cb)
    return jax.device_put(np.concatenate(blocks), sharding)


def put_blocks(blocks: list, sharding):
    """Public name for the sanctioned per-shard-blocks upload boundary —
    the durable-checkpoint restore path (exec/checkpoint) re-enters its
    host pages through the SAME transport the spill tier uses, so a
    resumed piece is byte-identical to the resident array it was pulled
    from (and multi-controller restores stay collective-free: each
    process uploads only its addressable blocks)."""
    return _put_blocks(blocks, sharding)


def _upload(hosts, sharding, stall: bool = False):
    """Per-array host shard-block lists -> device (:func:`_put_blocks`).
    The dispatch stays ASYNC — blocking every upload would serialize
    exactly the double-buffered overlap the pipelined loop exists for —
    except under an injected ``spill_stall``, where the readiness check
    runs inside the exchange watchdog so the simulated hang surfaces as
    a typed RankDesyncError at site ``spill.upload``.  (A real upload
    hang surfaces at the consumer's next watchdogged host sync;
    :func:`Ledger.readmit` — the whole-matrix, non-overlapped path —
    additionally blocks under the watchdog when
    ``CYLON_TPU_WATCHDOG_S`` is armed.)"""
    from . import recovery
    kind = recovery.injected("spill.upload")
    if kind in _RAISE_KINDS:
        raise recovery.make_fault(kind, "spill.upload")
    stall = stall or kind in ("stall", "spill_stall")
    devs = tuple(_put_blocks(blocks, sharding) for blocks in hosts)
    if stall:
        import jax
        recovery.exchange_watchdog(
            "spill.upload", lambda: jax.block_until_ready(list(devs)),
            timeout_s=_stall_timeout(True), stalled=True)
    return devs


def upload_window(reg: Registration, starts, window: int):
    """Upload ONE per-shard window ``[starts[i], starts[i]+window)`` of a
    spilled registration's host arrays back to the device (row-sharded)
    — the host-resident PieceSource's piece materialization.  Window
    content is byte-identical to the resident path's dynamic slice, so
    packed joins over uploaded windows are bit-equal to unspilled runs.
    Uploads are async dispatches: the pipelined range loop prefetches
    piece r+1's windows so this overlaps piece r's compute.

    DISK-resident registrations promote ON TOUCH: the first window
    access after a demote sha-verifies the owner's pages once
    (:meth:`Ledger.verify_disk` — a mismatch degrades that owner to
    recompute, typed, never a wrong answer), and every window then
    reads its rows straight off MEMORY-MAPPED pages — only the touched
    rows come off disk, so the working set never rematerializes in host
    RAM, and the same prefetch double-buffering overlaps the disk reads
    with piece compute."""
    if not reg.spilled:
        raise ValueError(f"{reg.owner} is device-resident; slice in-program")
    _LEDGER.touch(reg)
    starts = np.asarray(starts, np.int64)
    window = int(window)
    from_disk = reg.host is None
    if from_disk:
        _LEDGER.verify_disk(reg)
        sources = reg.disk_views
        if sources is None:
            # one mmap open per page per demote CYCLE (not per window):
            # the views stay valid until promote/release/re-demote,
            # which clear the cache
            with timing.region("disk.read"):
                sources = tuple(
                    [None if ent is None else _mmap_page(ent["path"])
                     for ent in per] for per in reg.disk)
            reg.disk_views = sources
    else:
        sources = reg.host
    outs = []
    with timing.region("spill.upload"):
        for blocks in sources:
            wins: list = [None] * len(blocks)
            for i, blk in enumerate(blocks):
                if blk is None:     # remote shard: another process's block
                    continue
                s = int(starts[i])
                win = np.zeros((window,) + blk.shape[1:], blk.dtype)
                m = min(window, blk.shape[0] - s)
                if m > 0:
                    win[:m] = blk[s:s + m]
                wins[i] = win
            outs.append(wins)
        devs = _upload(outs, reg.sharding)
    moved = _nbytes(devs)
    _STATS["readmit_events"] += 1
    _STATS["bytes_readmitted"] += moved
    if from_disk:
        _DSTATS["events"] += 1
        _DSTATS["bytes_promoted"] += moved
        timing.add_bytes("disk.read", moved)
    timing.add_bytes("spill.upload", moved)
    return devs


# ---------------------------------------------------------------------------
# stats + eviction log (bench detail; cross-rank coherence assertions)
# ---------------------------------------------------------------------------

# counters live in the metrics registry (cylon_tpu.obs.metrics — the
# TS112 facade); this dict-like view keeps every `_STATS[k] += 1` call
# site and the public stats() shim working verbatim
from ..obs import metrics as _metrics  # noqa: E402

_STATS = _metrics.group("memory", (
    "spill_events", "bytes_spilled",
    "readmit_events", "bytes_readmitted",
    "donated_bytes_reused", "cross_session_evictions",
    "window_evictions"))

#: disk-tier counters (registry names ``memory_disk_*``): demote/promote
#: events and page/byte traffic, bounded-IO retries taken at the disk
#: sites, corrupt-page degrades (owner recomputed) and write degrades
#: (ENOSPC/exhausted-retry demotions that stayed in memory)
_DSTATS = _metrics.group("memory_disk", (
    "events", "pages_demoted", "pages_promoted",
    "bytes_demoted", "bytes_promoted",
    "retries", "corrupt_degrades", "write_degrades"))

_metrics.gauge("memory_ledger_bytes",
               help="current resident-ledger balance (bytes)",
               fn=lambda: _LEDGER.balance())
_metrics.gauge("memory_peak_ledger_bytes",
               help="resident-ledger high-water mark (bytes)",
               fn=lambda: _LEDGER.peak)
_metrics.gauge("memory_host_ledger_bytes",
               help="host-resident spill-page balance (bytes) — the "
                    "disk tier's CYLON_TPU_HOST_BUDGET predicate",
               fn=lambda: _LEDGER.host_balance())

#: owners in eviction order since the last reset — the multihost driver
#: asserts this sequence is IDENTICAL across ranks
_EVICTION_LOG: list[str] = []

#: owners in DEMOTION (host→disk) order since the last reset — the disk
#: tier's rank-coherence audit, mirroring the eviction log one rung down
_DEMOTION_LOG: list[str] = []


def _note_spill(site: str, reg: Registration) -> None:
    _STATS["spill_events"] += 1
    _STATS["bytes_spilled"] += reg.nbytes
    if reg.session is not None and reg.session != _session_tag():
        # another tenant's resident state evicted under THIS context's
        # pressure (or the scheduler's admission pass, tag None): the
        # serving tier's "evict cold tenants first" event
        _STATS["cross_session_evictions"] += 1
    _EVICTION_LOG.append(reg.owner)
    timing.add_bytes(site, reg.nbytes)
    timing.bump(f"memory.{site}")
    from ..utils.logging import log
    log.info("memory: %s -> host (%d B)", reg.owner, reg.nbytes)


def stats() -> dict:
    """Spill counters for bench JSON detail (alongside recovery_events):
    ``spill_events``/``bytes_spilled`` (device→host evictions),
    ``readmit_events``/``bytes_readmitted`` (host→device re-entries),
    ``donated_bytes_reused`` (admission credit for buffers donated into
    the allocating program — bytes the ledger did NOT double-count),
    ``cross_session_evictions`` (one tenant's registrations evicted under
    another tenant's — or the scheduler's — admission pressure),
    ``window_evictions`` (closed event-time windows retired through the
    device→host→released lifecycle, :func:`evict_release`),
    ``peak_ledger_bytes`` (high-water resident balance) — plus the DISK
    tier block: ``disk_events`` (demote/promote operations),
    ``bytes_to_disk``/``bytes_from_disk``, per-page
    ``disk_pages_demoted``/``disk_pages_promoted``, ``disk_retries``
    (bounded-IO retries at the disk sites), ``disk_corrupt_degrades``
    (owners retired to recompute after a failed page hash) and
    ``disk_write_degrades`` (demotions that stayed in memory after an
    ENOSPC or exhausted retry budget)."""
    return dict(_STATS, peak_ledger_bytes=_LEDGER.peak,
                ledger_bytes=_LEDGER.balance(),
                host_ledger_bytes=_LEDGER.host_balance(),
                disk_events=_DSTATS["events"],
                bytes_to_disk=_DSTATS["bytes_demoted"],
                bytes_from_disk=_DSTATS["bytes_promoted"],
                disk_pages_demoted=_DSTATS["pages_demoted"],
                disk_pages_promoted=_DSTATS["pages_promoted"],
                disk_retries=_DSTATS["retries"],
                disk_corrupt_degrades=_DSTATS["corrupt_degrades"],
                disk_write_degrades=_DSTATS["write_degrades"])


def eviction_log() -> list[str]:
    return list(_EVICTION_LOG)


def demotion_log() -> list[str]:
    return list(_DEMOTION_LOG)


def reset_stats() -> None:
    """Zero the counters, the eviction/demotion logs and the peak
    high-water mark (live registrations are untouched — their handles
    stay valid)."""
    for k in _STATS:
        _STATS[k] = 0
    for k in _DSTATS:
        _DSTATS[k] = 0
    _EVICTION_LOG.clear()
    _DEMOTION_LOG.clear()
    _LEDGER.peak = _LEDGER.balance()
