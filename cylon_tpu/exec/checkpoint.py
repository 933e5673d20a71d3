"""Durable checkpoint/resume — the recovery ladder's persistence rung.

PRs 3–4 made the pipeline survive *in-process* faults: the consensus
retry ladder re-plans at degraded configurations and the HBM ledger
spills resident state to host RAM.  What neither can cure is a fault
that poisons the PROCESS — a real XLA ``RESOURCE_EXHAUSTED`` on an
HBM-poisoning rig, a reported libtpu compiler crash — where the only honest remedy is a fresh process, and before
this module that meant recomputing every completed piece from zero.
Following the lineage/checkpoint recovery tradition of the
MapReduce/Spark line (PAPERS.md), this module adds the missing
*durability* rung:

1. **Per-rank checkpoint directories** (``CYLON_TPU_CKPT_DIR``): each
   pipelined stage (one ``pipelined_join`` invocation — deterministic
   stage ids replay identically in a fresh process) owns
   ``<dir>/rank<r>/stage<k>-<label>/``.  Completed-piece state — the
   range loop's per-piece outputs, or the GroupBySink's per-piece
   partial aggregates — is serialized through the SAME host-page
   transport the PR 4 spill tier uses (``utils.host.host_shard_blocks``
   out, :func:`cylon_tpu.exec.memory.put_blocks` back in), so a
   restored piece is byte-identical to the resident array it was
   pulled from and multi-controller checkpoints stay collective-free
   (each process writes/reads only its addressable shards).  Every
   page carries a content hash (sha256); the piece meta sidecar is
   hashed into the manifest entry.

2. **Two-phase rank-coherent manifest commit**: after a piece's pages
   land, the updated manifest is STAGED (atomic rank-local write), then
   every rank votes :class:`~cylon_tpu.status.Code.CkptCommit` with its
   staged epoch over the PR 3 pmax wire
   (:func:`cylon_tpu.exec.recovery.ckpt_commit_consensus`) and only
   then renames staged → ``MANIFEST.json`` — so a manifest is committed
   on every rank at the IDENTICAL epoch or on none, and a crash between
   stage and commit leaves only staged files, which resume ignores.

3. **Resume** (``CYLON_TPU_RESUME=1``): a fresh process replaying the
   same workload reaches each stage with the same plan token (a hash of
   the stage's static plan — operator, key names, chunk count, piece
   capacities, per-range row counts); committed pieces whose token
   matches are loaded bit-identically and the range loop fast-forwards
   past them (``resume_fast_forwarded_pieces`` in the bench detail).  A
   corrupt or hash-mismatched page raises a typed
   :class:`~cylon_tpu.status.CheckpointCorruptError` and the stage
   falls back to recomputing its remaining pieces — corruption degrades
   resume to recompute, never to a wrong answer.

4. **The FINAL ladder rung** (:mod:`cylon_tpu.exec.recovery`): an
   unrecoverable ``DeviceOOMError`` or reported compiler crash
   flushes the session (:func:`flush_for_abort`) and raises a typed
   :class:`~cylon_tpu.status.ResumableAbort` carrying the resume token
   instead of a bare abort.

5. **Elastic resume** (docs/robustness.md "Elastic resume & preemption
   grace"): stages carry a world-invariant BASE token next to the full
   layout token; a resume whose checkpoint was committed at a DIFFERENT
   topology (world size or process layout) re-shards complete stages —
   foreign rank dirs' pages sha-verified, shard prefixes stitched into
   global row order, re-blocked through ``relational/repart``'s
   order-preserving split, re-voted and re-committed over the NEW mesh
   (:meth:`Stage.load_foreign_pieces` / :meth:`Stage.begin_rewrite`) —
   and counts what it could not adopt (``resume_world_mismatch``)
   instead of silently recomputing.  **Preemption grace**
   (:mod:`cylon_tpu.exec.preempt`): SIGTERM with
   ``CYLON_TPU_PREEMPT_GRACE_S`` armed drains at the next checkpoint
   boundary (:func:`drain_requested` → :func:`drain_abort`, the drain
   vote rank-coherent) so a spot scale-down is a planned
   ``ResumableAbort``, not a mid-piece crash.

Happy path contract: with ``CYLON_TPU_CKPT_DIR`` unset this module's
entry points are a couple of env reads — ZERO filesystem writes, zero
extra collectives, no measurable cost on the pipelined hot path.  In a
single-controller session even an armed checkpoint adds no collectives
(the commit consensus short-circuits locally).

Fault injection (``scripts/chaos_soak.py``, docs/robustness.md): sites
``ckpt.write``/``ckpt.load``; kind ``corrupt`` flips page bytes after
hashing (write) or simulates a failed hash check (load); ``kill``
SIGKILLs the process mid-write — the chaos-soak harness's hard-crash
primitive.

Lint rule TS107: this module is the ONE sanctioned place that writes
checkpoint artifacts — a direct ``open``/``np.save``/pickle of
``CYLON_TPU_CKPT_DIR`` paths in ``relational/`` or ``exec/pipeline.py``
bypasses the hash/manifest protocol and is a finding.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import re
import time

import numpy as np

from ..status import (CheckpointCorruptError, DataIntegrityError,
                      InvalidError, ResumableAbort)
from ..utils import timing


# ---------------------------------------------------------------------------
# switches (read dynamically: tests and the chaos harness flip env vars)
# ---------------------------------------------------------------------------

def ckpt_dir() -> str | None:
    """The checkpoint root (``CYLON_TPU_CKPT_DIR``), or None = disabled."""
    return os.environ.get("CYLON_TPU_CKPT_DIR") or None


def enabled() -> bool:
    return ckpt_dir() is not None


def resume_requested() -> bool:
    """``CYLON_TPU_RESUME=1``: committed pieces of matching stages are
    restored instead of recomputed.  A serving session the scheduler
    preempted and REQUEUED resumes in-process the same way: its
    ``_resume_pending`` flag arms the resume for the re-granted fn run
    only (per-session stage namespaces keep the tokens collision-free),
    without flipping the process-wide env knob for co-tenants."""
    if os.environ.get("CYLON_TPU_RESUME") == "1":
        return True
    from .scheduler import current_session
    sess = current_session()
    return bool(sess is not None
                and getattr(sess, "_resume_pending", False))


# ---------------------------------------------------------------------------
# stats (bench JSON detail, alongside recovery_events / spill counters)
# ---------------------------------------------------------------------------

# counters live in the metrics registry (cylon_tpu.obs.metrics — the
# TS112 facade); this dict-like view keeps every `_STATS[k] += 1` call
# site (and tests poking the table directly) working verbatim
from ..obs import metrics as _metrics  # noqa: E402

_STATS = _metrics.group("ckpt", (
    "checkpoint_events", "bytes_checkpointed",
    "resume_fast_forwarded_pieces", "corrupt_pages",
    "resume_resharded_pieces", "resume_world_mismatch"))


def stats() -> dict:
    """Checkpoint counters for the bench JSON detail:
    ``checkpoint_events`` (committed piece checkpoints),
    ``bytes_checkpointed`` (page bytes written),
    ``resume_fast_forwarded_pieces`` (pieces restored instead of
    recomputed), ``corrupt_pages`` (hash-mismatch fallbacks),
    ``resume_resharded_pieces`` (pieces adopted across a topology
    change — always also counted as fast-forwarded) and
    ``resume_world_mismatch`` (stages whose checkpoint came from a
    DIFFERENT topology: together with ``resume_resharded_pieces`` an
    operator can tell "resharded and fast-forwarded" apart from "threw
    the checkpoint away and recomputed")."""
    return dict(_STATS)


def reset_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def unrestore(k: int) -> None:
    """Back out ``k`` discarded restores from the fast-forward counter:
    a multiprocess resume adopts the MINIMUM restorable prefix across
    ranks (:func:`cylon_tpu.exec.recovery.ckpt_resume_consensus`), so
    pieces a rank restored beyond the agreed prefix are recomputed and
    must not count as fast-forwarded.  Backing out more than was ever
    counted is a consensus bug, not a bookkeeping nuance: the counter
    clamps at zero (a later bench read can never report a negative
    fast-forward) and a typed :class:`InvalidError` surfaces the
    over-unrestore loudly."""
    k = int(k)
    if k < 0:
        raise InvalidError(f"unrestore({k}): negative back-out")
    have = _STATS["resume_fast_forwarded_pieces"]
    if k > have:
        _STATS["resume_fast_forwarded_pieces"] = 0
        raise InvalidError(
            f"unrestore({k}) exceeds the {have} restores counted — the "
            "resume consensus agreed on more discards than this rank "
            "ever restored (counter clamped at zero)")
    _STATS["resume_fast_forwarded_pieces"] = have - k


def note_reshard(k: int) -> None:
    """Count ``k`` pieces adopted across a topology change: they fast-
    forwarded (the resumed loop skips their work) AND they resharded
    (their host pages were stitched and re-blocked onto the new mesh) —
    both counters move so the bench detail distinguishes an elastic
    adoption from a plain same-world fast-forward."""
    k = int(k)
    _STATS["resume_fast_forwarded_pieces"] += k
    _STATS["resume_resharded_pieces"] += k
    for _ in range(k):
        timing.bump("ckpt.piece_resharded")


# ---------------------------------------------------------------------------
# stage identity
# ---------------------------------------------------------------------------

#: per-(serving-session) stage sequences, key None = outside a
#: scheduler: checkpoint-enabled stages replay in the same PER-SESSION
#: order in a fresh process (each session's workload is deterministic,
#: and the serving scheduler re-creates sessions under the same names),
#: so (session, counter) IS the cross-process stage identity even when
#: concurrent sessions interleave their stage openings in a different
#: order — the plan token guards against the workload having actually
#: changed
_STAGE_SEQ: dict = {}

#: stage directories opened this process (for the resume-token file)
_OPEN_DIRS: list[str] = []


def reset_stages() -> None:
    """Restart the stage sequences (tests replaying a workload in-process
    to exercise the resume path without a fresh interpreter)."""
    _STAGE_SEQ.clear()
    _OPEN_DIRS.clear()


def reset_session_stages(sid: str) -> None:
    """Restart ONE serving session's stage sequence — the scheduler's
    preemptive-requeue path: the re-granted session replays its
    workload from the top, so its stage identities must restart at
    seq 0 for the resume to match the committed directories."""
    _STAGE_SEQ.pop(sid, None)


def plan_token(*parts) -> str:
    """Deterministic token over a stage's static plan (pass plain python
    ints/strs/tuples).  Stages carry TWO tokens (docs/robustness.md
    "Elastic resume & preemption grace"): a world-invariant BASE token
    over the workload identity (operator, keys, chunk count, consumption
    mode — nothing layout-derived), and the full LAYOUT token folding
    the base together with world size, piece capacities and per-range
    row counts.  A full-token match fast-forwards bit-identically; a
    base-only match with a different recorded topology takes the
    re-shard path (committed host pages stitched into global row order
    and re-blocked onto the live mesh); no match at all starts the
    stage over — foreign state is never spliced in."""
    return hashlib.sha1(repr(parts).encode()).hexdigest()[:16]


def _rank() -> int:
    import jax
    return jax.process_index()


def _procs() -> int:
    import jax
    return jax.process_count()


_RANK_DIR_RE = re.compile(r"rank(\d+)$")


def _rank_dirs() -> list[str]:
    """``rank<r>`` directory names under the checkpoint root, sorted by
    rank.  The elastic re-shard scan reads ALL of them (this module is
    the one sanctioned reader of foreign rank directories — lint rule
    TS111): with a shared checkpoint root (the GKE PVC drill,
    deploy/gke/README.md) every live rank sees every old rank's pages;
    with rank-local disks a world change degrades to recompute because
    the foreign shards simply are not visible."""
    root = ckpt_dir()
    try:
        names = os.listdir(root)
    except OSError:
        return []
    ranked = [(int(m.group(1)), n) for n in names
              if (m := _RANK_DIR_RE.fullmatch(n))]
    return [n for _, n in sorted(ranked)]


# ---------------------------------------------------------------------------
# page serialization — the spill tier's host-page transport, persisted
# ---------------------------------------------------------------------------

def _sha(raw: bytes) -> str:
    return hashlib.sha256(raw).hexdigest()


def _page_bytes(blocks: list) -> bytes:
    """One array's per-shard host blocks → one page (npz).  Remote
    shards' entries are None (another process owns them) and are simply
    absent — each rank's page holds exactly its addressable shards."""
    buf = io.BytesIO()
    arrs = {f"b{k}": b for k, b in enumerate(blocks) if b is not None}
    np.savez(buf, w=np.asarray(len(blocks), np.int64), **arrs)
    return buf.getvalue()


def _page_blocks(raw: bytes) -> list:
    with np.load(io.BytesIO(raw)) as z:
        blocks: list = [None] * int(z["w"])
        for key in z.files:
            if key != "w":
                blocks[int(key[1:])] = z[key]
    return blocks


class Stage:
    """One pipelined stage's durable checkpoint state: piece pages +
    hashed meta sidecars under the per-rank stage directory, committed
    under the two-phase manifest.  Obtain via :func:`open_stage`."""

    def __init__(self, env, label: str, token: str, seq: int,
                 base_token: str | None = None):
        self.env = env
        self.label = label
        self.token = token
        self.base = base_token
        self._dirname = f"stage{seq:03d}-{label}"
        self.dir = os.path.join(ckpt_dir(), f"rank{_rank()}", self._dirname)
        os.makedirs(self.dir, exist_ok=True)
        self.epoch = 0
        #: manifest generation — monotonic across sessions sharing this
        #: checkpoint root: seeded above anything already on disk, and
        #: bumped again by a re-shard rewrite (scan keeps the max)
        self.gen = 0
        self.complete_flag = False
        self.committed: dict[int, dict] = {}
        self.resuming = False
        #: world-mismatch resume state: {"world", "procs", "gen",
        #: "complete", "pieces", "manifests": {rank_dirname: manifest}} —
        #: set when the current manifest generation for this stage was
        #: written by a DIFFERENT topology (see _resolve_resume)
        self.foreign: dict | None = None
        if resume_requested():
            self._resolve_resume()
        else:
            # FRESH run over a non-empty stage dir landscape: supersede
            # whatever previous sessions parked here.  Generations must
            # be monotonic ACROSS sessions — a fresh run re-starting at
            # gen 0 would leave an earlier reshard rewrite's gen-1
            # manifests outranking ITS commits at the next resume,
            # silently fast-forwarding a previous run's data
            mans = self._scan_manifests()
            if mans:
                self.gen = max(int(m.get("gen", 0))
                               for m in mans.values()) + 1

    # -- manifest ----------------------------------------------------------
    @property
    def _manifest_path(self) -> str:
        return os.path.join(self.dir, "MANIFEST.json")

    def _read_manifest(self, rank_dirname: str | None = None) -> dict | None:
        path = self._manifest_path if rank_dirname is None else os.path.join(
            ckpt_dir(), rank_dirname, self._dirname, "MANIFEST.json")
        try:
            with open(path, encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _scan_manifests(self) -> dict:
        """Every rank dir's manifest for THIS stage (rank dirname →
        manifest).  One small JSON read per rank dir, once per stage
        handle — negligible next to the page traffic it arbitrates,
        and the price of generation monotonicity: an own-manifest-only
        shortcut would let a rank resume from a manifest a later
        reshard rewrite (possibly covering fewer ranks) already
        superseded."""
        mans: dict = {}
        for rd in _rank_dirs():
            man = self._read_manifest(rd)
            if man is not None:
                mans[rd] = man
        return mans

    def _resolve_resume(self) -> None:
        """Decide what this stage can restore.  The scan reads every
        ``rank<r>`` dir's manifest for this stage and keeps the highest
        GENERATION whose manifests agree (same plan, world, gen) — a
        re-shard rewrite bumps ``gen``, so rank dirs the rewrite did not
        cover (the old world had more ranks) are recognized as stale
        instead of masquerading as restorable state.  Three outcomes:

        * current generation matches this stage's full layout token AND
          live topology → plain fast-forward (``resuming``);
        * current generation matches only the BASE token, from a
          different world/process layout → the re-shard path
          (``foreign``; counted in ``resume_world_mismatch`` with a
          structured recovery event, so "resharded" vs "thrown away" is
          auditable — before this rung the mismatch was a SILENT
          recompute);
        * anything else → stale, stage starts over (logged)."""
        from ..utils.logging import log
        mans = self._scan_manifests()
        if not mans:
            return
        top_gen = max(int(m.get("gen", 0)) for m in mans.values())
        cur = {rd: m for rd, m in mans.items()
               if int(m.get("gen", 0)) == top_gen}
        plans = {m.get("plan") for m in cur.values()}
        worlds = {int(m.get("world", 0)) for m in cur.values()}
        procs = {int(m.get("procs", 1)) for m in cur.values()}
        if len(plans) != 1 or len(worlds) != 1 or len(procs) != 1:
            log.warning("checkpoint stage %s: rank manifests disagree at "
                        "generation %d (plans %s, worlds %s, procs %s) — "
                        "torn checkpoint ignored, stage starts over",
                        self._dirname, top_gen, plans, worlds, procs)
            self.gen = top_gen + 1   # the recompute supersedes the mess
            return
        plan, world = plans.pop(), worlds.pop()
        same_topo = (world == int(self.env.world_size)
                     and procs == {_procs()})
        if plan == self.token and same_topo:
            # adopt the current generation even when THIS rank's own
            # manifest is missing/unreadable (it recomputes, voted down
            # to 0 by the resume consensus): committing below the
            # on-disk generation would hand the NEXT resume's max-gen
            # scan stale data over this run's fresh commits
            self.gen = top_gen
            own = cur.get(f"rank{_rank()}")
            if own is None:
                return
            self.committed = {int(k): v
                              for k, v in own.get("pieces", {}).items()}
            self.epoch = int(own.get("epoch", 0))
            self.gen = int(own.get("gen", 0))
            self.complete_flag = bool(own.get("complete", False))
            self.resuming = bool(self.committed)
            return
        base = {m.get("base") for m in cur.values()}
        if (self.base is not None and base == {self.base}
                and not same_topo):
            # every rank dir must hold a piece for it to be adoptable
            # (each dir contributes that rank's shard blocks); the
            # contiguous common prefix is the restorable unit
            common = set.intersection(*[
                {int(k) for k in m.get("pieces", {})} for m in cur.values()])
            n = 0
            while n in common:
                n += 1
            # "complete" for WHOLE-stage adoption means the contiguous
            # common prefix covers the piece count recorded at
            # completion time on every rank — the complete flag alone
            # would let a truncated (torn/tampered) piece table adopt a
            # prefix as if it were the whole stage, a wrong answer
            want = {int(m.get("n_pieces", -1)) for m in cur.values()}
            complete = (all(bool(m.get("complete", False))
                            for m in cur.values())
                        and len(want) == 1 and n == want.pop())
            info = {"world": world, "procs": procs.pop(), "gen": top_gen,
                    "complete": complete, "pieces": n, "manifests": cur}
            self.foreign = info
            # whatever this run commits — a re-shard rewrite OR a fresh
            # recompute of an unadoptable stage — supersedes the foreign
            # generation, so old-world rank dirs the new (possibly
            # smaller) process set never rewrites read as stale forever
            self.gen = top_gen + 1
            _STATS["resume_world_mismatch"] += 1
            from . import recovery
            recovery._record("ckpt.reshard", "world_mismatch", "detected")
            log.warning(
                "checkpoint stage %s: committed at world=%d (%d rank "
                "dirs), resuming at world=%d — %s", self._dirname,
                world, len(cur), int(self.env.world_size),
                "re-shard path engaged (complete stage, %d pieces)" % n
                if info["complete"] else
                "stage incomplete at the old topology: whole-stage "
                "consumers (pipelined joins) recompute — old-layout "
                "pieces cannot splice into a new-layout loop — while "
                "mergeable consumers (stream views) adopt the %d-piece "
                "committed prefix (counted as resume_world_mismatch "
                "either way)" % n)
            return
        log.warning(
            "checkpoint stage %s: plan token mismatch (manifest %s, "
            "workload %s) — stale checkpoint ignored, stage starts "
            "over", self.dir, plan, self.token)
        self.gen = top_gen + 1       # the fresh commits supersede it

    def _commit(self) -> None:
        """Two-phase manifest commit: stage (atomic rank-local write +
        fsync), consensus (every rank of the LIVE mesh votes
        Code.CkptCommit with its staged epoch over the pmax wire — after
        an elastic re-shard that is the NEW mesh; stale old-world rank
        dirs are not voters), then rename staged → MANIFEST.json.
        Single-controller sessions skip the collective entirely."""
        from . import recovery
        self.epoch += 1
        man = {"plan": self.token, "base": self.base, "label": self.label,
               "epoch": self.epoch, "gen": self.gen,
               "complete": self.complete_flag,
               "n_pieces": len(self.committed),
               "world": int(self.env.world_size), "procs": _procs(),
               "pieces": {str(k): v for k, v in self.committed.items()}}
        staged = self._manifest_path + ".staged"

        def stage_write():
            with open(staged, "w", encoding="utf-8") as f:
                json.dump(man, f)
                f.flush()
                os.fsync(f.fileno())

        # bounded IO retry (exec/recovery.retry_io): a transient OSError
        # on shared storage — an NFS blip during a GKE drain — used to
        # abort a drain a 3-attempt backoff saves
        recovery.retry_io(stage_write, "ckpt.write")
        # stage -> vote -> publish: the commit vote must precede the
        # os.replace on every path (reordering fails the CX403 gate)
        recovery.ckpt_commit_consensus(getattr(self.env, "mesh", None),
                                       self.epoch)
        recovery.retry_io(lambda: os.replace(staged, self._manifest_path),
                          "ckpt.write")

    def has_piece(self, i: int) -> bool:
        return int(i) in self.committed

    @property
    def foreign_complete(self) -> bool:
        """True when the world-mismatched checkpoint covers the WHOLE
        stage — the precondition for adopting a non-mergeable (sinkless
        piece-output) stage across a topology change: a partial prefix
        of old-layout pieces has no expressible complement in the new
        layout, so only a complete stage re-shards; anything less
        recomputes (never a wrong answer)."""
        return (self.foreign is not None and self.foreign["complete"]
                and self.foreign["pieces"] > 0)

    def mark_complete(self) -> None:
        """Record that the stage finished all its pieces — the flag a
        LATER world-mismatched resume needs to know the committed set is
        the whole stage (adoptable) rather than a crash prefix
        (recompute).  One extra manifest commit per stage on the armed
        happy path; no-op when already marked."""
        if self.complete_flag:
            return
        self.complete_flag = True
        self._commit()

    def begin_rewrite(self) -> None:
        """Start the post-reshard rewrite: the adopted (re-blocked)
        state re-commits under THIS topology's layout token at the next
        manifest generation, so a second resume at this world is a plain
        fast-forward and the old world's surviving rank dirs — which the
        rewrite may not cover — read as stale (lower gen) forever."""
        self.gen = int(self.foreign["gen"]) + 1
        self.committed = {}
        self.epoch = 0
        self.resuming = False
        self.complete_flag = False

    # -- save --------------------------------------------------------------
    def save_piece(self, i: int, table) -> None:
        """Checkpoint one completed piece's Table: per-array host pages
        (spill-tier transport) + hashed meta sidecar, committed under
        the two-phase manifest.  The piece is durable only after
        :meth:`_commit` returns — a kill mid-write leaves staged files
        that resume ignores."""
        from . import recovery
        from . import integrity as _integrity
        corrupt = recovery.maybe_inject(
            "ckpt.write", intercept=("corrupt",)) == "corrupt"
        i = int(i)
        # armed audit (CYLON_TPU_AUDIT=1, exec/integrity): the piece's
        # order-invariant content fingerprint rides the manifest entry so
        # a resume can audit restored — and topology-mismatched adopted —
        # pieces beyond the page shas (the shas only prove the bytes on
        # disk match what was written; the fingerprint proves what was
        # written matches what the piece held).  None when unarmed: zero
        # cost, and old manifests without the key stay readable.
        fp = _integrity.manifest_fingerprint(table)
        with timing.region("ckpt.write"):
            nbytes, meta_sha, meta_file = self._write_pages(i, table,
                                                            corrupt)
            self.committed[i] = {"meta": meta_file, "sha": meta_sha,
                                 "nbytes": nbytes, "fp": fp}
            self._commit()
        _STATS["checkpoint_events"] += 1
        _STATS["bytes_checkpointed"] += nbytes
        timing.add_bytes("ckpt.write", nbytes)
        timing.bump("ckpt.piece_committed")
        # per-tenant durable-progress accounting: the scheduler's
        # no-progress guard keys off pieces committed since the last
        # preemption (docs/serving.md)
        from .scheduler import current_session
        sess = current_session()
        if sess is not None:
            sess.pieces_committed += 1

    def _write_pages(self, i: int, table, corrupt: bool):
        from ..utils.host import host_shard_blocks
        w = int(self.env.world_size)
        cols, flats = [], []
        for name, c in table.columns.items():
            cols.append({"name": name, "type": c.type,
                         "dictionary": c.dictionary, "bounds": c.bounds,
                         "has_validity": c.validity is not None})
            flats.append(c.data)
            if c.validity is not None:
                flats.append(c.validity)
        pages, total = [], 0
        for j, arr in enumerate(flats):
            raw = _page_bytes(host_shard_blocks(arr, w))
            fname = f"piece_{i}.p{j}"
            # each page carries a content hash computed over the GOOD
            # bytes; an injected corruption flips a byte AFTER hashing so
            # the resume path's verification catches it (the acceptance
            # path for CheckpointCorruptError)
            pages.append({"file": fname, "sha": _sha(raw), "nbytes": len(raw)})
            if corrupt and j == 0:
                raw = bytes([raw[0] ^ 0xFF]) + raw[1:]
            self._atomic_write(fname, raw)
            total += len(raw)
        meta = pickle.dumps({
            "cols": cols,
            "valid_counts": np.asarray(table.valid_counts, np.int64),
            "grouped_by": table.grouped_by,
            "pages": pages,
        })
        meta_file = f"piece_{i}.meta"
        self._atomic_write(meta_file, meta)
        return total + len(meta), _sha(meta), meta_file

    def _atomic_write(self, fname: str, raw: bytes) -> None:
        path = os.path.join(self.dir, fname)
        tmp = path + ".tmp"

        def write():
            with open(tmp, "wb") as f:
                f.write(raw)
            os.replace(tmp, path)

        # page writes share the checkpoint tier's bounded transient-
        # OSError backoff (exec/recovery.retry_io) with the disk tier
        from . import recovery
        recovery.retry_io(write, "ckpt.write")

    # -- load (resume fast-forward) ----------------------------------------
    def load_piece(self, i: int):
        """Restore one committed piece bit-identically: verify the meta
        sidecar against the manifest hash, every page against its meta
        hash, and re-enter the device through the spill tier's sanctioned
        upload boundary (:func:`cylon_tpu.exec.memory.put_blocks`).  Any
        mismatch (or an injected ``corrupt``) raises a typed
        :class:`CheckpointCorruptError` — the caller recomputes the
        stage's remaining pieces."""
        from . import memory, recovery
        from ..core.column import Column
        from ..core.table import Table
        if recovery.maybe_inject("ckpt.load", intercept=("corrupt",)):
            _STATS["corrupt_pages"] += 1
            raise CheckpointCorruptError(
                "injected checkpoint corruption on load", site="ckpt.load")
        entry = self.committed[int(i)]
        with timing.region("ckpt.load"):
            meta_raw = self._read_verified(entry["meta"], entry["sha"])
            meta = pickle.loads(meta_raw)
            sharding = self.env.sharding()
            flats = []
            for page in meta["pages"]:
                raw = self._read_verified(page["file"], page["sha"])
                flats.append(memory.put_blocks(_page_blocks(raw), sharding))
        flats = iter(flats)
        cols = {}
        for cm in meta["cols"]:
            data = next(flats)
            validity = next(flats) if cm["has_validity"] else None
            cols[cm["name"]] = Column(data, cm["type"], validity,
                                      cm["dictionary"], bounds=cm["bounds"])
        out = Table(cols, self.env, meta["valid_counts"])
        out.grouped_by = meta["grouped_by"]
        # armed resume audit (exec/integrity): recompute the restored
        # piece's order-invariant fingerprint against the manifest-
        # recorded one — catches what the shas cannot (a rewrite with
        # self-consistent hashes); a mismatch raises a typed
        # DataIntegrityError and the caller recomputes, never adopts
        from . import integrity
        integrity.audit_restored_table(out, entry.get("fp"))
        _STATS["resume_fast_forwarded_pieces"] += 1
        timing.bump("ckpt.piece_restored")
        return out

    # -- elastic re-shard (world-mismatch resume) --------------------------
    def load_foreign_pieces(self, limit: int | None = None,
                            prefix_ok: bool = False) -> list:
        """Adopt a world-mismatched checkpoint's committed pieces onto
        the LIVE mesh — the elastic resume path (docs/robustness.md
        "Elastic resume & preemption grace").  For each piece, every old
        ``rank<r>`` directory's pages are read and sha-verified (this is
        the one sanctioned foreign-rank read, lint rule TS111), the
        per-shard blocks merged across directories (each old rank held
        only its addressable shards), the shards' live prefixes stitched
        into GLOBAL row order, and the rows re-blocked onto the live
        mesh through :func:`cylon_tpu.relational.repart.
        even_partition_counts` — the same order-preserving split a
        fresh ``repartition`` would produce — before re-entering the
        device through the sanctioned upload boundary
        (:func:`cylon_tpu.exec.memory.put_blocks`).

        Any missing block, unreadable file or hash mismatch (or an
        injected ``corrupt`` at site ``ckpt.reshard``) raises a typed
        :class:`CheckpointCorruptError`: the caller degrades the stage
        to recompute — corruption never produces a wrong answer.

        Returns the adopted Tables in piece order, re-distributed but
        NOT yet counted (the caller counts via :func:`note_reshard`
        after the all-or-nothing resume vote) and NOT yet re-committed
        (the caller rewrites via :meth:`begin_rewrite` + save_piece so
        a second resume at this topology is a plain fast-forward).
        ``limit`` caps the adopted prefix.  ``prefix_ok`` is the
        mergeable-consumer mode (stream views — piece identity is the
        world-invariant batch ordinal): a corruption at piece k > 0
        returns the VERIFIED prefix ``0..k-1`` instead of raising, so
        one flipped byte in batch 199 of 200 costs one batch, not the
        stream's whole committed history; join stages keep the raising
        all-or-nothing contract (:attr:`foreign_complete`)."""
        from . import recovery
        if recovery.maybe_inject("ckpt.reshard",
                                 intercept=("corrupt",)) == "corrupt":
            _STATS["corrupt_pages"] += 1
            raise CheckpointCorruptError(
                "injected checkpoint corruption during re-shard",
                site="ckpt.reshard")
        n = self.foreign["pieces"] if limit is None \
            else min(int(limit), self.foreign["pieces"])
        out: list = []
        with timing.region("ckpt.reshard"):
            for i in range(n):
                try:
                    out.append(self._load_one_foreign(i))
                except (CheckpointCorruptError, DataIntegrityError) as e:
                    if not (prefix_ok and out):
                        raise
                    recovery._record("ckpt.reshard", "corrupt",
                                     "prefix_trim")
                    from ..utils.logging import log
                    log.warning(
                        "re-shard of stage %s: piece %d failed "
                        "verification (%s); adopting the verified "
                        "%d-piece prefix (mergeable consumer)",
                        self._dirname, i, e, len(out))
                    break
        return out

    def _load_one_foreign(self, i: int):
        from ..core.column import Column
        from ..core.table import Table
        from . import memory
        meta = None
        fp_rec = None
        merged: list[list] = []
        for rd, man in self.foreign["manifests"].items():
            entry = man["pieces"][str(i)]
            stage_dir = os.path.join(ckpt_dir(), rd, self._dirname)
            meta_d = pickle.loads(
                self._read_verified(entry["meta"], entry["sha"],
                                    dir=stage_dir))
            if meta is None:
                meta = meta_d
                fp_rec = entry.get("fp")
                merged = [[] for _ in meta["pages"]]
            for j, page in enumerate(meta_d["pages"]):
                raw = self._read_verified(page["file"], page["sha"],
                                          dir=stage_dir)
                blocks = _page_blocks(raw)
                if len(merged[j]) < len(blocks):
                    merged[j].extend([None] * (len(blocks) - len(merged[j])))
                for b, blk in enumerate(blocks):
                    if blk is not None:
                        merged[j][b] = blk
        vc_old = np.asarray(meta["valid_counts"], np.int64)
        for j, blocks in enumerate(merged):
            if any(b is None for b in blocks):
                _STATS["corrupt_pages"] += 1
                raise CheckpointCorruptError(
                    f"re-shard of stage {self._dirname} piece {i}: page "
                    f"{j} is missing shard blocks — an old rank "
                    "directory is absent or unreadable (is the "
                    "checkpoint root shared storage?)",
                    site="ckpt.reshard")
        from .. import config
        from ..relational.repart import even_partition_counts
        total = int(vc_old.sum())
        w_new = int(self.env.world_size)
        dest = even_partition_counts(total, w_new)
        new_cap = config.pow2ceil(max(int(dest.max(initial=0)), 1))
        dof = np.concatenate([[0], np.cumsum(dest)[:-1]]).astype(np.int64)
        sharding = self.env.sharding()
        flats = []
        for blocks in merged:
            rows = np.concatenate(
                [blocks[s][:int(vc_old[s])] for s in range(len(blocks))]) \
                if blocks else np.zeros(0)
            new_blocks = []
            for s in range(w_new):
                part = rows[int(dof[s]):int(dof[s]) + int(dest[s])]
                pad = np.zeros((new_cap - part.shape[0],) + part.shape[1:],
                               part.dtype)
                new_blocks.append(np.concatenate([part, pad]))
            flats.append(memory.put_blocks(new_blocks, sharding))
        flats = iter(flats)
        cols = {}
        for cm in meta["cols"]:
            data = next(flats)
            validity = next(flats) if cm["has_validity"] else None
            # the re-block pads with zeros (the old padding is dropped
            # with the old layout), so bounds must admit 0
            b = cm["bounds"]
            nb = (min(b[0], 0), max(b[1], 0)) if b is not None else None
            cols[cm["name"]] = Column(data, cm["type"], validity,
                                      cm["dictionary"], bounds=nb)
        # per-shard key contiguity does not survive re-blocking: the
        # grouped contract is deliberately dropped, consumers re-derive
        out = Table(cols, self.env, dest)
        # armed adoption audit (exec/integrity): the order-invariant
        # fingerprint is topology-independent — the XOR over per-row
        # hashes survives the stitch + re-block — so the OLD world's
        # recorded fp audits the table as adopted onto the NEW mesh
        from . import integrity
        integrity.audit_restored_table(out, fp_rec, site="ckpt.reshard")
        return out

    def _read_verified(self, fname: str, want_sha: str,
                       dir: str | None = None) -> bytes:
        path = os.path.join(self.dir if dir is None else dir, fname)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError as e:
            _STATS["corrupt_pages"] += 1
            raise CheckpointCorruptError(
                f"checkpoint page {path} unreadable: {e}",
                site="ckpt.load") from e
        if _sha(raw) != want_sha:
            _STATS["corrupt_pages"] += 1
            raise CheckpointCorruptError(
                f"checkpoint page {path} failed its content-hash check "
                "(torn write or on-disk corruption)", site="ckpt.load")
        return raw


def open_stage(env, label: str, token: str,
               base_token: str | None = None) -> Stage:
    """The next pipelined stage's checkpoint handle (advances the
    deterministic PER-SESSION stage sequence; under the serving
    scheduler the stage directory is additionally namespaced by the
    session name, so concurrent tenants' checkpoints never collide and a
    resumed process matches each tenant's stages regardless of how the
    original interleave ordered them).  ``base_token`` is the
    world-invariant workload identity (:func:`plan_token`) — passing it
    makes the stage eligible for the elastic re-shard path when a
    resume finds its checkpoint committed at a different topology.
    Call only when :func:`enabled`."""
    from . import recovery
    sid = recovery.current_session()
    seq = _STAGE_SEQ.get(sid, 0)
    _STAGE_SEQ[sid] = seq + 1
    if sid is not None:
        label = f"{sid}.{label}"
    stage = Stage(env, label, token, seq, base_token=base_token)
    _OPEN_DIRS.append(stage.dir)
    return stage


def corrupt_fallback(stage: Stage, piece: int, err: Exception) -> None:
    """Log + count a corruption-triggered recompute fallback (the range
    loop calls this, then recomputes the stage's remaining pieces)."""
    from . import recovery
    from ..utils.logging import log
    recovery._record("ckpt.load", "corrupt", "recompute")
    log.warning("checkpoint stage %s piece %d failed verification (%s); "
                "recomputing this stage's remaining pieces instead of "
                "restoring", stage.label, piece, err)


def drain_requested(env) -> bool:
    """Preemption-grace drain poll — called by the pipelined range loop
    and the streaming absorb path at their checkpoint boundaries (the
    points where completed-piece state is already durably committed).
    True only when ALL of: a grace budget is declared
    (``CYLON_TPU_PREEMPT_GRACE_S``), durable checkpointing is armed,
    and the rank-coherent drain vote
    (:func:`cylon_tpu.exec.recovery.drain_consensus`) agrees a
    preemption notice arrived somewhere.  With checkpointing unarmed
    the SIGTERM flag changes nothing — no drain, no writes, no
    collectives (the happy-path contract, asserted in
    tests/test_checkpoint.py).

    A serving session the scheduler flagged for a PREEMPTIVE or FLEET
    drain (docs/serving.md) exits through the same poll: the flag is
    one thread-local read (zero cost for unflagged tenants), the vote
    rides the identical session-namespaced wire, and the
    ``sched.preempt`` injector site fires here so a SIGKILL *during* a
    preemption drain is a constructible chaos schedule."""
    from .scheduler import current_session
    sess = current_session()
    if (sess is not None and sess._drain_mode is not None and enabled()):
        from . import recovery
        kind = recovery.maybe_inject("sched.preempt",
                                     intercept=("stall",))
        if kind == "stall":
            # widen the drain window for kill/term races in chaos
            # schedules — the stall is injected, never organic
            time.sleep(0.25)
        return recovery.drain_consensus(getattr(env, "mesh", None), True)
    from . import preempt
    if not (preempt.armed() and enabled()):
        return False
    from . import recovery
    return recovery.drain_consensus(getattr(env, "mesh", None),
                                    preempt.requested())


def drain_abort(label: str) -> None:
    """Raise the preemption-grace drain: committed state is already
    durable (the caller sits at a checkpoint boundary and has flushed
    any pending sink state), so this records the resume token and exits
    via typed :class:`ResumableAbort` — a planned scale-down, not a
    fault.  The supervisor's relaunch (same or DIFFERENT topology)
    fast-forwards past everything committed inside the grace window."""
    from . import preempt, recovery
    token = flush_for_abort(label)
    recovery._record(label, "preempt", "drain")
    timing.bump("ckpt.preempt_drain")
    g = preempt.grace_seconds()
    if g is not None:
        left = preempt.remaining_s()
        why = (f"preemption notice received (grace {g:g}s"
               f"{'' if left is None else f', {left:.1f}s left'})")
    else:
        # scheduler-initiated drain (preemptive requeue / fleet
        # resize): no OS grace budget is armed
        why = "scheduler drain requested"
    raise ResumableAbort(
        f"{label}: {why} "
        "— current stage flushed and committed; rerun with "
        f"CYLON_TPU_RESUME=1 to fast-forward (resume token: {token}); a "
        "different world size re-shards committed state automatically",
        token=token)


def flush_for_abort(label: str) -> str:
    """The FINAL ladder rung's flush: committed state is already durable
    (every piece commits at its own stage boundary), so this records the
    resume token — a ``RESUME_TOKEN.json`` breadcrumb naming the stages
    this process committed — and returns the token (the checkpoint
    root's absolute path)."""
    root = ckpt_dir()
    token = os.path.abspath(root)
    try:
        os.makedirs(root, exist_ok=True)
        path = os.path.join(root, "RESUME_TOKEN.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"label": label, "pid": os.getpid(),
                       "stages": list(_OPEN_DIRS),
                       "resume": "rerun with CYLON_TPU_RESUME=1"}, f)
    except OSError:
        pass  # the committed manifests are the durable state; the
        # breadcrumb is best-effort
    # flight-recorder postmortem (obs/trace, armed runs only): the
    # last-N timeline events land alongside the manifests — the
    # multi-event successor of the single last_region() breadcrumb
    from ..obs import trace
    trace.postmortem(f"abort flush: {label}", dir_path=root)
    return token
