"""Global configuration for cylon_tpu.

The reference framework is int64-first (Arrow/pandas default integer keys,
BASELINE.json's 1B int64-key join).  JAX defaults to 32-bit; we enable x64 at
import so device tables can faithfully hold pandas/Arrow int64/float64 columns.
Set ``CYLON_TPU_X64=0`` to opt out (columns will then be downcast on transfer).

Reference analog: the CMake/feature-flag + env-var config surface
(cpp/CMakeLists.txt:129-441, redis_ucx_ucc_oob_context.cpp:104-105) collapses
into this module plus per-op option dataclasses.
"""

from __future__ import annotations

import os

import jax

X64_ENABLED = os.environ.get("CYLON_TPU_X64", "1") != "0"
if X64_ENABLED:
    jax.config.update("jax_enable_x64", True)

# Persistent compiled-program cache: TPC-H-class workloads compile dozens
# of distinct programs and a TPU compile costs seconds to minutes each;
# the persistent cache makes every rerun from the same place warm.
#
# Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax keeps its cache there
# by itself and this package sets no directory.  Where it is not, the
# directory is ONE fixed path inside the checkout (``<repo>/.jax_cache``,
# git-ignored): the path is part of the cache key's neighbourhood, so a
# directory that moves never hits.  ``CYLON_TPU_COMPILE_CACHE=0`` turns
# the cache off.
#
# CPU-only processes (JAX_PLATFORMS=cpu — the test rig, dryrun, multihost
# drivers) run UNCACHED, by turning the cache off: XLA:CPU executable
# (de)serialization segfaults nondeterministically (observed live across
# three full-suite runs, ~1% of compiles, crashing in
# put_executable_and_time / get_executable_and_time /
# backend_compile_and_load), and CPU compiles are fast enough not to
# need persistence.

#: the checkout's own cache directory (used only when jax was given none)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def _cpu_only() -> bool:
    # the programmatic config value is authoritative: it folds in the
    # JAX_PLATFORMS env default AND any jax.config.update('jax_platforms')
    # a test conftest/driver issued before importing this package
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


COMPILE_CACHE_ENABLED = not (
    _cpu_only() or os.environ.get("CYLON_TPU_COMPILE_CACHE") == "0")
if not COMPILE_CACHE_ENABLED:
    jax.config.update("jax_enable_compilation_cache", False)
elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)


def jax_cache_dir() -> str:
    """Directory jax's persistent compilation cache uses in this process
    ('' when the cache is off)."""
    if not COMPILE_CACHE_ENABLED:
        return ""
    return str(jax.config.jax_compilation_cache_dir or "")


def _env_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v not in ("0", "false", "False", "")


#: Print [BENCH] timing lines (reference: CYLON_BENCH_TIMER, util/macros.hpp:102).
BENCH_TIMINGS = _env_flag("CYLON_TPU_BENCH", False)

#: Phase-timing attribution mode (``CYLON_TPU_TIMING``).  ``block``
#: (default): ``timing.maybe_block`` syncs the device inside each region
#: so async work is charged to the phase that dispatched it — exact
#: attribution, but it SERIALIZES piece production against join compute,
#: perturbing exactly the overlap the pipeline exists for.  ``async``:
#: regions record dispatch-only wall time and the caller blocks ONCE at
#: iteration end (bench_smoke.py) — phase numbers stop hiding overlap.
TIMING_ASYNC = os.environ.get("CYLON_TPU_TIMING", "block") == "async"

#: Consume range pieces as PACKED windows (relational/piece.PackedPiece):
#: the pipelined join slices + unpacks lanes INSIDE the jitted join
#: program instead of materializing each piece to full-width HBM columns
#: and re-packing.  Off = the seed's materialize-then-join path (kept as
#: the equivalence reference; tests compare the two exactly).
PACKED_PIECES = _env_flag("CYLON_TPU_PACKED_PIECES", True)

#: Phase-overlapped piece scheduling (exec/pipeline.pipelined_join): the
#: setup phases (build sort, range bounds, probe targets, probe sort)
#: dispatch back-to-back with NO host sync between them — their host-side
#: outputs resolve in ONE batched pull at a designated sync point — and
#: per-piece phase work for piece r+1 dispatches while piece r is being
#: consumed (typed faults raised while dispatching ahead are HELD and
#: re-raised at the piece's consume point, so the recovery ladder sees
#: the same consensus-coherent event order with overlap on or off).
#: Off = the prior per-phase-sync dispatch behavior (escape hatch).
PACKED_OVERLAP = _env_flag("CYLON_TPU_PACKED_OVERLAP", True)

#: Donate per-piece scratch (phase-1 carry/payload buffers, splitter
#: operands, the pipeline's dead sorted-table columns at pack time)
#: through the jitted programs via donate_argnums, so the steady-state
#: piece loop reuses buffers instead of re-allocating per piece.  The
#: HBM ledger credits donated bytes against pack admission
#: (exec/memory.ensure_headroom(reuse=)).  Results are bit-equal with
#: donation on or off (tests/test_pipeline_packed.py::TestPackedPieces).
DONATE_BUFFERS = _env_flag("CYLON_TPU_DONATE", True)

#: Route the pipelined join's phase-1 probe (per-row range assignment
#: against the build side's key-group splitters) through the Pallas TPU
#: kernel in ops/pallas_probe.py instead of the XLA (rows x splitters)
#: comparison matrix.  Bit-equal by construction (same lexicographic
#: algebra); interpreter fallback exercises the kernel on CPU rigs.
#: Default off — opt in per run; eligibility (int-kind key operands,
#: tile-aligned capacity) still gates per call site.
PALLAS_PROBE = _env_flag("CYLON_TPU_PALLAS_PROBE", False)

#: AOT pre-compile (lower().compile()) the per-piece join programs for
#: every distinct piece-capacity pair BEFORE the range loop, so a
#: mid-stream capacity change never stalls dispatch on a compile.  The
#: AOT executable lands in the persistent compile cache (the in-process
#: jit call path re-loads it from there), so this only pays off where
#: that cache is enabled — accelerator processes; CPU runs skip it.
PREWARM_PIECE_PROGRAMS = _env_flag("CYLON_TPU_PREWARM", True)

#: Shape-family canonicalization at INGEST (exec/compiler.family_cap):
#: single-controller tables pad their row capacity to the same pow2 family
#: buckets the multi-rank distributor already uses, so N tenants with
#: near-miss row counts share ONE compiled program per plan shape instead
#: of compiling per-tenant.  Padding rides the existing validity lanes —
#: results stay bit- and order-equal (tests/test_compiler.py).  The
#: decision is a pure function of the row count (rank-uniform, no vote).
#: ``CYLON_TPU_SHAPE_FAMILIES=0`` restores exact-shape placement.
SHAPE_FAMILIES = _env_flag("CYLON_TPU_SHAPE_FAMILIES", True)

#: Bounded in-process compile ledger (exec/compiler): maximum LIVE
#: compiled programs per mesh across all program_cache builders; above it
#: the facade retires least-recently-used programs (re-use recompiles,
#: optionally warm from the persistent cache).  0 (default) = unbounded —
#: only the per-builder PROGRAM_CACHE_SIZE LRU applies.  In multiprocess
#: sessions the eviction count rides the count-consensus wire so every
#: rank drops the same programs.
COMPILE_BUDGET = int(os.environ.get("CYLON_TPU_COMPILE_BUDGET", "0"))

#: Facade-owned persistent compile-cache directory (exec/compiler):
#: houses the compile-intent journal, the quarantine ledger and the
#: warm-manifest (and, on accelerator platforms, arms jax's own disk
#: cache under ``<dir>/xla``).  Empty (default) = the facade's durable
#: layer is DISARMED: zero filesystem writes on the happy path.
COMPILE_CACHE_DIR = os.environ.get("CYLON_TPU_COMPILE_CACHE_DIR", "")

#: Compile watchdog deadline in seconds (0 = off, the default): each
#: facade-routed ``.lower()``/``.compile()``/first-trace call runs under
#: this timeout and a hung compile surfaces as a typed
#: CompileTimeoutError instead of wedging the rank (exec/compiler,
#: same worker-thread pattern as the exchange watchdog).
COMPILE_TIMEOUT_S = float(os.environ.get("CYLON_TPU_COMPILE_TIMEOUT_S", "0"))

#: High-cardinality string-key crossover: columns with at least MIN_ROWS
#: rows whose sampled distinct ratio reaches RATIO take the hashed-codes
#: path (core.column.HashedStrings) instead of building a sorted
#: dictionary — dictionary construction (np.unique over every value) is a
#: host-memory wall at ~1e8+ distinct strings.
STRING_HASH_MIN_ROWS = int(os.environ.get("CYLON_TPU_STRING_HASH_MIN",
                                          str(4_000_000)))
STRING_HASH_RATIO = float(os.environ.get("CYLON_TPU_STRING_HASH_RATIO",
                                         "0.5"))

#: Per-factory bound on cached compiled programs (shard_map/jit factories
#: are memoized on static args; long-lived processes joining many distinct
#: schemas would otherwise accumulate executables without limit).  LRU:
#: eviction drops the jit wrapper (and its executables); re-use recompiles.
PROGRAM_CACHE_SIZE = int(os.environ.get("CYLON_TPU_PROGRAM_CACHE", "256"))

#: Per-shard exchange RECEIVE allocation ceiling (bytes, accelerators
#: only): a predicted receive above this fails fast with an OOM-shaped
#: error BEFORE allocating — a real device OOM poisons this rig's
#: process, so preempting a doomed alloc is the only clean failure.  The
#: default leaves headroom under a 16 GB HBM for inputs + exchange
#: staging; the remedy for receive concentration is the heavy-key split.
EXCHANGE_RECV_BUDGET_BYTES = int(os.environ.get(
    "CYLON_TPU_EXCHANGE_RECV_BUDGET", str(12 * 1024**3)))
#: apply the receive guard on CPU meshes too (tests; host RAM is
#: normally far above HBM-sized budgets, so default off)
EXCHANGE_RECV_GUARD_CPU = _env_flag("CYLON_TPU_EXCHANGE_GUARD_CPU", False)

#: HBM budget for the resident-allocation ledger (exec/memory), in TOTAL
#: bytes across the mesh.  0 (default) = platform-detected: per-chip
#: ``bytes_limit`` × device count on accelerators, unlimited on CPU rigs
#: (host RAM, not HBM, is the ceiling there).  Set it below the resident
#: working set to force the host spill tier — cold packed sources evict
#: to host RAM and re-upload per piece window (docs/robustness.md).
HBM_BUDGET_BYTES = int(os.environ.get("CYLON_TPU_HBM_BUDGET", "0"))

#: Host spill tier switch (``CYLON_TPU_SPILL=0`` disables eviction; the
#: ledger keeps accounting either way).  With spill off, memory pressure
#: degrades through the pre-existing rungs only (chunk escalation /
#: typed abort).
SPILL_ENABLED = _env_flag("CYLON_TPU_SPILL", True)

#: Host-side ledger budget for the DISK tier (bytes of host-resident
#: spill pages across the process; 0 = unlimited, disk tier disarmed).
#: When device→host evictions push the host-resident spill balance past
#: this, cold host pages demote to per-rank spill files under
#: ``CYLON_TPU_SPILL_DIR`` — the residency ladder's final rung
#: (docs/robustness.md "Disk tier & scan pushdown").  With it unset the
#: disk tier adds ZERO filesystem writes and zero extra work.
HOST_BUDGET_BYTES = int(os.environ.get("CYLON_TPU_HOST_BUDGET", "0"))

#: Root directory for the disk tier's per-rank spill page files
#: (``<dir>/rank<r>/<owner>.a<j>.s<k>.spill.npy``).  Empty = a private
#: temp directory created lazily on the first demote.  Spill files are
#: PROCESS-TRANSIENT (unlike checkpoints): their hashes live in memory
#: and a fresh process never reads a predecessor's files.
SPILL_DIR = os.environ.get("CYLON_TPU_SPILL_DIR", "")

#: Exchange watchdog deadline in seconds (0 = off, the default): blocking
#: multihost exchange host-syncs run under this timeout and a peer hang
#: surfaces as a typed RankDesyncError (site + last-known phase attached)
#: instead of an infinite block.  See exec/recovery.exchange_watchdog and
#: docs/robustness.md.  Fault injection (CYLON_TPU_FAULTS, same doc) is
#: parsed by exec/recovery directly.
EXCHANGE_WATCHDOG_S = float(os.environ.get("CYLON_TPU_WATCHDOG_S", "0"))

#: A join side at or below this row count is REPLICATED (allgather)
#: instead of shuffling both sides — the broadcast-hash-join cutover.
BROADCAST_JOIN_ROWS = int(os.environ.get("CYLON_TPU_BROADCAST_JOIN_ROWS",
                                         "65536"))

# Heavy-key (skew) split tuning — reference analog: the sampled partition
# machinery of table.cpp:620-689 applied to skew (SURVEY.md §7 hard-part
# 4).  Detection runs on the ROW HASH of the (possibly multi-column) key
# tuple, so float keys and multi-column keys participate uniformly and
# the flag predicate is exactly the shuffle-routing hash.
#: Rows sampled per shard for the heavy-hitter estimate:
SKEW_SAMPLE = int(os.environ.get("CYLON_TPU_SKEW_SAMPLE", "4096"))
#: Minimum per-shard sampled share for a key to enter the estimate:
SKEW_MIN_SHARE = float(os.environ.get("CYLON_TPU_SKEW_MIN_SHARE", "0.01"))
#: (How heavy a key must be is no setting: relational/skew.split_rule,
#: the owner's projected load against a bound with a chip run behind it.)
#: At most this many heavy keys split per join:
SKEW_MAX_KEYS = int(os.environ.get("CYLON_TPU_SKEW_MAX_KEYS", "8"))
#: Replication guard: skip the split when the BUILD side's heavy rows,
#: replicated world-ways, would exceed GUARD_RATIO x the build size AND
#: GUARD_ROWS rows — W-way replication would recreate the blow-up the
#: split avoids.
SKEW_GUARD_RATIO = float(os.environ.get("CYLON_TPU_SKEW_GUARD_RATIO", "2.0"))
SKEW_GUARD_ROWS = int(os.environ.get("CYLON_TPU_SKEW_GUARD_ROWS", "65536"))

# Adaptive skew-split join (relational/skew.py — the plan facade, lint
# rule TS115; docs/skew.md).  Heavy probe keys detected through the
# weighted Misra-Gries sketch (obs/sketch) are split across a contiguous
# rank group (order-preserving salted sub-partitioning) with the matching
# build rows duplicate-broadcast to the group; the output is stitched
# back bit-equal AND order-equal to the unsplit hash plan.
#: Master switch (default ARMED — "0" falls back to plain hashing for
#: inner/left/right/outer; semi/anti keep the legacy round-robin spread):
SKEW_SPLIT = os.environ.get("CYLON_TPU_SKEW_SPLIT", "1") != "0"
#: Fan-out oversubscription: a key with estimated share s splits over
#: ceil(s * world * FANOUT_FACTOR) contiguous ranks (clamped to
#: [2, world] and to the key's exact row count):
SKEW_FANOUT_FACTOR = float(os.environ.get("CYLON_TPU_SKEW_FANOUT_FACTOR",
                                          "1.25"))

# Multi-slice topology tier (cylon_tpu/topo — the plan facade, lint rule
# TS116; docs/topology.md).  SURVEY §5.8: "DCN between pods via jax's
# multi-slice runtime" — inter-slice ≠ intra-slice, so the exchange goes
# hierarchical on a multi-slice fabric: slice-local all-to-all over ICI
# (align rows on the destination's gateway-local rank), then ONE
# aggregated cross-slice exchange over DCN, bit- and order-equal to the
# flat plan by the slice-major layout.
#: Master switch for the hierarchical (two-hop) shuffle route on
#: multi-slice topologies.  "0" keeps the flat one-hop exchange on any
#: topology (the comparison baseline chaos/bench legs run).  Single-slice
#: topologies always take the flat route regardless — zero extra
#: collectives, zero host syncs.
TOPO_SHUFFLE = _env_flag("CYLON_TPU_TOPO_SHUFFLE", True)
#: ``CYLON_TPU_SLICES=<n>`` declares an n-slice two-tier fabric over the
#: visible devices (contiguous slice-major blocks) — the CPU-grid
#: simulation knob tests and chaos schedules use; parsed by
#: cylon_tpu/topo/model.py (real multi-slice TPU fleets are discovered
#: from device attributes instead).

#: Distributed-sort splitter samples per shard: grows with the world size
#: (more shards need finer splitters for the same balance; the reference's
#: SortOptions.num_samples is likewise caller-tunable, table.hpp:358).
SORT_SAMPLES_PER_SHARD = int(os.environ.get("CYLON_TPU_SORT_SAMPLES", "0"))


def sort_samples(world: int) -> int:
    """Splitter samples per shard: explicit override, else 64 minimum
    scaled linearly with the world (16 x W) so splitter resolution keeps
    pace with the number of cut points."""
    if SORT_SAMPLES_PER_SHARD > 0:
        return SORT_SAMPLES_PER_SHARD
    return max(64, 16 * world)


def pow2ceil(n: int) -> int:
    """Bucket a dynamic capacity to the next 2^(b-5) step for n in
    (2^(b-1), 2^b] (exact powers of two below 16Ki): 16 steps per octave,
    worst-case overshoot 2^(b-5)/2^(b-1) = 6.25%.  Keeps the family of
    compiled shapes logarithmic while bounding overshoot — at tens of
    millions of rows every output-space gather/scatter pays for overshoot
    (~15 ns/row measured), which dwarfs the marginal compiles (and
    capacity hysteresis amortizes those anyway)."""
    n = max(int(n), 1)
    if n <= 16384:
        return 1 << (n - 1).bit_length()
    step = 1 << ((n - 1).bit_length() - 5)
    return -(-n // step) * step
