"""Chaos-soak harness for the recovery ladder + durable checkpoint rung.

Seeded randomized fault schedules over every injector site
(``CYLON_TPU_FAULTS`` grammar, docs/robustness.md) driven against a
TPC-H-shaped pipelined join+groupby workload running in a CHILD
subprocess, so the ``kill`` fault kind (SIGKILL mid-range-loop) and the
``ResumableAbort`` path can actually be survived and resumed:

* every schedule must end in a BIT-EQUAL result (sha over the sorted
  result columns' raw bytes vs an un-injected baseline), possibly after
  the consensus retry ladder degraded the run in-process;
* or in a hard crash / typed ``ResumableAbort`` — then the harness
  reruns the child with ``CYLON_TPU_RESUME=1`` against the surviving
  checkpoint directory and THAT run must be bit-equal, fast-forwarding
  past committed pieces (``resume_fast_forwarded_pieces``) where any
  were committed;
* recovery-event counts stay bounded (the ladder's escalation is finite
  by construction — an unbounded count means a retry loop escaped it).

The first four schedules are pinned (kill-and-resume, corrupt-on-write
then kill, corrupt-on-load during resume, and kill-and-resume with the
phase-overlap escape hatch OFF — ``CYLON_TPU_PACKED_OVERLAP=0`` must
stay bit-equal to the overlap-on baseline even through a crash+resume)
so the acceptance paths run on every seed; the rest are drawn from
``--seed``.  Randomized draws run under the DEFAULT dispatch config,
which has the overlapped scheduler on — every drawn schedule therefore
also soaks deferred-fault re-raising (exec/pipeline._PieceFuture).

``--stream`` switches to the STREAMING-INGEST acceptance flow
(cylon_tpu/stream): a seeded micro-batch stream feeds a StreamTable +
IncrementalView whose absorbed partials commit durably per batch; the
pinned schedules SIGKILL the process mid-ingest (``stream.append::3=
kill`` and a kill during the view's ckpt.write) and the resumed rerun
must fast-forward the committed stream-view state (ffwd > 0; the
per-batch partials are the durable unit — windowed-join buffers replay
from upstream) with the final view bit-equal to the baseline.

``--concurrent K`` switches to the MULTI-TENANT acceptance flow
(exec/scheduler): K differently-seeded serving sessions interleave on
one mesh; the pinned schedule SIGKILLs the process mid-query in tenant
t0 only (the ``@session`` injector grammar, per-session occurrence
counting), and the resumed rerun must fast-forward t0's committed
pieces while EVERY tenant's answer stays bit-equal to its solo
(single-session) run — crash isolation under multi-tenancy.

``--oocore`` switches to the OUT-OF-CORE acceptance flow (the disk
tier, docs/robustness.md "Disk tier & scan pushdown"): the standard
join+sink workload runs under ``CYLON_TPU_HBM_BUDGET`` +
``CYLON_TPU_HOST_BUDGET`` caps sized below its working set, so packed
sources evict to host AND demote to per-rank spill files.  Pinned
schedules: a capped happy-path run (bit-equal with ``disk_events > 0``
and ``bytes_to_disk > 0``), ENOSPC mid-demote (typed degrade to
in-memory — no crash, bit-equal), corrupt-on-promote (the ladder
recomputes the owner — bit-equal, never a wrong answer), SIGKILL
mid-demote then resume (bit-equal), and the UNARMED contract leg (no
host budget ⇒ zero disk events and zero spill-file writes, asserted).

``--elastic`` switches to the ELASTIC-RESUME acceptance flow
(docs/robustness.md "Elastic resume & preemption grace"): a TWO-stage
workload (sinkless pipelined join feeding a join+sink) checkpoints at
world=2 in a subprocess; pinned schedules SIGKILL it mid-stage-2 and
resume at world=1 (the completed stage 1 must RE-SHARD and
fast-forward — ``resume_resharded_pieces > 0`` — while the interrupted
stage 2 recomputes, counted in ``resume_world_mismatch``), resume at
world=2 plain (no reshard, ordinary fast-forward), kill the world=1
resume AGAIN and resume at world=2-after-reshard (the rewritten
world=1 manifests re-shard back up), inject ``ckpt.reshard`` corruption
during a reshard (degrades to recompute, never a wrong answer), and
deliver SIGTERM with the preemption grace armed (the child must exit
via typed ResumableAbort — exit 17, not a signal death — within the
grace budget).  Every schedule must end bit-equal to the uninterrupted
world=2 baseline.

``--multislice`` switches to the MULTI-SLICE TOPOLOGY acceptance flow
(cylon_tpu/topo, docs/topology.md): a join+groupby workload on a
simulated two-tier grid (``CYLON_TPU_SLICES=2`` over a world-4 CPU
mesh) whose FLAT-routed run (``CYLON_TPU_TOPO_SHUFFLE=0``) is the
bit-equality oracle.  Pinned schedules: the armed happy path (a voted
topology plan, bit-equal, cross-slice DCN messages at ~1/R of the flat
plan's); a capacity fault inside the hierarchical exchange (the ladder
retries and must re-adopt the IDENTICAL voted plan hash — topology
derivation is deterministic); SIGKILL of one WHOLE SLICE mid-run
(simulated as a hard kill of the checkpointed two-stage elastic
workload at world=4/slices=2, resumed on the surviving world=2 single
slice — the PR 9 elastic re-shard must fast-forward stage 1 bit-equal
and the resumed topology re-votes); and the unarmed single-slice
contract leg: with no slice declaration the ARMED route must vote
nothing and move exactly the flat run's exchange rows and exchange
count — zero extra collectives, zero host syncs.

``--compile`` switches to the COMPILE-LIFECYCLE acceptance flow
(cylon_tpu/exec/compiler, docs/robustness.md "Compile lifecycle"): the
standard join+sink workload with the facade's persistent compile cache
armed per-leg (``CYLON_TPU_COMPILE_CACHE_DIR``).  Pinned legs: SIGKILL
*inside* a guarded ``.lower()/.compile()`` (the ``compile.build``
injector site) — the crash leaves the rank's intent journal on disk,
and the rerun against the same dir must ADOPT the orphan into the
crash quarantine (``quarantine_adoptions > 0``, the poisoned program
surfaces as typed ``CompileQuarantinedError``) and still complete
bit-equal via the ladder's capacity rung (a re-planned chunk count
compiles DIFFERENT shapes, skirting the quarantined signature);
corrupt-on-build (the manifest entry is poisoned, the relaunch's
arm-time hash validation drops it — ``manifest_drops > 0`` — and the
recompile is bit-equal); an injected compile stall with the watchdog
budget armed (typed ``CompileTimeoutError``, never a hang, and the
SAME dir reruns clean — a timeout does not poison the cache); and the
unarmed contract leg (no compile env vars ⇒ the facade never arms,
never creates its dir, and writes nothing).

``--audit`` switches to the DATA-INTEGRITY AUDIT acceptance flow
(cylon_tpu/exec/integrity, docs/robustness.md "Integrity audit tier"):
a monolithic join+groupby whose unarmed run is the bit-equality oracle.
Pinned legs: the armed clean run (``CYLON_TPU_AUDIT=1`` — bit-equal,
fingerprint checks > 0, zero violations, and exactly the unarmed run's
exchange rows/count: the audit adds no exchange traffic); an injected
silent corruption (``exchange.corrupt=corrupt`` flips one exchanged
byte) which the armed fingerprint must catch as a typed
``DataIntegrityError`` the ladder converts into ONE recompute —
bit-equal, with the ``integrity`` recovery event on the record;
PERSISTENT corruption (``exchange.corrupt::*=corrupt``) which must end
in a typed abort, never a silent wrong answer; the same one-shot
corruption under the skew-split route (``CYLON_TPU_SKEW_SPLIT=1``) and
under the two-tier topology route (``CYLON_TPU_SLICES=2`` +
``CYLON_TPU_TOPO_SHUFFLE=1``) — caught at the post-exchange stage
either way, recovered onto the same voted plan, bit-equal; and the
UNARMED contract leg: zero fingerprint checks, zero fingerprint votes
(the conservation laws still run — they are free host math).

``--skew`` switches to the ADAPTIVE-SKEW-SPLIT acceptance flow
(docs/skew.md): a monolithic skewed-key join+groupby (one hot key on
~80% of probe rows) whose unsplit run (``CYLON_TPU_SKEW_SPLIT=0``) is
the bit-equality oracle.  Pinned schedules: the armed happy path (a
non-empty voted plan, bit-equal), an exchange capacity fault INSIDE the
split (the ladder's retry must re-detect and re-vote the IDENTICAL plan
hash — determinism of the detection inputs), a spill fault under an
HBM budget cap (same contract), SIGKILL mid-workload then a fresh rerun
(same plan hash, bit-equal), and the unarmed-at-skew-0 contract leg: at
skew 0 the ARMED run must vote nothing, split nothing and move exactly
the exchange rows the unsplit run moves — zero extra collectives.

Usage::

    python scripts/chaos_soak.py --seed 7                 # 20 schedules
    python scripts/chaos_soak.py --seed 7 --schedules 4 --rows 1500
    python scripts/chaos_soak.py --concurrent 3 --rows 2000
    python scripts/chaos_soak.py --elastic --rows 1500 --chunks 3
    python scripts/chaos_soak.py --oocore --rows 2000 --chunks 3
    python scripts/chaos_soak.py --skew --rows 4000
    python scripts/chaos_soak.py --compile --rows 3000
    python scripts/chaos_soak.py --multislice --rows 3000
    python scripts/chaos_soak.py --audit --rows 3000

Exit status 0 = every schedule converged; 1 otherwise.  A trimmed soak
runs in CI as a slow-marked test (tests/test_checkpoint.py); the
concurrent flow as a slow-marked test in tests/test_scheduler.py.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

#: (site, eligible kinds) for the randomized draws — `stall`/`desync`
#: are excluded: a desync is terminal by design (never retried), so a
#: schedule containing one cannot converge and would only test the
#: harness, not the ladder
SITE_KINDS = [
    ("shuffle.recv_guard", ["predicted", "device_oom", "capacity"]),
    ("join.piece_cap", ["capacity"]),
    ("groupby.device_oom", ["device_oom", "predicted"]),
    ("spill.evict", ["predicted"]),
    ("ckpt.write", ["corrupt", "device_oom", "kill"]),
    ("ckpt.load", ["corrupt"]),
]

#: per-run ceiling on logged recovery events: the ladder's schedule is
#: spill + 2 chunk rungs (+1 cap rung) per operator — a soak workload
#: crossing this is looping, not recovering
MAX_RECOVERY_EVENTS = 8

RESUMABLE_EXIT = 17


# ---------------------------------------------------------------------------
# worker: one workload run in this process (spawned by the parent)
# ---------------------------------------------------------------------------

def _result_sha(df) -> str:
    import numpy as np
    h = hashlib.sha256()
    for col in sorted(df.columns):
        h.update(np.ascontiguousarray(df[col].to_numpy()).tobytes())
    return h.hexdigest()


def worker(args) -> int:
    import numpy as np

    import cylon_tpu as ct
    from cylon_tpu.ctx.context import CPUMeshConfig
    from cylon_tpu.exec import GroupBySink, checkpoint, pipelined_join, \
        recovery
    from cylon_tpu.status import ResumableAbort

    recovery.install_faults(None)   # validate the env grammar up front
    env = ct.CylonEnv(config=CPUMeshConfig(world_size=args.world))

    # TPC-H-shaped: orders ⋈ lineitem on the order key, aggregated per
    # order — integer "money" so every retry/restore path is exactly
    # bit-comparable.  Seeded (per tenant): the resumed process rebuilds
    # the identical inputs, which is what makes the stage plan tokens
    # match.
    def make_workload(seed: int, rows: int):
        def attempt(nc):
            rng = np.random.default_rng(seed)
            n_ord = max(rows // 4, 64)
            orders = ct.Table.from_pydict(
                {"o_orderkey": np.arange(n_ord, dtype=np.int64),
                 "o_shippriority": rng.integers(0, 5,
                                                n_ord).astype(np.int64)},
                env)
            lineitem = ct.Table.from_pydict(
                {"l_orderkey": rng.integers(0, n_ord,
                                            rows).astype(np.int64),
                 "l_quantity": rng.integers(1, 51, rows).astype(np.int64),
                 "l_extendedprice": rng.integers(900_00, 10_500_00,
                                                 rows).astype(np.int64)},
                env)
            sink = GroupBySink("l_orderkey", [("l_quantity", "sum"),
                                              ("l_extendedprice", "sum")])
            pipelined_join(lineitem, orders, "l_orderkey", "o_orderkey",
                           how="inner", n_chunks=nc, sink=sink)
            return sink.finalize()
        return attempt

    if args.stream:
        return _worker_stream(args, env)

    if args.elastic:
        return _worker_elastic(args, env)

    if args.skew:
        return _worker_skew(args, env)

    if args.multislice:
        return _worker_topo(args, env)

    if args.compile_flow:
        return _worker_compile(args, env, make_workload)

    if args.fleet:
        return _worker_fleet(args, env, make_workload)

    if args.concurrent > 1:
        return _worker_concurrent(args, env, make_workload)

    attempt = make_workload(20260803, args.rows)
    try:
        out = recovery.run_with_recovery(
            lambda: attempt(args.chunks), True, attempt, "soak", env=env)
    except ResumableAbort as e:
        print(json.dumps({"resumable": True, "token": e.token,
                          "events": len(recovery.recovery_events())}),
              flush=True)
        return RESUMABLE_EXIT

    from cylon_tpu.exec import memory
    df = out.to_pandas().sort_values("l_orderkey").reset_index(drop=True)
    print(json.dumps({
        "ok": True, "sha": _result_sha(df), "rows": int(len(df)),
        "events": len(recovery.recovery_events()),
        "event_list": recovery.recovery_events(),
        # disk-tier counters: the --oocore flow asserts these
        **{k: v for k, v in memory.stats().items()
           if k.startswith(("disk_", "bytes_to_disk", "bytes_from_disk"))},
        **checkpoint.stats(),
    }), flush=True)
    return 0


def _worker_stream(args, env) -> int:
    """The streaming-ingest acceptance workload (cylon_tpu/stream): a
    seeded micro-batch stream appended into a StreamTable + an
    IncrementalView whose absorbed partials commit durably per batch
    (one checkpoint piece per append with CYLON_TPU_CKPT_DIR armed).  A
    SIGKILL mid-ingest (``stream.append::N=kill`` or a kill during the
    view's ckpt.write) crashes the process between commits; the resumed
    rerun replays the SAME seeded stream, fast-forwards the committed
    stream-view state — the durable per-batch partials, the only
    checkpointed streaming state (windowed-join buffers replay from
    upstream; docs/streaming.md) — with ffwd > 0, and the final view
    must be bit-equal to the uninterrupted run."""
    import numpy as np

    from cylon_tpu.exec import checkpoint, recovery
    from cylon_tpu.stream import IncrementalView, StreamTable

    rng = np.random.default_rng(20260804)
    st = StreamTable(env, key="k", name="soak")
    view = IncrementalView(
        st, "k", [("v", "sum"), ("v", "mean"), ("v", "var")],
        name="soak_view", env=env)
    n_batches = max(args.rows // 500, 6)
    for _ in range(n_batches):
        st.append({"k": rng.integers(0, 64, 500).astype(np.int64),
                   "v": rng.integers(-100, 100, 500).astype(np.float64)})
    df = view.read().to_pandas().sort_values("k").reset_index(drop=True)
    print(json.dumps({
        "ok": True, "sha": _result_sha(df), "rows": int(len(df)),
        "batches": n_batches, "ffwd": view.fast_forwarded,
        "events": len(recovery.recovery_events()),
        **checkpoint.stats(),
    }), flush=True)
    return 0


def run_stream(args) -> int:
    """The ``--stream`` acceptance flow (pinned, not drawn): baseline →
    SIGKILL mid-ingest with checkpointing armed → resume.  The resume
    must fast-forward the committed stream-view state (ffwd > 0 —
    restored per-batch partials, not recomputed appends; windowed-join
    buffers are not checkpointed and replay from upstream) and end
    bit-equal to the uninterrupted baseline."""
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_stream_")
    failures: list = []

    base_p, base = _spawn(args, os.path.join(args.workdir, "base"), "",
                          resume=False, stream=True)
    if base_p.returncode != 0 or not base or not base.get("sha"):
        print((base_p.stdout + base_p.stderr)[-3000:], file=sys.stderr)
        print("chaos-soak: stream baseline failed", file=sys.stderr)
        return 1
    print(f"# stream baseline sha={base['sha'][:16]} "
          f"batches={base['batches']}", flush=True)

    # pinned schedules: a hard kill at the Nth append, and one during
    # the view's checkpoint write — both mid-ingest, both must resume
    for faults in ("stream.append::3=kill", "ckpt.write::2=kill"):
        killdir = os.path.join(args.workdir,
                               faults.split("=")[0].replace(":", "_"))
        p, info = _spawn(args, killdir, faults, resume=False, stream=True)
        if p.returncode != -9:
            failures.append(
                f"stream kill ({faults!r}) did not crash the process "
                f"(rc={p.returncode})")
            continue
        if not os.path.exists(os.path.join(killdir,
                                           "TRACE_POSTMORTEM.json")):
            failures.append(f"stream kill ({faults!r}) left no "
                            "TRACE_POSTMORTEM.json breadcrumb")
        p2, info2 = _spawn(args, killdir, "", resume=True, stream=True)
        if p2.returncode != 0 or not info2:
            failures.append(f"stream resume ({faults!r}) failed "
                            f"rc={p2.returncode}: "
                            f"{(p2.stdout + p2.stderr)[-2000:]}")
        elif info2.get("sha") != base["sha"]:
            failures.append(
                f"stream resume ({faults!r}) diverged: {info2}")
        elif not info2.get("ffwd"):
            failures.append(
                f"stream resume ({faults!r}) recomputed committed "
                f"window state: {info2}")
        else:
            print(f"# stream {faults!r} + resume -> ok "
                  f"(ffwd={info2['ffwd']})", flush=True)

    # injection sanity: a predicted fault at the append site surfaces
    # TYPED — stream.append has no retry rung (an append is not a
    # guarded operator with a fallback), so the contract is a loud
    # typed abort, never a silent wrong answer
    p, info = _spawn(args, os.path.join(args.workdir, "pred"),
                     "stream.append::2=predicted", resume=False,
                     stream=True)
    if p.returncode == 0:
        failures.append(
            f"stream predicted fault was swallowed (rc=0): {info}")
    elif "PredictedResourceExhausted" not in (p.stdout + p.stderr):
        failures.append(
            f"stream predicted fault did not surface typed "
            f"(rc={p.returncode})")
    else:
        print("# stream predicted-fault schedule -> ok (typed abort)",
              flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"stream": True, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def _worker_compile(args, env, make_workload) -> int:
    """The compile-lifecycle acceptance workload (docs/robustness.md,
    "Compile lifecycle"): the standard join+sink workload under the
    consensus ladder, with the compile facade armed per-leg through the
    environment (CYLON_TPU_COMPILE_CACHE_DIR / _COMPILE_TIMEOUT_S /
    CYLON_TPU_FAULTS at the ``compile.build`` site).  The JSON line
    reports the result sha plus the facade's full counter set and the
    persistent dir's file listing — the parent's evidence for quarantine
    adoption, manifest poison drops, rewarm expectations and the
    unarmed zero-write contract.  A watchdog timeout the ladder cannot
    cure exits 3; an UNCURED quarantine (the ladder's re-planned shapes
    still hit the poisoned signature) exits 4 — both typed, never
    hangs."""
    from cylon_tpu.exec import compiler, recovery
    from cylon_tpu.status import (CompileQuarantinedError,
                                  CompileTimeoutError)

    attempt = make_workload(20260807, args.rows)
    try:
        out = recovery.run_with_recovery(
            lambda: attempt(args.chunks), True, attempt, "soak", env=env)
    except CompileTimeoutError as e:
        print(json.dumps({"timeout_typed": True, "site": e.site,
                          "signature": e.signature,
                          **compiler.stats()}), flush=True)
        return 3
    except CompileQuarantinedError as e:
        print(json.dumps({"quarantined_typed": True,
                          "signature": e.signature,
                          **compiler.stats()}), flush=True)
        return 4
    df = out.to_pandas().sort_values("l_orderkey").reset_index(drop=True)
    d = compiler.cache_dir()
    print(json.dumps({
        "ok": True, "sha": _result_sha(df), "rows": int(len(df)),
        "armed": bool(compiler.armed()),
        "cache_files": (sorted(os.listdir(d))
                        if d and os.path.isdir(d) else []),
        "events": len(recovery.recovery_events()),
        **compiler.stats(),
    }), flush=True)
    return 0


def run_compile(args) -> int:
    """The ``--compile`` acceptance flow (pinned, not drawn) — see the
    module docstring.  The kill leg's occurrence index targets a PIECE
    compile (chunk-shape-dependent), so the rerun's quarantine is
    curable by the ladder's capacity rung: re-planned chunk counts
    compile different shapes and skirt the poisoned signature."""
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_compile_")
    failures: list = []
    #: the first join _packed_count_fn compile in the pinned workload's
    #: deterministic fresh-compile order (rows=3000, chunks=4, world=4)
    #: — a per-piece program whose shapes change with the chunk count
    kill_nth = 21

    def spawn(tag, faults, cache_dir=None, extra=None):
        workdir = os.path.join(args.workdir, tag)
        env_extra = {}
        if cache_dir is not None:
            env_extra["CYLON_TPU_COMPILE_CACHE_DIR"] = cache_dir
        env_extra.update(extra or {})
        return _spawn(args, workdir, faults, resume=False,
                      extra_env=env_extra, compile_flow=True)

    # unarmed baseline: the bit-equality oracle AND the zero-write leg
    p, base = spawn("base", "")
    if p.returncode != 0 or not base or not base.get("sha"):
        print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
        print("chaos-soak: compile baseline failed", file=sys.stderr)
        return 1
    print(f"# compile unarmed baseline sha={base['sha'][:16]}", flush=True)
    if base.get("armed"):
        failures.append(f"facade armed with no compile env vars: {base}")
    if base.get("quarantined") or base.get("watchdog_timeouts") \
            or base.get("expected_warm"):
        failures.append(f"unarmed run exercised armed-only state: {base}")

    # kill mid-compile → orphan intent → rerun adopts + quarantines +
    # completes bit-equal via the ladder's re-planned shapes
    kdir = os.path.join(args.workdir, "kill", "ccache")
    p, _ = spawn("kill", f"compile.build::{kill_nth}=kill",
                 cache_dir=kdir)
    if p.returncode != -9:
        failures.append(f"kill mid-compile did not crash the process "
                        f"(rc={p.returncode})")
    elif not os.path.exists(os.path.join(kdir, "intent.rank0.json")):
        failures.append("killed compile left no intent journal on disk")
    else:
        p2, info2 = spawn("kill_rerun", "", cache_dir=kdir)
        if p2.returncode != 0 or not info2 \
                or info2.get("sha") != base["sha"]:
            failures.append(f"rerun after kill mid-compile diverged "
                            f"(rc={p2.returncode}): {info2}\n"
                            f"{(p2.stdout + p2.stderr)[-2000:]}")
        elif not info2.get("quarantine_adoptions"):
            failures.append(f"rerun never adopted the orphan intent: "
                            f"{info2}")
        elif not info2.get("quarantined"):
            failures.append(f"adopted orphan not quarantined: {info2}")
        elif not info2.get("expected_warm"):
            failures.append(f"rerun saw no rewarm expectations from the "
                            f"killed run's manifest: {info2}")
        elif "quarantine.json" not in info2.get("cache_files", []):
            failures.append(f"quarantine not persisted: {info2}")
        else:
            print(f"# compile kill + rerun -> ok (adoptions="
                  f"{info2['quarantine_adoptions']} expected_warm="
                  f"{info2['expected_warm']})", flush=True)

    # corrupt-on-build: the poisoned manifest entry fails its content
    # hash at the relaunch's arm time — dropped to a clean recompile,
    # bit-equal, never wrong code
    cdir = os.path.join(args.workdir, "corrupt", "ccache")
    p, info = spawn("corrupt", "compile.build::1=corrupt",
                    cache_dir=cdir)
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"corrupt-on-build leg diverged "
                        f"(rc={p.returncode}): {info}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    else:
        p2, info2 = spawn("corrupt_rerun", "", cache_dir=cdir)
        if p2.returncode != 0 or not info2 \
                or info2.get("sha") != base["sha"]:
            failures.append(f"relaunch over poisoned manifest diverged "
                            f"(rc={p2.returncode}): {info2}")
        elif not info2.get("manifest_drops"):
            failures.append(f"poisoned manifest entry not dropped at "
                            f"arm time: {info2}")
        else:
            print(f"# compile corrupt + relaunch -> ok (drops="
                  f"{info2['manifest_drops']})", flush=True)

    # injected stall with the watchdog budget armed: typed
    # CompileTimeoutError (exit 3), never a hang — and the SAME dir
    # then reruns clean (a timeout does not poison the cache)
    sdir = os.path.join(args.workdir, "stall", "ccache")
    p, info = spawn("stall", "compile.build::1=stall", cache_dir=sdir,
                    extra={"CYLON_TPU_COMPILE_TIMEOUT_S": "0.5"})
    if p.returncode != 3 or not info or not info.get("timeout_typed"):
        failures.append(f"stall did not surface a typed compile timeout "
                        f"(rc={p.returncode}): {info}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    elif not info.get("watchdog_timeouts"):
        failures.append(f"watchdog timeout not counted: {info}")
    else:
        p2, info2 = spawn("stall_rerun", "", cache_dir=sdir)
        if p2.returncode != 0 or not info2 \
                or info2.get("sha") != base["sha"]:
            failures.append(f"rerun after stall diverged "
                            f"(rc={p2.returncode}): {info2}")
        elif info2.get("quarantine_adoptions"):
            failures.append(f"a watchdog timeout left an orphan intent "
                            f"(must clear in finally): {info2}")
        else:
            print("# compile stall -> ok (typed timeout, dir reruns "
                  "clean)", flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"compile": True, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def _worker_skew(args, env) -> int:
    """The adaptive-skew-split acceptance workload (docs/skew.md): a
    monolithic skewed-key inner join + groupby-sum on the DataFrame
    engine's default route.  ``--skew-frac`` shapes the probe key
    column (0.0 = the unarmed contract leg); CYLON_TPU_SKEW_SPLIT in
    the environment arms/disarms the route.  The JSON line reports the
    result sha, the voted plan hash (None when the join ran unsplit)
    and the always-on exchange row counter — the flow's zero-extra-
    collectives evidence."""
    import numpy as np

    import cylon_tpu as ct
    from cylon_tpu.exec import integrity, recovery
    from cylon_tpu.obs import metrics
    from cylon_tpu.relational import groupby_aggregate, join_tables
    from cylon_tpu.relational import skew as skew_facade

    rng = np.random.default_rng(20260805)
    n = max(args.rows, 2048)
    mv = max(int(n * 0.9), 8)
    hot = np.int64(mv // 2)
    lk = rng.integers(0, mv, n).astype(np.int64)
    if args.skew_frac > 0.0:
        lk = np.where(rng.random(n) < args.skew_frac, hot, lk)
    rk = rng.integers(0, mv, n).astype(np.int64)
    rk[rk == hot] = hot + 1
    rk[0] = hot
    lt = ct.Table.from_pydict(
        {"k": lk, "a": rng.integers(0, mv, n).astype(np.int64)}, env)
    rt = ct.Table.from_pydict(
        {"k": rk, "b": rng.integers(0, mv, n).astype(np.int64)}, env)

    # injected recoverable faults (capacity, spill, device_oom shapes)
    # are handled by the operators' own ladders inside these calls; a
    # `kill` kind SIGKILLs mid-flight and the parent reruns fresh
    j = join_tables(lt, rt, "k", "k", how="inner")
    out = groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum")])
    plan = skew_facade.last_plan()
    df = out.to_pandas().sort_values("k").reset_index(drop=True)
    print(json.dumps({
        "ok": True, "sha": _result_sha(df), "rows": int(len(df)),
        "events": len(recovery.recovery_events()),
        "event_list": recovery.recovery_events(),
        "plan_hash": (format(plan.plan_hash(), "016x")
                      if plan is not None else None),
        "skew_split_joins": int(metrics.counter("skew_split_joins").value),
        "exchange_rows": int(metrics.counter("exchange_rows_total").value),
        # integrity-audit counters: the --audit flow asserts these
        **{f"audit_{k}": v for k, v in integrity.stats().items()
           if k in ("conservation_checks", "fingerprint_checks",
                    "fingerprint_votes", "violations",
                    "corruptions_injected")},
    }), flush=True)
    return 0


def _worker_topo(args, env) -> int:
    """The multi-slice topology acceptance workload (docs/topology.md):
    a monolithic join + groupby-sum whose route — flat vs hierarchical
    two-hop — is controlled by CYLON_TPU_SLICES / CYLON_TPU_TOPO_SHUFFLE
    in the environment.  The JSON line reports the result sha, the
    voted topology plan hash (None when every exchange routed flat),
    the always-on exchange counters (the zero-extra-collectives
    evidence) and the per-tier DCN message/wire counters (the ~1/R
    cross-slice instrument)."""
    import numpy as np

    import cylon_tpu as ct
    from cylon_tpu.exec import integrity, recovery
    from cylon_tpu.obs import metrics
    from cylon_tpu.relational import groupby_aggregate, join_tables
    from cylon_tpu.topo import model as topo_model

    rng = np.random.default_rng(20260806)
    n = max(args.rows, 2048)
    mv = max(int(n * 0.9), 8)
    lt = ct.Table.from_pydict(
        {"k": rng.integers(0, mv, n).astype(np.int64),
         "a": rng.integers(0, mv, n).astype(np.int64)}, env)
    rt = ct.Table.from_pydict(
        {"k": rng.integers(0, mv, n).astype(np.int64),
         "b": rng.integers(0, mv, n).astype(np.int64)}, env)
    j = join_tables(lt, rt, "k", "k", how="inner")
    out = groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum")])
    plan = topo_model.last_plan()
    df = out.to_pandas().sort_values("k").reset_index(drop=True)
    print(json.dumps({
        "ok": True, "sha": _result_sha(df), "rows": int(len(df)),
        "events": len(recovery.recovery_events()),
        "event_list": recovery.recovery_events(),
        "topo_plan_hash": (format(plan.plan_hash(), "016x")
                           if plan is not None else None),
        "topo_plans_voted": int(
            metrics.counter("topo_plans_voted").value),
        "exchange_rows": int(metrics.counter("exchange_rows_total").value),
        "exchange_count": int(metrics.counter("exchange_count").value),
        "dcn_rows": int(metrics.counter("exchange_dcn_rows_total").value),
        "dcn_messages": int(
            metrics.counter("exchange_dcn_messages_total").value),
        "dcn_wire_bytes": int(
            metrics.counter("exchange_dcn_wire_bytes_total").value),
        # integrity-audit counters: the --audit flow asserts these
        **{f"audit_{k}": v for k, v in integrity.stats().items()
           if k in ("conservation_checks", "fingerprint_checks",
                    "fingerprint_votes", "violations",
                    "corruptions_injected")},
    }), flush=True)
    return 0


def run_multislice(args) -> int:
    """The ``--multislice`` acceptance flow (pinned, not drawn) — see
    the module docstring.  Simulated two-tier grid: world 4, 2 slices
    of 2 (``CYLON_TPU_SLICES=2``); R = ranks per slice = 2."""
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_topo_")
    failures: list = []
    r_per_slice = 2

    def spawn(tag, faults, slices=2, armed=True, extra=None):
        workdir = os.path.join(args.workdir, tag)
        env_extra = {"CYLON_TPU_TOPO_SHUFFLE": "1" if armed else "0"}
        if slices:
            env_extra["CYLON_TPU_SLICES"] = str(slices)
        env_extra.update(extra or {})
        return _spawn(args, workdir, faults, resume=False,
                      extra_env=env_extra, multislice=True, world=4)

    # flat-routed baseline on the two-tier grid: the bit-equality
    # oracle AND the cross-slice traffic yardstick
    p, base = spawn("base", "", armed=False)
    if p.returncode != 0 or not base or not base.get("sha"):
        print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
        print("chaos-soak: multislice baseline failed", file=sys.stderr)
        return 1
    print(f"# topo flat baseline sha={base['sha'][:16]} "
          f"dcn_messages={base['dcn_messages']}", flush=True)
    if base.get("topo_plans_voted"):
        failures.append(f"flat-routed run voted a topology plan: {base}")

    # armed happy path: voted plan, bit-equal, DCN messages ~1/R
    p, info = spawn("hier", "")
    plan0 = (info or {}).get("topo_plan_hash")
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"hierarchical run diverged (rc={p.returncode}): "
                        f"{info}\n{(p.stdout + p.stderr)[-2000:]}")
    elif not plan0 or not info.get("topo_plans_voted"):
        failures.append(f"hierarchical run never voted a plan: {info}")
    elif info.get("dcn_rows") != base.get("dcn_rows"):
        failures.append(
            f"cross-slice PAYLOAD changed (must be route-invariant): "
            f"{info.get('dcn_rows')} != {base.get('dcn_rows')}")
    elif info["dcn_messages"] * r_per_slice > base["dcn_messages"] * 1.2:
        failures.append(
            f"DCN message count not reduced ~1/R: hier="
            f"{info['dcn_messages']} flat={base['dcn_messages']} R=2")
    else:
        print(f"# topo hier -> ok (plan={plan0} dcn_messages="
              f"{info['dcn_messages']} vs flat {base['dcn_messages']})",
              flush=True)

    # capacity fault INSIDE the hierarchical exchange (the receive
    # guard probes before phase B dispatch): the ladder's retry must
    # re-adopt the IDENTICAL voted topology plan before going bit-equal
    p, info = spawn("capacity", "shuffle.recv_guard::1=capacity",
                    extra={"CYLON_TPU_EXCHANGE_GUARD_CPU": "1"})
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"capacity-fault leg diverged (rc={p.returncode}):"
                        f" {info}\n{(p.stdout + p.stderr)[-2000:]}")
    elif info.get("topo_plan_hash") != plan0:
        failures.append(f"capacity-fault recovery adopted a DIFFERENT "
                        f"topology plan: {info.get('topo_plan_hash')} != "
                        f"{plan0}")
    elif not info.get("events") or info["events"] > MAX_RECOVERY_EVENTS:
        failures.append(f"capacity-fault leg events out of range: {info}")
    else:
        print("# topo capacity fault -> ok (same voted plan, bit-equal)",
              flush=True)

    # whole-slice loss → elastic resume: the checkpointed two-stage
    # elastic workload runs at world=4/slices=2, a SIGKILL mid-stage-2
    # takes the process (and with it both slices) down, and the resume
    # runs on the SURVIVING world=2 single slice — the PR 9 re-shard
    # must fast-forward stage 1 bit-equal while stage 2 recomputes
    k1 = args.chunks + 1
    two_tier = {"CYLON_TPU_SLICES": "2"}
    one_tier = {"CYLON_TPU_SLICES": "1"}
    p, ebase = _spawn(args, os.path.join(args.workdir, "ebase"), "",
                      resume=False, elastic=True, world=4,
                      extra_env=two_tier)
    if p.returncode != 0 or not ebase or not ebase.get("sha"):
        failures.append(f"elastic two-tier baseline failed "
                        f"(rc={p.returncode}): "
                        f"{(p.stdout + p.stderr)[-2000:]}")
    else:
        dK = os.path.join(args.workdir, "slicekill")
        p1, _ = _spawn(args, dK, f"ckpt.write::{k1}=kill", resume=False,
                       elastic=True, world=4, extra_env=two_tier)
        if p1.returncode != -9:
            failures.append(f"whole-slice kill did not crash "
                            f"(rc={p1.returncode})")
        else:
            p2, info2 = _spawn(args, dK, "", resume=True, elastic=True,
                               world=2, extra_env=one_tier)
            if p2.returncode != 0 or not info2 \
                    or info2.get("sha") != ebase["sha"]:
                failures.append(
                    f"slice-loss resume diverged (rc={p2.returncode}): "
                    f"{info2}\n{(p2.stdout + p2.stderr)[-2000:]}")
            elif not info2.get("resume_resharded_pieces") \
                    or not info2.get("resume_world_mismatch"):
                failures.append(f"slice loss did not re-shard: {info2}")
            else:
                print(f"# topo slice-kill + elastic resume -> ok "
                      f"(resharded={info2['resume_resharded_pieces']} "
                      f"ffwd={info2['resume_fast_forwarded_pieces']})",
                      flush=True)

    # unarmed single-slice contract: with NO slice declaration the
    # ARMED route must vote nothing and run the byte-identical flat
    # engine — same sha, same exchange rows, same exchange count (zero
    # extra collectives, zero host syncs)
    p, flat0 = spawn("single_unarmed", "", slices=0, armed=False)
    p2, flat1 = spawn("single_armed", "", slices=0, armed=True)
    if p.returncode != 0 or p2.returncode != 0 or not flat0 or not flat1:
        failures.append(f"single-slice legs failed (rc={p.returncode}/"
                        f"{p2.returncode}): {flat0} {flat1}")
    elif flat1.get("sha") != flat0.get("sha"):
        failures.append(f"armed-on-single-slice diverged: {flat1}")
    elif flat1.get("topo_plan_hash") is not None \
            or flat1.get("topo_plans_voted"):
        failures.append(f"armed-on-single-slice voted a plan: {flat1}")
    elif (flat1.get("exchange_rows") != flat0.get("exchange_rows")
          or flat1.get("exchange_count") != flat0.get("exchange_count")):
        failures.append(
            f"armed-on-single-slice moved different exchange traffic: "
            f"{flat1} != {flat0}")
    else:
        print("# topo unarmed single-slice -> ok (no vote, identical "
              "exchange counters)", flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"multislice": True, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def run_audit(args) -> int:
    """The ``--audit`` acceptance flow (pinned, not drawn) — see the
    module docstring.  Drives the integrity audit tier
    (cylon_tpu/exec/integrity) end to end: silent exchange corruption
    injected via ``exchange.corrupt`` must be CAUGHT by the armed
    fingerprint (typed, one recompute, bit-equal) on the flat, the
    skew-split and the two-tier topology routes; persistent corruption
    must end in a typed abort; and the unarmed path must do zero
    fingerprint work."""
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_audit_")
    failures: list = []

    def spawn(tag, faults, extra=None, skew=False):
        workdir = os.path.join(args.workdir, tag)
        return _spawn(args, workdir, faults, resume=False,
                      extra_env=extra or {}, world=4,
                      skew=skew, multislice=not skew)

    def integrity_event(info):
        # the ladder's recompute of a caught corruption records an
        # event with kind="integrity"
        return any(ev.get("kind") == "integrity"
                   for ev in (info or {}).get("event_list") or [])

    # unarmed baseline: the bit-equality oracle AND the zero-overhead
    # contract — no fingerprint checks, no fingerprint votes (the
    # conservation laws still run; they are free host math)
    p, base = spawn("base", "")
    if p.returncode != 0 or not base or not base.get("sha"):
        print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
        print("chaos-soak: audit baseline failed", file=sys.stderr)
        return 1
    print(f"# audit unarmed baseline sha={base['sha'][:16]} "
          f"conservation_checks={base['audit_conservation_checks']}",
          flush=True)
    if base.get("audit_fingerprint_checks") \
            or base.get("audit_fingerprint_votes"):
        failures.append(f"UNARMED run did fingerprint work: {base}")
    if not base.get("audit_conservation_checks"):
        failures.append(f"conservation laws not always-on: {base}")

    # armed clean run: bit-equal, fingerprints checked, zero
    # violations, and exactly the unarmed run's exchange traffic (the
    # audit's all_gather is not an exchange — armed adds no exchange
    # collectives, and the checks are stage-boundary, not per-row)
    p, info = spawn("armed", "", extra={"CYLON_TPU_AUDIT": "1"})
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"armed clean run diverged (rc={p.returncode}): "
                        f"{info}\n{(p.stdout + p.stderr)[-2000:]}")
    elif not info.get("audit_fingerprint_checks") \
            or not info.get("audit_fingerprint_votes"):
        failures.append(f"armed run never fingerprinted: {info}")
    elif info.get("audit_violations"):
        failures.append(f"armed clean run reported violations: {info}")
    elif (info.get("exchange_rows") != base.get("exchange_rows")
          or info.get("exchange_count") != base.get("exchange_count")):
        failures.append(
            f"arming the audit changed exchange traffic: {info} != {base}")
    else:
        print(f"# audit armed clean -> ok (fp_checks="
              f"{info['audit_fingerprint_checks']})", flush=True)

    # one-shot silent corruption, armed: the flipped byte must surface
    # as a typed DataIntegrityError the ladder converts into ONE
    # recompute — bit-equal, with the integrity event on the record
    p, info = spawn("corrupt", "exchange.corrupt=corrupt",
                    extra={"CYLON_TPU_AUDIT": "1"})
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"caught-corruption leg diverged "
                        f"(rc={p.returncode}): {info}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    elif not info.get("audit_violations") \
            or not info.get("audit_corruptions_injected"):
        failures.append(f"corruption not injected/detected: {info}")
    elif not integrity_event(info):
        failures.append(f"no integrity recovery event recorded: {info}")
    elif info.get("events", 0) > MAX_RECOVERY_EVENTS:
        failures.append(f"corruption recovery events out of range: {info}")
    else:
        print("# audit one-shot corruption -> ok (caught, one recompute, "
              "bit-equal)", flush=True)

    # PERSISTENT corruption: every recompute re-flips, so the ladder
    # must exhaust its single rung and abort TYPED — a wrong answer or
    # a clean exit here is the silent-corruption disaster this tier
    # exists to prevent
    p, info = spawn("persist", "exchange.corrupt::*=corrupt",
                    extra={"CYLON_TPU_AUDIT": "1"})
    if p.returncode == 0:
        failures.append(f"persistent corruption returned a result: {info}")
    elif "DataIntegrityError" not in (p.stderr or ""):
        failures.append(f"persistent corruption died untyped "
                        f"(rc={p.returncode}): "
                        f"{(p.stdout + p.stderr)[-2000:]}")
    else:
        print("# audit persistent corruption -> typed abort (ok)",
              flush=True)

    # corruption under the SKEW-SPLIT route: the fingerprint must catch
    # it at the post-exchange stage inside the split join, and the
    # recompute must land on the same voted plan, bit-equal
    skew_env = {"CYLON_TPU_SKEW_SPLIT": "1", "CYLON_TPU_AUDIT": "1"}
    p, sbase = spawn("skew_base", "", extra=skew_env, skew=True)
    if p.returncode != 0 or not sbase or not sbase.get("sha") \
            or not sbase.get("skew_split_joins"):
        failures.append(f"audit skew baseline failed (rc={p.returncode}, "
                        f"did it split?): {sbase}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    else:
        p, info = spawn("skew_corrupt", "exchange.corrupt=corrupt",
                        extra=skew_env, skew=True)
        if p.returncode != 0 or not info \
                or info.get("sha") != sbase["sha"]:
            failures.append(f"skew-route corruption leg diverged "
                            f"(rc={p.returncode}): {info}\n"
                            f"{(p.stdout + p.stderr)[-2000:]}")
        elif not info.get("audit_violations") or not integrity_event(info):
            failures.append(f"skew-route corruption not caught: {info}")
        elif info.get("plan_hash") != sbase.get("plan_hash"):
            failures.append(f"skew-route recompute changed the voted "
                            f"plan: {info.get('plan_hash')} != "
                            f"{sbase.get('plan_hash')}")
        else:
            print("# audit corruption under skew-split -> ok (caught, "
                  "same plan, bit-equal)", flush=True)

    # corruption under the TWO-TIER topology route: the hierarchical
    # exchange's delivered bytes are fingerprint-verified exactly like
    # the flat route's — caught post-exchange, bit-equal to the flat
    # oracle (route bit-equality is the topo tier's own invariant)
    topo_env = {"CYLON_TPU_SLICES": "2", "CYLON_TPU_TOPO_SHUFFLE": "1",
                "CYLON_TPU_AUDIT": "1"}
    p, info = spawn("topo_corrupt", "exchange.corrupt=corrupt",
                    extra=topo_env)
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"topo-route corruption leg diverged "
                        f"(rc={p.returncode}): {info}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    elif not info.get("topo_plans_voted"):
        failures.append(f"topo-route leg never voted a plan: {info}")
    elif not info.get("audit_violations") or not integrity_event(info):
        failures.append(f"topo-route corruption not caught: {info}")
    else:
        print("# audit corruption under two-tier route -> ok (caught, "
              "bit-equal)", flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"audit": True, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def run_skew(args) -> int:
    """The ``--skew`` acceptance flow (pinned, not drawn) — see the
    module docstring.  Every schedule must end bit-equal to the UNSPLIT
    baseline, and every recovered schedule must land on the IDENTICAL
    voted plan hash."""
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_skew_")
    failures: list = []

    def spawn(tag, faults, armed=True, frac=0.8, extra=None):
        workdir = os.path.join(args.workdir, tag)
        env_extra = {"CYLON_TPU_SKEW_SPLIT": "1" if armed else "0"}
        env_extra.update(extra or {})
        return _spawn(args, workdir, faults, resume=False,
                      extra_env=env_extra, skew=True, skew_frac=frac)

    # unsplit baseline: the bit-equality oracle
    p, base = spawn("base", "", armed=False)
    if p.returncode != 0 or not base or not base.get("sha"):
        print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
        print("chaos-soak: skew baseline failed", file=sys.stderr)
        return 1
    print(f"# skew unsplit baseline sha={base['sha'][:16]}", flush=True)

    # armed happy path: a non-empty voted plan, bit-equal
    p, info = spawn("split", "")
    plan0 = (info or {}).get("plan_hash")
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"armed split diverged (rc={p.returncode}): {info}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    elif not plan0 or not info.get("skew_split_joins"):
        failures.append(f"armed split never voted a plan: {info}")
    else:
        print(f"# skew split -> ok (plan={plan0})", flush=True)

    # exchange capacity fault INSIDE the split (the build-side hash
    # shuffle's receive guard): the ladder retries the join, which must
    # re-detect and re-vote the IDENTICAL plan before going bit-equal
    p, info = spawn("capacity", "shuffle.recv_guard::1=capacity",
                    extra={"CYLON_TPU_EXCHANGE_GUARD_CPU": "1"})
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"capacity-fault leg diverged (rc={p.returncode}): "
                        f"{info}\n{(p.stdout + p.stderr)[-2000:]}")
    elif info.get("plan_hash") != plan0:
        failures.append(f"capacity-fault recovery re-voted a DIFFERENT "
                        f"plan: {info.get('plan_hash')} != {plan0}")
    elif not info.get("events") or info["events"] > MAX_RECOVERY_EVENTS:
        failures.append(f"capacity-fault leg events out of range: {info}")
    else:
        print("# skew capacity fault -> ok (same plan, bit-equal)",
              flush=True)

    # spill fault under an HBM budget cap: same contract
    p, info = spawn("spill", "spill.evict::1=predicted",
                    extra={"CYLON_TPU_HBM_BUDGET": "4096"})
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"spill-fault leg diverged (rc={p.returncode}): "
                        f"{info}\n{(p.stdout + p.stderr)[-2000:]}")
    elif info.get("plan_hash") != plan0:
        failures.append(f"spill-fault recovery re-voted a DIFFERENT "
                        f"plan: {info.get('plan_hash')} != {plan0}")
    else:
        print("# skew spill fault -> ok (same plan, bit-equal)", flush=True)

    # SIGKILL mid-workload (at the groupby site, after the voted split
    # exchange ran), then a fresh rerun: same plan, bit-equal
    p, _ = spawn("kill", "groupby.device_oom::1=kill")
    if p.returncode != -9:
        failures.append(f"kill leg did not crash (rc={p.returncode})")
    else:
        p2, info2 = spawn("kill_rerun", "")
        if p2.returncode != 0 or not info2 \
                or info2.get("sha") != base["sha"]:
            failures.append(f"rerun after kill diverged "
                            f"(rc={p2.returncode}): {info2}\n"
                            f"{(p2.stdout + p2.stderr)[-2000:]}")
        elif info2.get("plan_hash") != plan0:
            failures.append(f"rerun after kill voted a DIFFERENT plan: "
                            f"{info2.get('plan_hash')} != {plan0}")
        else:
            print("# skew kill + rerun -> ok (same plan, bit-equal)",
                  flush=True)

    # unarmed-at-skew-0 contract: the ARMED run at skew 0 votes nothing,
    # splits nothing, and moves exactly the unsplit run's exchange rows
    p, flat0 = spawn("flat_unsplit", "", armed=False, frac=0.0)
    p2, flat1 = spawn("flat_armed", "", armed=True, frac=0.0)
    if p.returncode != 0 or p2.returncode != 0 or not flat0 or not flat1:
        failures.append(f"flat legs failed (rc={p.returncode}/"
                        f"{p2.returncode}): {flat0} {flat1}")
    elif flat1.get("sha") != flat0.get("sha"):
        failures.append(f"armed-at-skew-0 diverged: {flat1}")
    elif flat1.get("plan_hash") is not None \
            or flat1.get("skew_split_joins"):
        failures.append(f"armed-at-skew-0 voted a plan: {flat1}")
    elif flat1.get("exchange_rows") != flat0.get("exchange_rows"):
        failures.append(
            f"armed-at-skew-0 moved extra exchange rows: "
            f"{flat1.get('exchange_rows')} != {flat0.get('exchange_rows')}")
    else:
        print("# skew unarmed-at-0 -> ok (no vote, no extra exchange "
              "rows)", flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"skew": True, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def run_oocore(args) -> int:
    """The ``--oocore`` acceptance flow (pinned, not drawn): the disk
    tier's end-to-end contract.  Budget caps sized below the workload's
    working set force evict→demote; every schedule must end bit-equal
    to the uncapped baseline — degraded, resumed or recomputed, never
    wrong — and the unarmed leg must write NOTHING."""
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_oocore_")
    failures: list = []
    caps = {"CYLON_TPU_HBM_BUDGET": "4096",
            "CYLON_TPU_HOST_BUDGET": "4096"}

    def spawn(tag, faults, resume=False, capped=True, spill_sub="spill"):
        workdir = os.path.join(args.workdir, tag)
        extra = dict(caps) if capped else {}
        extra["CYLON_TPU_SPILL_DIR"] = os.path.join(workdir, spill_sub)
        return _spawn(args, workdir, faults, resume=resume,
                      extra_env=extra), os.path.join(workdir, spill_sub)

    # uncapped, un-injected baseline: the bit-equality oracle
    (p, base), _sd = spawn("base", "", capped=False)
    if p.returncode != 0 or not base or not base.get("sha"):
        print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
        print("chaos-soak: oocore baseline failed", file=sys.stderr)
        return 1
    print(f"# oocore baseline sha={base['sha'][:16]}", flush=True)
    if base.get("disk_events"):
        failures.append(f"UNARMED baseline wrote to disk: {base}")
    if os.path.isdir(_sd):
        failures.append(f"unarmed run created the spill dir {_sd}")

    # capped happy path: bit-equal THROUGH the disk tier, traffic counted
    (p, info), _sd = spawn("capped", "")
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"capped run diverged (rc={p.returncode}): {info}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    elif not info.get("disk_events") or not info.get("bytes_to_disk"):
        failures.append(f"capped run never touched the disk tier: {info}")
    else:
        print(f"# oocore capped -> ok (disk_events={info['disk_events']} "
              f"bytes_to_disk={info['bytes_to_disk']})", flush=True)

    # ENOSPC mid-demote: typed degrade to in-memory — no crash, bit-equal
    (p, info), _sd = spawn("enospc", "disk.write::1=enospc")
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"enospc mid-demote crashed or diverged "
                        f"(rc={p.returncode}): {info}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    elif not info.get("disk_write_degrades"):
        failures.append(f"enospc degrade not counted: {info}")
    else:
        print("# oocore enospc -> ok (typed degrade, bit-equal)",
              flush=True)

    # corrupt-on-promote: the ladder recomputes the owner — bit-equal
    (p, info), _sd = spawn("corrupt", "disk.read::1=corrupt")
    if p.returncode != 0 or not info or info.get("sha") != base["sha"]:
        failures.append(f"corrupt-on-promote crashed or produced a WRONG "
                        f"answer (rc={p.returncode}): {info}\n"
                        f"{(p.stdout + p.stderr)[-2000:]}")
    elif not info.get("disk_corrupt_degrades"):
        failures.append(f"corrupt degrade not counted: {info}")
    elif info.get("events", 0) > MAX_RECOVERY_EVENTS:
        failures.append(f"unbounded retries after corruption: {info}")
    else:
        print("# oocore corrupt-on-promote -> ok (recompute, bit-equal)",
              flush=True)

    # SIGKILL mid-demote, then resume: bit-equal after the crash
    (p, _), _sd = spawn("kill", "disk.write::1=kill")
    if p.returncode != -9:
        failures.append(f"kill mid-demote did not crash "
                        f"(rc={p.returncode})")
    else:
        workdir = os.path.join(args.workdir, "kill")
        extra = dict(caps)
        extra["CYLON_TPU_SPILL_DIR"] = os.path.join(workdir, "spill2")
        p2, info2 = _spawn(args, workdir, "", resume=True, extra_env=extra)
        if p2.returncode != 0 or not info2 \
                or info2.get("sha") != base["sha"]:
            failures.append(f"resume after kill mid-demote diverged "
                            f"(rc={p2.returncode}): {info2}\n"
                            f"{(p2.stdout + p2.stderr)[-2000:]}")
        else:
            print(f"# oocore kill mid-demote + resume -> ok (ffwd="
                  f"{info2.get('resume_fast_forwarded_pieces')})",
                  flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"oocore": True, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def _worker_elastic(args, env) -> int:
    """The elastic-resume acceptance workload: TWO chained pipelined
    stages — a sinkless join (stage 1) feeding a join+GroupBySink
    (stage 2) — so a kill landing mid-stage-2 leaves a COMPLETE stage 1
    behind, which a resume at a different world must re-shard and
    fast-forward while stage 2 recomputes.  Integer "money" columns and
    a unique-key final groupby keep the sorted result sha world-
    invariant, which is what makes one uninterrupted world=2 baseline
    the oracle for every resume world.  A preemption-grace drain
    (SIGTERM via the ``term`` injector kind, grace budget in the env)
    exits via typed ResumableAbort → RESUMABLE_EXIT instead of a signal
    death."""
    import numpy as np

    import cylon_tpu as ct
    from cylon_tpu.exec import GroupBySink, checkpoint, pipelined_join, \
        recovery
    from cylon_tpu.status import ResumableAbort

    rng = np.random.default_rng(20260804)
    rows = args.rows
    n_ord = max(rows // 4, 64)
    n_cust = 16
    orders = ct.Table.from_pydict(
        {"o_orderkey": np.arange(n_ord, dtype=np.int64),
         "o_custkey": rng.integers(0, n_cust, n_ord).astype(np.int64)},
        env)
    lineitem = ct.Table.from_pydict(
        {"l_orderkey": rng.integers(0, n_ord, rows).astype(np.int64),
         "l_quantity": rng.integers(1, 51, rows).astype(np.int64),
         "l_extendedprice": rng.integers(900_00, 10_500_00,
                                         rows).astype(np.int64)},
        env)
    customers = ct.Table.from_pydict(
        {"c_custkey": np.arange(n_cust, dtype=np.int64),
         "c_nationkey": rng.integers(0, 5, n_cust).astype(np.int64)},
        env)
    try:
        # stage 1 (sinkless): its piece outputs are the checkpointed
        # state a world change must re-shard in global row order
        jt = pipelined_join(lineitem, orders, "l_orderkey", "o_orderkey",
                            how="inner", n_chunks=args.chunks)
        # stage 2 (sink): mergeable partial aggregates
        sink = GroupBySink("o_custkey", [("l_quantity", "sum"),
                                         ("l_extendedprice", "sum")])
        pipelined_join(jt, customers, "o_custkey", "c_custkey",
                       how="inner", n_chunks=args.chunks, sink=sink)
        out = sink.finalize()
    except ResumableAbort as e:
        print(json.dumps({"resumable": True, "token": e.token,
                          "events": len(recovery.recovery_events()),
                          **checkpoint.stats()}), flush=True)
        return RESUMABLE_EXIT
    df = out.to_pandas().sort_values("o_custkey").reset_index(drop=True)
    print(json.dumps({
        "ok": True, "sha": _result_sha(df), "rows": int(len(df)),
        "world": int(env.world_size),
        "events": len(recovery.recovery_events()),
        **checkpoint.stats(),
    }), flush=True)
    return 0


def run_elastic(args) -> int:
    """The ``--elastic`` acceptance flow (pinned, not drawn) — see the
    module docstring.  ``k1`` is stage 2's first checkpoint write (the
    stage-1 pieces occupy writes 1..chunks), so a fault there leaves
    stage 1 complete and stage 2 untouched or partial."""
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_elastic_")
    failures: list = []
    k1 = args.chunks + 1

    p, base = _spawn(args, os.path.join(args.workdir, "base"), "",
                     resume=False, elastic=True, world=2)
    if p.returncode != 0 or not base or not base.get("sha"):
        print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
        print("chaos-soak: elastic baseline failed", file=sys.stderr)
        return 1
    print(f"# elastic baseline sha={base['sha'][:16]} world=2", flush=True)

    def resume_leg(tag, workdir, world, resume_faults="", extra=None,
                   want_reshard=True):
        p2, info = _spawn(args, workdir, resume_faults, resume=True,
                          elastic=True, world=world, extra_env=extra)
        if p2.returncode != 0 or not info:
            failures.append(f"{tag}: resume at world={world} failed "
                            f"rc={p2.returncode}: "
                            f"{(p2.stdout + p2.stderr)[-2000:]}")
            return None
        if info.get("sha") != base["sha"]:
            failures.append(f"{tag}: resume at world={world} diverged "
                            f"from the world=2 baseline: {info}")
        elif want_reshard and not info.get("resume_resharded_pieces"):
            failures.append(f"{tag}: world change did not re-shard "
                            f"(recomputed everything): {info}")
        elif want_reshard and not info.get("resume_world_mismatch"):
            failures.append(f"{tag}: world mismatch not counted: {info}")
        elif not want_reshard and info.get("resume_resharded_pieces"):
            failures.append(f"{tag}: same-world resume resharded: {info}")
        elif not info.get("resume_fast_forwarded_pieces"):
            failures.append(f"{tag}: resume recomputed every committed "
                            f"piece: {info}")
        else:
            print(f"# elastic {tag} -> ok (world={world} "
                  f"ffwd={info['resume_fast_forwarded_pieces']} "
                  f"resharded={info['resume_resharded_pieces']} "
                  f"mismatch={info['resume_world_mismatch']})", flush=True)
        return info

    def kill_leg(tag, workdir, faults, extra=None):
        p1, _ = _spawn(args, workdir, faults, resume=False, elastic=True,
                       world=2, extra_env=extra)
        if p1.returncode != -9:
            failures.append(f"{tag}: kill schedule did not crash "
                            f"(rc={p1.returncode})")
            return False
        if not os.path.exists(os.path.join(workdir,
                                           "TRACE_POSTMORTEM.json")):
            failures.append(f"{tag}: killed child left no "
                            "TRACE_POSTMORTEM.json breadcrumb")
        return True

    # A: ckpt at world=2, SIGKILL mid-stage-2 → resume at world=1:
    # stage 1 re-shards 2→1 and fast-forwards, stage 2 recomputes
    dA = os.path.join(args.workdir, "killA")
    if kill_leg("A", dA, f"ckpt.write::{k1}=kill"):
        resume_leg("A (2→1 reshard)", dA, 1)

    # B: same kill → plain resume at world=2 (fast-forward, no reshard)
    dB = os.path.join(args.workdir, "killB")
    if kill_leg("B", dB, f"ckpt.write::{k1}=kill"):
        resume_leg("B (2→2 plain)", dB, 2, want_reshard=False)

    # C: kill at world=2, resume at world=1 and kill THAT mid-stage-2
    # (stage 1 is now rewritten in the world=1 layout), then resume at
    # world=2-after-reshard: the gen-bumped world=1 manifests must
    # re-shard back up while the stale world=2 rank dirs read as stale
    dC = os.path.join(args.workdir, "killC")
    if kill_leg("C", dC, f"ckpt.write::{k1}=kill"):
        p2, _ = _spawn(args, dC, f"ckpt.write::{args.chunks + 2}=kill",
                       resume=True, elastic=True, world=1)
        if p2.returncode != -9:
            failures.append(f"C: second kill (world=1 resume) did not "
                            f"crash (rc={p2.returncode})")
        else:
            resume_leg("C (1→2 after-reshard)", dC, 2)

    # D: corruption injected DURING the re-shard read: the stage must
    # degrade to recompute — bit-equal, nothing resharded
    dD = os.path.join(args.workdir, "killD")
    if kill_leg("D", dD, f"ckpt.write::{k1}=kill"):
        p2, info = _spawn(args, dD, "ckpt.reshard::1=corrupt",
                          resume=True, elastic=True, world=1)
        if p2.returncode != 0 or not info:
            failures.append(f"D: corrupt-reshard resume failed "
                            f"rc={p2.returncode}")
        elif info.get("sha") != base["sha"]:
            failures.append(f"D: corrupt reshard produced a WRONG "
                            f"answer: {info}")
        elif info.get("resume_resharded_pieces"):
            failures.append(f"D: corrupt reshard still adopted pieces: "
                            f"{info}")
        else:
            print("# elastic D (corrupt reshard → recompute) -> ok",
                  flush=True)

    # F: SIGKILL DURING the re-shard itself (mid-adoption crash): the
    # checkpoint state is untouched (adoption commits nothing until the
    # rewrite), so resuming AGAIN must re-shard cleanly
    dF = os.path.join(args.workdir, "killF")
    if kill_leg("F", dF, f"ckpt.write::{k1}=kill"):
        p2, _ = _spawn(args, dF, "ckpt.reshard::1=kill", resume=True,
                       elastic=True, world=1)
        if p2.returncode != -9:
            failures.append(f"F: kill mid-reshard did not crash "
                            f"(rc={p2.returncode})")
        else:
            resume_leg("F (reshard after mid-reshard kill)", dF, 1)

    # E: preemption grace — SIGTERM (term kind) with the grace budget
    # armed must exit via typed ResumableAbort (RESUMABLE_EXIT), not a
    # signal death, with the current stage committed; the world=1
    # resume then rides the committed prefix
    dE = os.path.join(args.workdir, "termE")
    grace = {"CYLON_TPU_PREEMPT_GRACE_S": "30"}
    p1, info1 = _spawn(args, dE, f"ckpt.write::{k1}=term", resume=False,
                       elastic=True, world=2, extra_env=grace)
    if p1.returncode != RESUMABLE_EXIT:
        failures.append(f"E: SIGTERM with grace armed did not drain via "
                        f"ResumableAbort (rc={p1.returncode}): "
                        f"{(p1.stdout + p1.stderr)[-1500:]}")
    elif not info1 or not info1.get("checkpoint_events"):
        failures.append(f"E: grace drain committed nothing: {info1}")
    elif not os.path.exists(os.path.join(dE, "TRACE_POSTMORTEM.json")):
        failures.append("E: grace drain left no TRACE_POSTMORTEM.json "
                        "breadcrumb beside the manifests")
    else:
        print(f"# elastic E drain -> ok (committed="
              f"{info1['checkpoint_events']})", flush=True)
        p2, info2 = _spawn(args, dE, "", resume=True, elastic=True,
                           world=1, extra_env=grace)
        if p2.returncode != 0 or not info2 \
                or info2.get("sha") != base["sha"]:
            failures.append(f"E: resume after grace drain diverged "
                            f"(rc={p2.returncode}): {info2}")
        else:
            print(f"# elastic E resume -> ok (ffwd="
                  f"{info2['resume_fast_forwarded_pieces']})", flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"elastic": True, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def _worker_concurrent(args, env, make_workload) -> int:
    """K concurrent serving sessions over one mesh (exec/scheduler), each
    a differently-seeded pipelined join+sink tenant.  ``--only i``
    restricts to one tenant — the SOLO leg whose sha is the concurrent
    run's bit-equality oracle.  Faults target tenants with the
    ``@session`` grammar (``ckpt.write::2=kill@t0``); a kill takes the
    whole process down and the parent reruns with CYLON_TPU_RESUME=1 —
    the per-session checkpoint stage namespace then fast-forwards the
    killed tenant while every tenant's answer stays bit-equal to its
    solo run."""
    from cylon_tpu.exec import checkpoint, recovery
    from cylon_tpu.exec.scheduler import QueryScheduler
    from cylon_tpu.status import ResumableAbort

    def make_fn(i: int):
        attempt = make_workload(20260803 + 7919 * i, args.rows)

        def fn():
            out = recovery.run_with_recovery(
                lambda: attempt(args.chunks), True, attempt,
                f"soak.t{i}", env=env)
            return out.to_pandas().sort_values("l_orderkey") \
                .reset_index(drop=True)
        return fn

    sched = QueryScheduler(env, policy="fair")
    idxs = [i for i in range(args.concurrent)
            if args.only is None or i == args.only]
    for i in idxs:
        sched.submit(f"t{i}", make_fn(i))
    sessions = sched.run()
    shas, events = {}, {}
    for s in sessions:
        if isinstance(s.error, ResumableAbort):
            print(json.dumps({"resumable": True, "token": s.error.token,
                              "session": s.name}), flush=True)
            return RESUMABLE_EXIT
        if s.error is not None:
            raise s.error
        shas[s.name] = _result_sha(s.result)
        events[s.name] = s.recovery_events()
    print(json.dumps({
        "ok": True, "shas": shas, "session_events": events,
        "events": len(recovery.recovery_events()),
        **checkpoint.stats(),
    }), flush=True)
    return 0


def _worker_fleet(args, env, make_workload) -> int:
    """One ``--fleet`` worker process (docs/serving.md, "Preemption &
    elastic serving").  The case rides ``CYLON_TPU_FLEET_CASE``:

    * ``preempt`` — tA (long, low priority) submits tB (short, high
      priority) from inside its own first run; under
      ``policy=priority`` + ``max_concurrency=1`` the scheduler
      preempt-drains tA at its next checkpoint boundary, runs tB, then
      requeues tA which resumes in-process (fast-forward > 0).  Solo
      oracles are computed in-process with checkpointing popped, so
      ``bit_equal`` is decided right here.
    * ``resize`` — three tenants under a ResizeController
      (``CYLON_TPU_FLEET_TARGET`` armed): sustained queue depth
      engages the all-or-nothing fleet drain; the worker exits
      RESUMABLE_EXIT with zero failed_typed tenants, and the SAME case
      relaunched without the target (at the new ``--world``) resumes
      every tenant to a bit-equal finish.
    * ``deadline`` — ``CYLON_TPU_ADMISSION_TIMEOUT_S`` armed, fifo,
      one slot: the queued tenant must fail typed
      (AdmissionTimeoutError), never hang.
    """
    from cylon_tpu.exec import checkpoint
    from cylon_tpu.exec.fleet import ResizeController
    from cylon_tpu.exec.scheduler import QueryScheduler
    from cylon_tpu.status import AdmissionTimeoutError, ResumableAbort

    case = os.environ.get("CYLON_TPU_FLEET_CASE", "preempt")

    def df_of(seed, rows, nc):
        out = make_workload(seed, rows)(nc)
        return out.to_pandas().sort_values("l_orderkey") \
            .reset_index(drop=True)

    # tenant specs: (name, seed, rows, chunks) — tA long (many drain
    # boundaries), tB short (the high-priority arrival)
    specs = {
        "tA": (20260803, args.rows, args.chunks + 2),
        "tB": (20260810, max(args.rows // 3, 256), 2),
        "tC": (20260817, args.rows, args.chunks),
    }

    # solo oracles, computed in-process with durable checkpointing (and
    # any resume request) popped so they neither write stages nor
    # fast-forward from the scheduler runs' stages
    saved = {k: os.environ.pop(k, None)
             for k in ("CYLON_TPU_CKPT_DIR", "CYLON_TPU_RESUME")}
    solo = {name: _result_sha(df_of(*spec))
            for name, spec in specs.items()}
    for k, v in saved.items():
        if v is not None:
            os.environ[k] = v

    def finish(sched, extra) -> int:
        shas, outcomes = {}, sched.stats()["outcomes"]
        for s in sched.sessions:
            if isinstance(s.error, ResumableAbort):
                print(json.dumps({
                    "resumable": True, "token": s.error.token,
                    "session": s.name, "outcomes": outcomes,
                    "failed_typed": outcomes.get("failed_typed", 0),
                    "resize_target": sched.resize_target, **extra}),
                    flush=True)
                return RESUMABLE_EXIT
            if s.error is not None:
                raise s.error
            shas[s.name] = _result_sha(s.result)
        print(json.dumps({
            "ok": True, "shas": shas,
            "bit_equal": all(shas[n] == solo[n] for n in shas),
            "outcomes": outcomes,
            "failed_typed": outcomes.get("failed_typed", 0),
            "preemptions": sched.stats()["preemptions"],
            "requeues": sched.stats()["requeues"],
            "resize_target": sched.resize_target,
            **checkpoint.stats(), **extra}), flush=True)
        return 0

    if case == "preempt":
        sched = QueryScheduler(env, policy="priority", max_concurrency=1)
        runs = {"n": 0}
        fnA = lambda: df_of(*specs["tA"])  # noqa: E731

        def tA():
            runs["n"] += 1
            if runs["n"] == 1:
                # the high-priority arrival lands MID-TRAFFIC: tA's own
                # first slice submits it
                sched.submit("tB", lambda: df_of(*specs["tB"]),
                             priority=5)
            return fnA()

        sched.submit("tA", tA)
        sched.submit("tC", lambda: df_of(*specs["tC"]))
        sched.run()
        return finish(sched, {"case": case})

    if case == "resize":
        target = int(os.environ.get("CYLON_TPU_FLEET_TARGET", "0") or 0)
        fleet = (ResizeController(env, target_world=target,
                                  queue_depth_high=2)
                 if target > 0 else None)
        sched = QueryScheduler(env, policy="fair", max_concurrency=1,
                               fleet=fleet)
        for name in ("tA", "tB", "tC"):
            sched.submit(name, lambda n=name: df_of(*specs[n]))
        sched.run()
        return finish(sched, {"case": case, "world": args.world})

    if case == "deadline":
        sched = QueryScheduler(env, policy="fifo", max_concurrency=1)
        sched.submit("tA", lambda: df_of(*specs["tA"]))
        sched.submit("tB", lambda: df_of(*specs["tB"]))
        sched.run()
        a = sched.sessions[0]
        b = sched.sessions[1]
        outcomes = sched.stats()["outcomes"]
        print(json.dumps({
            "ok": a.state == "done" and b.state == "failed",
            "timeout_typed": isinstance(b.error, AdmissionTimeoutError),
            "tA_bit_equal": (a.result is not None
                             and _result_sha(a.result) == solo["tA"]),
            "outcomes": outcomes,
            "admission_timeouts": sched.stats()["admission_timeouts"],
            "case": case}), flush=True)
        return 0

    print(json.dumps({"ok": False,
                      "error": f"unknown fleet case {case!r}"}))
    return 1


# ---------------------------------------------------------------------------
# parent: schedule generation + child supervision
# ---------------------------------------------------------------------------

def _draw_schedule(rng) -> dict:
    n = 1 + int(rng.random() < 0.4)
    entries, resume_entries = [], []
    have_capacity = False
    for _ in range(n):
        site, kinds = SITE_KINDS[int(rng.integers(0, len(SITE_KINDS)))]
        kind = kinds[int(rng.integers(0, len(kinds)))]
        if kind == "capacity" and have_capacity:
            # the capacity ladder is ONE rung by design (bounded
            # escalation, docs/robustness.md) and a capacity abort is
            # not resumable — a schedule with two capacity faults is
            # unconvergeable by construction, like the excluded
            # stall/desync kinds; redraw the kind (or drop the entry
            # where capacity is the site's only kind)
            others = [k for k in kinds if k != "capacity"]
            if not others:
                continue
            kind = others[int(rng.integers(0, len(others)))]
        have_capacity = have_capacity or kind == "capacity"
        nth = int(rng.integers(1, 3))
        entry = f"{site}::{nth}={kind}"
        if site == "ckpt.load":
            # ckpt.load only fires while RESUMING (Stage.load_piece) —
            # armed in the primary run it would never trigger and the
            # schedule would silently degenerate to a happy-path run;
            # arm it in the resume leg instead
            resume_entries.append(entry)
        else:
            entries.append(entry)
    if resume_entries and not any(e.endswith("=kill") for e in entries):
        # the resume leg only runs after a hard crash — force one
        entries.append("ckpt.write::2=kill")
    return {"faults": ",".join(entries),
            "resume_faults": ",".join(resume_entries)}


def _pinned_schedules() -> list[dict]:
    return [
        # the acceptance path: SIGKILL mid-range-loop after one piece
        # committed, resume must fast-forward (ffwd > 0, no recompute of
        # the committed piece)
        {"faults": "ckpt.write::2=kill", "resume_faults": "",
         "expect_ffwd": True},
        # a corrupted page among the committed pieces: resume detects
        # the hash mismatch and degrades to recompute — still bit-equal
        {"faults": "ckpt.write::1=corrupt,ckpt.write::3=kill",
         "resume_faults": ""},
        # corruption injected on the LOAD side of the resume itself
        {"faults": "ckpt.write::3=kill",
         "resume_faults": "ckpt.load::1=corrupt"},
        # the overlap escape hatch: kill-and-resume with the
        # phase-overlapped scheduler DISABLED — both dispatch modes must
        # hash-equal the overlap-on baseline, crash and resume included
        {"faults": "ckpt.write::2=kill", "resume_faults": "",
         "expect_ffwd": True,
         "env": {"CYLON_TPU_PACKED_OVERLAP": "0"}},
    ]


def _spawn(args, workdir: str, faults: str, resume: bool,
           extra_env: dict | None = None, concurrent: int = 1,
           only: int | None = None, stream: bool = False,
           elastic: bool = False, world: int | None = None,
           skew: bool = False, skew_frac: float = 0.8,
           multislice: bool = False, fleet: bool = False,
           compile_flow: bool = False) -> tuple:
    env = dict(os.environ)
    env.pop("CYLON_TPU_PREEMPT_GRACE_S", None)  # armed per-leg only
    # the out-of-core caps and the topology declaration are armed
    # per-leg too (extra_env) — an inherited budget/slice map would
    # cap or re-route the baseline legs
    for k in ("CYLON_TPU_HBM_BUDGET", "CYLON_TPU_HOST_BUDGET",
              "CYLON_TPU_SPILL_DIR", "CYLON_TPU_SLICES",
              "CYLON_TPU_TOPO_SHUFFLE", "CYLON_TPU_FLEET_CASE",
              "CYLON_TPU_FLEET_TARGET", "CYLON_TPU_ADMISSION_TIMEOUT_S",
              "CYLON_TPU_COMPILE_CACHE_DIR", "CYLON_TPU_COMPILE_TIMEOUT_S",
              "CYLON_TPU_COMPILE_BUDGET", "CYLON_TPU_AUDIT"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["CYLON_TPU_FAULTS"] = faults
    env["CYLON_TPU_CKPT_DIR"] = workdir
    # arm the flight recorder (cylon_tpu/obs/trace): a killed or drained
    # child leaves TRACE_POSTMORTEM.json next to its manifests — the
    # crash breadcrumb the schedules assert below
    env["CYLON_TPU_TRACE"] = os.path.join(workdir, "trace.json")
    env.update(extra_env or {})
    if resume:
        env["CYLON_TPU_RESUME"] = "1"
    else:
        env.pop("CYLON_TPU_RESUME", None)
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           f"--rows={args.rows}", f"--chunks={args.chunks}",
           f"--concurrent={concurrent}", f"--world={world or 4}"]
    if only is not None:
        cmd.append(f"--only={only}")
    if stream:
        cmd.append("--stream")
    if elastic:
        cmd.append("--elastic")
    if skew:
        cmd += ["--skew", f"--skew-frac={skew_frac}"]
    if multislice:
        cmd.append("--multislice")
    if fleet:
        cmd.append("--fleet")
    if compile_flow:
        cmd.append("--compile")
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=600)
    info = None
    for line in reversed(p.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                info = json.loads(line)
            except ValueError:
                pass
            break
    return p, info


def _run_schedule(args, idx: int, sched: dict, baseline_sha: str,
                  failures: list) -> None:
    workdir = tempfile.mkdtemp(prefix=f"soak{idx:02d}_", dir=args.workdir)

    def fail(msg, proc=None):
        tail = ("\n" + (proc.stdout + proc.stderr)[-2000:]) if proc else ""
        failures.append(f"schedule {idx} ({sched['faults']!r}): {msg}{tail}")

    p, info = _spawn(args, workdir, sched["faults"], resume=False,
                     extra_env=sched.get("env"))
    outcome = "ok"
    if p.returncode == 0:
        if not info or info.get("sha") != baseline_sha:
            fail(f"completed but result diverged: {info}", p)
        elif info["events"] > MAX_RECOVERY_EVENTS:
            fail(f"unbounded retries: {info['events']} recovery events", p)
    elif p.returncode == -9 or p.returncode == RESUMABLE_EXIT:
        outcome = "killed" if p.returncode == -9 else "resumable"
        if not os.path.exists(os.path.join(workdir,
                                           "TRACE_POSTMORTEM.json")):
            # the injected kill dumps the flight recorder BEFORE the
            # SIGKILL; a ResumableAbort dumps at its flush — either way
            # the breadcrumb must land next to the manifests
            fail("no TRACE_POSTMORTEM.json breadcrumb after kill/abort", p)
        p2, info2 = _spawn(args, workdir, sched.get("resume_faults", ""),
                           resume=True, extra_env=sched.get("env"))
        if p2.returncode != 0:
            fail(f"resume run failed rc={p2.returncode}", p2)
        elif not info2 or info2.get("sha") != baseline_sha:
            fail(f"resumed result diverged: {info2}", p2)
        elif info2["events"] > MAX_RECOVERY_EVENTS:
            fail(f"unbounded retries on resume: {info2['events']}", p2)
        elif sched.get("expect_ffwd") \
                and not info2.get("resume_fast_forwarded_pieces"):
            fail(f"resume recomputed committed pieces: {info2}", p2)
        else:
            outcome += (f"+resumed(ffwd="
                        f"{info2.get('resume_fast_forwarded_pieces')})")
    else:
        fail(f"unexpected exit rc={p.returncode}", p)
    rf = sched.get("resume_faults", "")
    print(f"# schedule {idx:02d} faults={sched['faults']!r}"
          + (f" resume_faults={rf!r}" if rf else "")
          + f" -> {outcome}", flush=True)
    shutil.rmtree(workdir, ignore_errors=True)


def run_concurrent(args) -> int:
    """The ``--concurrent K`` acceptance flow: K serving sessions on one
    mesh, a mid-query SIGKILL targeted at tenant t0 (``@session``
    grammar), and a resumed rerun that must (a) fast-forward t0 past its
    committed pieces (ffwd > 0) and (b) leave EVERY tenant's answer
    bit-equal to its solo (single-session) run — crash isolation under
    multi-tenancy, not just under a single query."""
    K = args.concurrent
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_conc_")
    failures: list = []

    # solo legs: each tenant alone on the mesh — the bit-equality oracle
    solo_shas: dict = {}
    for i in range(K):
        p, info = _spawn(args, os.path.join(args.workdir, f"solo{i}"),
                         "", resume=False, concurrent=K, only=i)
        if p.returncode != 0 or not info or not info.get("shas"):
            print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
            print(f"chaos-soak: solo leg t{i} failed", file=sys.stderr)
            return 1
        solo_shas.update(info["shas"])
    print(f"# concurrent acceptance: {K} tenants, solo shas "
          f"{ {k: v[:12] for k, v in solo_shas.items()} }", flush=True)

    # un-injected concurrent run: interleaving alone must not change
    # any tenant's answer
    ckdir = os.path.join(args.workdir, "conc")
    p, info = _spawn(args, ckdir, "", resume=False, concurrent=K)
    if p.returncode != 0 or not info or info.get("shas") != solo_shas:
        failures.append(f"un-injected concurrent run diverged: {info}")

    # the pinned kill schedule: SIGKILL mid-query in tenant t0 after its
    # 2nd committed piece; every tenant dies with the process
    killdir = os.path.join(args.workdir, "kill")
    p, info = _spawn(args, killdir, "ckpt.write::2=kill@t0",
                     resume=False, concurrent=K)
    if p.returncode not in (-9, RESUMABLE_EXIT):
        failures.append(
            f"targeted kill did not crash the process (rc={p.returncode})")
    else:
        p2, info2 = _spawn(args, killdir, "", resume=True, concurrent=K)
        if p2.returncode != 0 or not info2:
            failures.append(f"concurrent resume failed rc={p2.returncode}:"
                            f" {(p2.stdout + p2.stderr)[-2000:]}")
        elif info2.get("shas") != solo_shas:
            failures.append(f"resumed concurrent result diverged: {info2}")
        elif not info2.get("resume_fast_forwarded_pieces"):
            failures.append(
                f"resume recomputed t0's committed pieces: {info2}")
        else:
            print(f"# kill@t0 + resume -> ok (ffwd="
                  f"{info2['resume_fast_forwarded_pieces']})", flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"concurrent": K, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def run_fleet(args) -> int:
    """The ``--fleet`` acceptance flow (docs/serving.md): four pinned
    legs proving fleet survival under live traffic — (1) a priority
    arrival preempts a running tenant which requeues and finishes
    bit-equal with ffwd > 0; (2) SIGKILL *during* the preemption drain
    (the new ``sched.preempt`` injector site) → relaunch resumes every
    tenant bit-equal; (3) elastic mesh resize world 4→2 mid-traffic
    with ZERO failed tenants (``failed_typed == 0``, every tenant
    bit-equal to its solo run after the cross-world resume); (4) the
    admission-deadline leg surfaces a typed AdmissionTimeoutError, not
    a hang."""
    own_workdir = args.workdir is None
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_fleet_")
    failures: list = []

    def fail(msg, p=None):
        if p is not None:
            print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
        failures.append(msg)
        print(f"# FAIL: {msg}", flush=True)

    # -- leg 1: preempt -> requeue -> resume, bit-equal ------------------
    d1 = os.path.join(args.workdir, "preempt")
    p, info = _spawn(args, d1, "", resume=False, fleet=True,
                     extra_env={"CYLON_TPU_FLEET_CASE": "preempt"})
    if p.returncode != 0 or not info or not info.get("ok"):
        fail(f"preempt leg rc={p.returncode}: {info}", p)
    elif (not info.get("bit_equal")
          or info.get("preemptions", 0) < 1
          or not info.get("resume_fast_forwarded_pieces")):
        fail(f"preempt leg: expected bit-equal requeue with ffwd>0, "
             f"got {info}")
    else:
        print(f"# preempt leg -> ok (preemptions="
              f"{info['preemptions']}, ffwd="
              f"{info['resume_fast_forwarded_pieces']})", flush=True)

    # -- leg 2: SIGKILL during the preemption drain ----------------------
    d2 = os.path.join(args.workdir, "killdrain")
    p, info = _spawn(args, d2, "sched.preempt::1=kill@tA", resume=False,
                     fleet=True,
                     extra_env={"CYLON_TPU_FLEET_CASE": "preempt"})
    if p.returncode != -9:
        fail(f"kill during preemption drain did not crash the process "
             f"(rc={p.returncode})", p)
    else:
        p2, info2 = _spawn(args, d2, "", resume=True, fleet=True,
                           extra_env={"CYLON_TPU_FLEET_CASE": "preempt"})
        if p2.returncode != 0 or not info2 or not info2.get("ok"):
            fail(f"killdrain resume rc={p2.returncode}: {info2}", p2)
        elif (not info2.get("bit_equal")
              or not info2.get("resume_fast_forwarded_pieces")):
            fail(f"killdrain resume diverged or recomputed: {info2}")
        else:
            print(f"# kill@drain + resume -> ok (ffwd="
                  f"{info2['resume_fast_forwarded_pieces']})", flush=True)

    # -- leg 3: elastic mesh resize world 4 -> 2, zero failed tenants ----
    d3 = os.path.join(args.workdir, "resize")
    p, info = _spawn(args, d3, "", resume=False, fleet=True, world=4,
                     extra_env={"CYLON_TPU_FLEET_CASE": "resize",
                                "CYLON_TPU_FLEET_TARGET": "2"})
    if p.returncode != RESUMABLE_EXIT or not info \
            or not info.get("resumable"):
        fail(f"resize leg did not drain resumably rc={p.returncode}: "
             f"{info}", p)
    elif info.get("failed_typed"):
        fail(f"resize drain failed tenants typed: {info}")
    elif info.get("resize_target") != 2:
        fail(f"resize drain carried wrong target: {info}")
    else:
        p2, info2 = _spawn(args, d3, "", resume=True, fleet=True,
                           world=2,
                           extra_env={"CYLON_TPU_FLEET_CASE": "resize"})
        if p2.returncode != 0 or not info2 or not info2.get("ok"):
            fail(f"resize resume rc={p2.returncode}: {info2}", p2)
        elif not info2.get("bit_equal") or info2.get("failed_typed"):
            fail(f"resize resume diverged or failed tenants: {info2}")
        elif not info2.get("resume_world_mismatch"):
            fail(f"resize resume never took the cross-world reshard "
                 f"path: {info2}")
        else:
            print(f"# resize 4->2 + resume -> ok (world_mismatch="
                  f"{info2['resume_world_mismatch']}, ffwd="
                  f"{info2.get('resume_fast_forwarded_pieces', 0)})",
                  flush=True)

    # -- leg 4: admission deadline is typed, not a hang ------------------
    d4 = os.path.join(args.workdir, "deadline")
    p, info = _spawn(args, d4, "", resume=False, fleet=True,
                     extra_env={"CYLON_TPU_FLEET_CASE": "deadline",
                                "CYLON_TPU_ADMISSION_TIMEOUT_S": "0.3"})
    if p.returncode != 0 or not info or not info.get("ok"):
        fail(f"deadline leg rc={p.returncode}: {info}", p)
    elif not info.get("timeout_typed") or not info.get("tA_bit_equal"):
        fail(f"deadline leg: expected typed AdmissionTimeoutError with "
             f"tA unharmed, got {info}")
    else:
        print(f"# admission deadline -> ok (typed, "
              f"timeouts={info['admission_timeouts']})", flush=True)

    if own_workdir:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps({"fleet_legs": 4, "failures": len(failures),
                      "detail": failures[:10]}))
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--schedules", type=int, default=20)
    ap.add_argument("--rows", type=int, default=3000)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--concurrent", type=int, default=1,
                    help="K>1: run the K-tenant concurrent acceptance "
                         "flow (kill one tenant mid-query, resume, "
                         "assert every tenant bit-equal to its solo run)")
    ap.add_argument("--only", type=int, default=None,
                    help="(worker) restrict the concurrent scheduler to "
                         "one tenant — the solo bit-equality leg")
    ap.add_argument("--oocore", action="store_true",
                    help="run the out-of-core acceptance flow (HBM+host "
                         "budget caps force the disk tier; enospc/"
                         "corrupt/kill schedules must end bit-equal, "
                         "and the unarmed leg must write nothing)")
    ap.add_argument("--stream", action="store_true",
                    help="run the streaming-ingest acceptance flow "
                         "(SIGKILL mid-ingest with checkpointing armed; "
                         "resume must fast-forward committed window "
                         "state and stay bit-equal)")
    ap.add_argument("--elastic", action="store_true",
                    help="run the elastic-resume acceptance flow "
                         "(checkpoint at world=2, SIGKILL/SIGTERM "
                         "mid-run, resume at world=1 and at world=2-"
                         "after-reshard; every schedule must end "
                         "bit-equal to the uninterrupted baseline)")
    ap.add_argument("--skew", action="store_true",
                    help="run the adaptive-skew-split acceptance flow "
                         "(faults inside a skew-split join must recover "
                         "onto the SAME voted plan, bit-equal to the "
                         "unsplit baseline; the armed-at-skew-0 leg "
                         "must add zero collectives)")
    ap.add_argument("--skew-frac", type=float, default=0.8,
                    help="(worker) fraction of probe rows on the hot key")
    ap.add_argument("--compile", dest="compile_flow",
                    action="store_true",
                    help="run the compile-lifecycle acceptance flow "
                         "(SIGKILL mid-compile leaves an intent journal "
                         "the rerun adopts into the crash quarantine; "
                         "poisoned manifest entries drop to a clean "
                         "recompile; stalls surface typed via the "
                         "watchdog; the unarmed leg writes nothing)")
    ap.add_argument("--multislice", action="store_true",
                    help="run the multi-slice topology acceptance flow "
                         "(simulated two-tier grid: hierarchical route "
                         "bit-equal to flat with a voted plan and ~1/R "
                         "DCN messages; whole-slice kill resumes via "
                         "elastic reshard; unarmed single-slice leg "
                         "adds zero collectives)")
    ap.add_argument("--audit", action="store_true",
                    help="run the data-integrity audit acceptance flow "
                         "(armed silent-corruption drill caught as a "
                         "typed DataIntegrityError and recomputed "
                         "bit-equal on the flat, skew-split and "
                         "two-tier routes; persistent corruption "
                         "aborts typed; the unarmed leg does zero "
                         "fingerprint work)")
    ap.add_argument("--fleet", action="store_true",
                    help="run the fleet-survival acceptance flow "
                         "(preemptive drain/requeue with in-process "
                         "resume, SIGKILL during a preemption drain, "
                         "elastic mesh resize 4->2 mid-traffic with "
                         "zero failed tenants, typed admission "
                         "deadline)")
    ap.add_argument("--world", type=int, default=4,
                    help="(worker) mesh world size for this process")
    args = ap.parse_args()

    if args.worker:
        sys.path.insert(0, REPO)
        return worker(args)

    if args.oocore:
        return run_oocore(args)

    if args.skew:
        return run_skew(args)

    if args.audit:
        return run_audit(args)

    if args.multislice:
        return run_multislice(args)

    if args.stream:
        return run_stream(args)

    if args.compile_flow:
        return run_compile(args)

    if args.elastic:
        return run_elastic(args)

    if args.fleet:
        return run_fleet(args)

    if args.concurrent > 1:
        return run_concurrent(args)

    import numpy as np
    rng = np.random.default_rng(args.seed)
    args.workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_soak_")

    schedules = _pinned_schedules()
    while len(schedules) < args.schedules:
        schedules.append(_draw_schedule(rng))
    schedules = schedules[:args.schedules]

    # un-injected, un-checkpointed baseline: the bit-equality oracle
    p, info = _spawn(args, os.path.join(args.workdir, "baseline"), "",
                     resume=False)
    if p.returncode != 0 or not info or not info.get("sha"):
        print((p.stdout + p.stderr)[-3000:], file=sys.stderr)
        print("chaos-soak: baseline run failed", file=sys.stderr)
        return 1
    baseline_sha = info["sha"]
    print(f"# baseline sha={baseline_sha[:16]} rows={info['rows']}",
          flush=True)

    failures: list = []
    for i, sched in enumerate(schedules):
        _run_schedule(args, i, sched, baseline_sha, failures)

    print(json.dumps({"schedules": len(schedules),
                      "failures": len(failures), "seed": args.seed,
                      "detail": failures[:10]}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
