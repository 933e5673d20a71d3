"""Multi-host execution: 2 jax.distributed processes over one logical world
(VERDICT item 6 — makes TPUConfig(distributed=True) and the cross-process
barrier tested code).  The moral analog of the reference's `mpirun -np 2`
suite runs (python/pycylon/test/test_all.py:23-29)."""

import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


#: memo for the backend-capability probe: once one world size shows the
#: jaxlib CPU client can't run multiprocess collectives, skip the other
#: parametrizations up front instead of re-spawning doomed process trees
_CPU_MULTIPROCESS_UNSUPPORTED = False


def _spawn_drivers(nproc, extra_env, timeout=570):
    driver = os.path.join(os.path.dirname(__file__), "multihost_driver.py")
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env.update(extra_env)
    procs = [subprocess.Popen(
        [sys.executable, driver, str(i), str(nproc), coord],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(driver))))
        for i in range(nproc)]
    outs = []
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


def _cpu_backend_unsupported(outs) -> bool:
    return any("Multiprocess computations aren't implemented on the CPU "
               "backend" in out for out in outs)


def test_kill_rank0_and_resume(tmp_path):
    """Durable checkpoint acceptance, two-process edition: launch 1 kills
    rank 0 mid-range-loop (injected `kill` at ckpt.write — rank 1's
    orphaned commit vote surfaces as a typed desync under the watchdog);
    launch 2 resumes with CYLON_TPU_RESUME=1 and both ranks must
    fast-forward past the committed pieces and converge on the IDENTICAL
    manifest epoch and bit-equal result (asserted in-driver by
    allgather)."""
    global _CPU_MULTIPROCESS_UNSUPPORTED
    if _CPU_MULTIPROCESS_UNSUPPORTED:
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    base_env = {"CYLON_TPU_MH_SCENARIO": "kill_resume",
                "CYLON_TPU_CKPT_DIR": str(tmp_path),
                "CYLON_TPU_WATCHDOG_S": "30"}
    procs, outs = _spawn_drivers(2, base_env)
    if _cpu_backend_unsupported(outs):
        _CPU_MULTIPROCESS_UNSUPPORTED = True
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    # rank 0 must have died by SIGKILL mid-loop; rank 1 must NOT have
    # silently completed (its commit partner vanished)
    assert procs[0].returncode == -9, (procs[0].returncode, outs[0][-2000:])
    assert "KILLRESUME_OK pid=1" not in outs[1], outs[1][-2000:]
    procs2, outs2 = _spawn_drivers(2, {**base_env, "CYLON_TPU_RESUME": "1"})
    for i, (p, out) in enumerate(zip(procs2, outs2)):
        assert p.returncode == 0, f"resume proc {i} failed:\n{out[-4000:]}"
        assert f"KILLRESUME_OK pid={i}" in out, out[-2000:]
    # both ranks printed the same epoch (also asserted in-driver via
    # allgather) and fast-forwarded at least one committed piece
    import re
    stats = [re.search(r"KILLRESUME_OK pid=\d+ epoch=(\d+) ffwd=(\d+)", o)
             for o in outs2]
    assert all(stats), outs2
    assert stats[0].group(1) == stats[1].group(1), outs2
    assert all(int(m.group(2)) > 0 for m in stats), outs2


def test_elastic_resume_world_change(tmp_path):
    """Elastic resume acceptance, cross-process edition (docs/
    robustness.md "Elastic resume & preemption grace"): a 2-process
    world=8 session checkpoints a two-stage workload and is SIGKILLed at
    stage 2's first write (stage 1 complete across BOTH rank dirs); a
    SINGLE-process world=4 relaunch must detect the topology change,
    merge the two rank dirs' shard blocks, re-shard stage 1 onto the
    4-device mesh (ffwd > 0, resharded > 0), recompute stage 2 and match
    the pandas oracle."""
    global _CPU_MULTIPROCESS_UNSUPPORTED
    if _CPU_MULTIPROCESS_UNSUPPORTED:
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    base_env = {"CYLON_TPU_MH_SCENARIO": "elastic_resume",
                "CYLON_TPU_CKPT_DIR": str(tmp_path),
                "CYLON_TPU_WATCHDOG_S": "30"}
    procs, outs = _spawn_drivers(2, base_env)
    if _cpu_backend_unsupported(outs):
        _CPU_MULTIPROCESS_UNSUPPORTED = True
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    # rank 0 died by SIGKILL mid-stage-2; rank 1 must not have completed
    assert procs[0].returncode == -9, (procs[0].returncode, outs[0][-2000:])
    assert "ELASTIC_OK pid=1" not in outs[1], outs[1][-2000:]
    # the relaunch is ONE process (4 local devices): world 8 -> 4
    procs2, outs2 = _spawn_drivers(1, {**base_env, "CYLON_TPU_RESUME": "1"})
    assert procs2[0].returncode == 0, outs2[0][-4000:]
    import re
    m = re.search(r"ELASTIC_OK pid=0 world=4 ffwd=(\d+) resharded=(\d+) "
                  r"mismatch=(\d+)", outs2[0])
    assert m, outs2[0][-2000:]
    assert int(m.group(1)) > 0 and int(m.group(2)) > 0, outs2[0][-1000:]


@pytest.mark.parametrize("nproc", [2, 4])
def test_multi_process_join_groupby_sort(nproc):
    """2- and 4-process worlds (reference test_all.py runs mpirun -n {2,4});
    the 4-process case exercises the multi-controller paths in
    _shard_frames/host pulls beyond W=2."""
    global _CPU_MULTIPROCESS_UNSUPPORTED
    if _CPU_MULTIPROCESS_UNSUPPORTED:
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    driver = os.path.join(os.path.dirname(__file__), "multihost_driver.py")
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    procs = [subprocess.Popen(
        [sys.executable, driver, str(i), str(nproc), coord],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(driver))))
        for i in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=570)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any("Multiprocess computations aren't implemented on the CPU backend"
           in out for out in outs):
        # capability gate, not a code failure: this jaxlib's CPU client has
        # no cross-process collective transport (newer jaxlibs use a gloo
        # mesh), so a multi-controller CPU world cannot run here at all
        _CPU_MULTIPROCESS_UNSUPPORTED = True
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-4000:]}"
        assert f"MULTIHOST_OK pid={i} world={4 * nproc}" in out, out[-2000:]
        # rank-coherent recovery: only rank 0 was injected, yet every
        # process converged on the same retry branch without deadlock
        assert f"RECOVERY_OK pid={i} events=1" in out, out[-2000:]
        # rank-coherent spill: eviction pressure injected on rank 0 only;
        # consensus made every process run the IDENTICAL eviction
        # sequence (the driver cross-checks the sequence hash via
        # allgather and prints it per rank)
        assert f"SPILL_OK pid={i} evictions=" in out, out[-2000:]
        # rank-coherent skew plan: the Code.SkewPlan vote rode the real
        # cross-process wire and every rank adopted the IDENTICAL plan
        # hash (the driver allgathers the hash crc and bit-checks the
        # stitched + fused outputs against the unsplit plan)
        assert f"SKEWPLAN_OK pid={i} keys=" in out, out[-2000:]
        # the two-hop topology leg: identical voted plan hash on every
        # rank + bit/order-equal to the flat route (asserted in-driver)
        assert f"TOPO_OK pid={i} plan=" in out, out[-2000:]
        # the integrity-audit leg: armed fingerprints voted over the
        # real cross-process wire (identical order-invariant fp on
        # every rank, allgather-checked in-driver), and a corruption
        # injected on rank 0 only made EVERY rank raise typed and
        # retry identically — one integrity event per rank, bit-equal
        assert f"AUDIT_OK pid={i} fp=" in out, out[-2000:]
