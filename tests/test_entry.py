"""Driver-entry regression tests.

Round-1 lesson: the driver's multichip dryrun must be exercised
by the suite itself, and it must never touch any backend other than cpu —
the round-1 dryrun died because ingestion staged arrays on the default
(accelerator) backend before distributing.  The subprocess test reproduces
the driver environment (host-device-count flag only, no JAX_PLATFORMS pin)
and asserts the cpu client is the ONLY initialized backend.
"""

import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_8():
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
        __graft_entry__.dryrun_multichip(8)
    finally:
        sys.path.remove(REPO)


def test_entry_jit_compiles_and_runs():
    """The jitted step against pandas: join total, group keys, both sums —
    a kernel signature that moved under entry() has to show here."""
    import jax
    import pandas as pd
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
        fn, args = __graft_entry__.entry()
        gkeys, a_sum, b_sum, total = jax.jit(fn)(*args)
    finally:
        sys.path.remove(REPO)
    l_keys, l_vals, r_keys, r_vals = args
    joined = pd.DataFrame({"k": l_keys, "a": l_vals}).merge(
        pd.DataFrame({"k": r_keys, "b": r_vals}), on="k")
    exp = joined.groupby("k", as_index=False).sum()
    assert int(total) == len(joined)
    n = len(exp)
    got = pd.DataFrame({"k": np.asarray(gkeys)[:n], "a": np.asarray(a_sum)[:n],
                        "b": np.asarray(b_sum)[:n]})
    got = got.sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(got["k"], exp["k"])
    np.testing.assert_allclose(got["a"], exp["a"], rtol=1e-9)
    np.testing.assert_allclose(got["b"], exp["b"], rtol=1e-9)


def test_dryrun_touches_only_cpu_backend():
    """Run the dryrun in a clean subprocess (driver-style env: device-count
    flag, NO platform pin) and assert no non-cpu backend got initialized."""
    code = """
import jax, sys
sys.path.insert(0, {repo!r})
import __graft_entry__
__graft_entry__.dryrun_multichip(8)
# private, but present and a dict of live backends on jax 0.9.0
from jax._src import xla_bridge
backends = set(xla_bridge._backends)
assert backends <= {{"cpu"}}, f"non-cpu backends initialized: {{backends}}"
print("OK")
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run([sys.executable, "-c", code.format(repo=REPO)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_chip_smoke_phases_on_cpu_mesh(capsys):
    """chip_smoke.py's resident and pipelined phases, rehearsed at 65536
    rows on the CPU mesh against its pandas reference — the platform check
    lives in main(), which must refuse this rig and print no result."""
    import cylon_tpu as ct
    from cylon_tpu.ctx.context import CPUMeshConfig
    from cylon_tpu.exec import checkpoint, compiler, memory, recovery
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    compiler.install_listener()
    # what ``check_not_degraded`` reads is the process's: a file that ran
    # earlier in this xdist worker may have spilled or checkpointed
    recovery.reset_events()
    memory.reset_stats()
    checkpoint.reset_stats()
    env = ct.CylonEnv(config=CPUMeshConfig(world_size=1))
    inp = chip_smoke.make_inputs(65536, seed=0)
    ref, join_rows = chip_smoke.reference(inp)
    lt, rt = chip_smoke.build_tables(env, inp)
    res = chip_smoke.resident_phase(env, lt, rt, ref, join_rows)
    assert list(res["frame"].columns) == ["k", "a_sum", "b_sum"]
    assert res["info"]["gather"]["window"] == 0    # no TPU: plain gather
    pipe = chip_smoke.pipelined_phase(env, lt, rt, res["frame"], inp)
    assert pipe["info"]["n_chunks"] == chip_smoke.N_CHUNKS
    capsys.readouterr()
    assert chip_smoke.main([]) != 0                # the CPU is not a chip
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "no TPU found" in out.err
