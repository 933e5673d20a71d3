"""Test rig: force an 8-device virtual CPU mesh.

The moral equivalent of the reference's ``mpirun --oversubscribe`` localhost
testing (SURVEY.md §4.3): multi-chip is simulated by multi-device on one
host.  Must run before any test imports jax-heavy modules.

``jax.config.update('jax_platforms')`` wins as long as no backend has been
initialized, so the platform is pinned here rather than left to env vars.
"""

import os

# read by the CPU client at first backend init
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# trace-safety sentinel (CYLON_TPU_TRACECHECK=1): every test runs under a
# device→host transfer guard — the ONLY sanctioned implicit D2H pulls are
# the cylon_tpu.utils.host funnel's (wrapped in explicit allow scopes) —
# and the retrace sentinel counts XLA compiles per (builder, shape
# signature); budget overruns (RT301/RT302) fail the session at exit.
# Off by default so the plain tier-1 run is byte-identical.
# ---------------------------------------------------------------------------
TRACECHECK = os.environ.get("CYLON_TPU_TRACECHECK") == "1"

# CYLON_TPU_COMPILE_COUNT=1 (set by tests/run_all.py): count XLA
# backend_compile events through the compile-lifecycle facade's
# monitoring listener and print one greppable `# COMPILE_COUNT` line per
# test file at session exit — the suite driver's per-file compile budget
# audit (docs/robustness.md "Compile lifecycle")
COMPILE_COUNT = os.environ.get("CYLON_TPU_COMPILE_COUNT") == "1"

if COMPILE_COUNT:
    from cylon_tpu.exec import compiler as _compiler
    _compiler.install_listener()

if TRACECHECK:
    from cylon_tpu.analysis import runtime as _rt
    _rt.enable()


@pytest.fixture(autouse=TRACECHECK)
def _tracecheck_transfer_guard():
    with jax.transfer_guard_device_to_host("disallow"):
        yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')")


def pytest_sessionfinish(session, exitstatus):
    if COMPILE_COUNT:
        from cylon_tpu.exec import compiler as _compiler
        st = _compiler.stats()
        names = sorted({os.path.basename(str(a)).split("::")[0]
                        for a in session.config.args}) or ["?"]
        print(f"\n# COMPILE_COUNT file={','.join(names)} "
              f"n={st['compile_events']} "
              f"seconds={st['compile_seconds']:g} "
              f"live={st['programs_live']}", flush=True)
    if not TRACECHECK:
        return
    from cylon_tpu.analysis import runtime as _rt
    violations = _rt.check_budgets()
    if violations:
        rep = "\n".join(f"  {rule} {msg}" for rule, _b, msg in violations)
        print(f"\n[tracecheck] retrace-sentinel violations:\n{rep}")
        session.exitstatus = 1
    else:
        st = _rt.state()
        n = sum(st.compiles.values())
        print(f"\n[tracecheck] retrace sentinel clean: "
              f"{n} compiling calls across {len(st.builds)} builders")


@pytest.fixture(scope="session")
def env8():
    """8-rank distributed env (one per virtual CPU device)."""
    import cylon_tpu as ct
    from cylon_tpu.ctx.context import CPUMeshConfig
    return ct.CylonEnv(config=CPUMeshConfig())


@pytest.fixture(scope="session")
def env4():
    import cylon_tpu as ct
    from cylon_tpu.ctx.context import CPUMeshConfig
    return ct.CylonEnv(config=CPUMeshConfig(world_size=4))


@pytest.fixture(scope="session")
def env1():
    import cylon_tpu as ct
    return ct.CylonEnv(config=ct.LocalConfig())


@pytest.fixture(params=["env1", "env4"])
def env(request):
    """One device and the mesh of four; a module that wants other worlds
    defines its own ``env`` (tests/test_pipeline.py)."""
    return request.getfixturevalue(request.param)


@pytest.fixture
def two_tier(env8, monkeypatch):
    """The 8-rank session env re-declared as 2 slices of 4 (the CPU
    simulation knob); restores the single-slice view on teardown."""
    from cylon_tpu.topo import model as topo_model
    monkeypatch.setenv("CYLON_TPU_SLICES", "2")
    topo_model._reslice()
    yield env8
    monkeypatch.delenv("CYLON_TPU_SLICES")
    topo_model._reslice()


@pytest.fixture()
def rng():
    return np.random.default_rng(42)
