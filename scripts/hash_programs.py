"""Do a tree's programs lower to the parent's text?  (CPU sandbox, no chip.)

    cd <a copy of the tree AT ONE PATH for both sides> && \\
        ALLOW_MULTIPLE_LIBTPU_LOAD=1 python scripts/hash_programs.py out.json

Runs the benchmark's three query shapes at 2^16 rows on the CPU rig (one
device and four), records every program they launch with its static key
and argument shapes, lowers each for a DESCRIBED ``v5e:2x2`` (one chip and
four) and writes ``sha256[:16]`` of the StableHLO text with ``loc`` stripped;
then the windowed forms as ``tests/chip_compile/helpers.py`` builds them
(fused at 512 slots and at 8,192 with window 4096; ``groupby__raw_fn`` /
``_combine_fn`` at 16,384 with window 1024; ``repart__filter_count_fn``
and ``_filter_mat_fn`` plain and at window 1024).  Mosaic's kernel body embeds
the PATHS and LINE NUMBERS of the traced Python frames - THIS file's
among them - so both trees must sit at the same path and hold the same copy
of this script and of that helper module (copy the parent there and the two
files into it, run, copy the change there, run, compare the outputs): a
windowed program whose hash moved compiles cold on the chip (PERF.md §6).
Nothing runs on a TPU; nothing here is a device metric."""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import re
import sys


def _is_array_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def capture_launches() -> list:
    """``(builder, (static args, static kwargs), argument specs, world)`` of
    every program the three query shapes launch on the CPU rig; an
    argument's spec is ``(shape, dtype, partition spec)``."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import cylon_tpu as ct
    from cylon_tpu.analysis import runtime
    from cylon_tpu.ctx.context import CPUMeshConfig
    from cylon_tpu.relational import (groupby_aggregate, join_tables,
                                      sort_table)

    log, world = [], [1]
    tag_program = runtime.tag_program

    def spec_of(x):
        spec = x.sharding.spec if isinstance(x, jax.Array) else P()
        return tuple(np.shape(x)), np.dtype(x.dtype).str, tuple(spec)

    def recording_tag(name, program, key=()):
        tagged = tag_program(name, program, key)

        def call(*args, **kw):
            log.append((name, key[1], jax.tree.map(
                spec_of, args, is_leaf=lambda x: isinstance(
                    x, (jax.Array, np.ndarray))), world[0]))
            return tagged(*args, **kw)
        return call

    rng = np.random.default_rng(0)
    n = 1 << 16

    def col():
        return rng.integers(0, int(n * 0.9), n).astype(np.int64)

    left, right = {"k": col(), "a": col()}, {"k": col(), "b": col()}
    runtime.tag_program = recording_tag
    try:
        for world[0] in (1, 4):
            env = ct.CylonEnv(config=ct.LocalConfig() if world[0] == 1
                              else CPUMeshConfig(world_size=4))
            lt = ct.Table.from_pydict(left, env)
            rt = ct.Table.from_pydict(right, env)
            for _ in range(2):      # first sight, then the settled dispatch
                groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"),
                                  "k", [("a", "sum"), ("b", "sum")]
                                  ).to_pandas()
            if world[0] == 1:
                sort_table(groupby_aggregate(lt, "k", [("a", "sum")]),
                           "a_sum").to_pandas()
    finally:
        runtime.tag_program = tag_program
    return log


def text_hash(program, args) -> tuple:
    """``(sha256[:16], characters)`` of the program's lowered text, its
    source locations taken out."""
    from cylon_tpu.exec import compiler
    fn = compiler._unwrap_program(program)
    target = fn._fn if isinstance(fn, compiler._Program) else fn
    text = re.sub(r"loc\([^)]*\)", "", target.lower(*args).as_text())
    text = "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("#loc"))
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text)


def hash_all(log: list, tests_dir: str) -> dict:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.relational import fused

    # a compile for a described device cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    meshes = {w: Mesh(np.array(topo.devices[:w]), (ROW_AXIS,))
              for w in (1, 4)}
    out = {}
    for name, key, args, world in log:
        ident = (f"{world}dev {name.rpartition('cylon_tpu.')[2]} "
                 f"{hashlib.md5(repr(key).encode()).hexdigest()[:6]}")
        if ident in out:
            continue
        mesh = meshes[world]
        module, _, builder = name.rpartition(".")
        program = getattr(importlib.import_module(module), builder)(
            mesh, *key[0], **dict(key[1]))
        out[ident] = text_hash(program, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x[0], np.dtype(x[1]),
                sharding=NamedSharding(mesh, P(*x[2]))),
            args, is_leaf=_is_array_spec))

    # the windowed forms, as tests/chip_compile/ builds them; the
    # builders ask the backend whether to interpret the kernel
    sys.path.insert(0, tests_dir)
    from chip_compile import helpers as tcc
    default_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        for world, mesh in meshes.items():
            rows, static = 1 << 14, tcc._fused_static(2)
            # (a tree from before PR 35 counts lanes: three u32 arrays)
            fargs = tcc._fused_args(mesh, rows,
                                    static[2] if len(static) > 5 else 3)
            gargs = tcc._groupby_args(mesh, 17408)
            out[f"{world}dev fused 512 plain"] = text_hash(
                fused._fused_fn(mesh, rows, False, *static, 512, 1), fargs)
            out[f"{world}dev fused 8192 w4096"] = text_hash(
                fused._fused_fn(mesh, rows, False, *static, 8192, 1, 4096),
                fargs)
            for site in ("raw", "combine"):
                out[f"{world}dev groupby {site} 16384 w1024"] = text_hash(
                    tcc._groupby_program(mesh, site, 16384, 1024), gargs)
            out.update(_filter_hashes(mesh, world))
    finally:
        jax.default_backend = default_backend
    return out


def _filter_hashes(mesh, world: int) -> dict:
    """``filter_table``'s two programs (the TPC-H cell's) at a 65,536-row
    shard of Q3's ``lineitem`` lanes: the count program, the materialize
    program with XLA's gather and with the windowed take."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.ops import lanes
    from cylon_tpu.relational import repart
    cap, out_cap = 1 << 16, 1 << 15
    spec = lanes.plan_lanes(("int64",) * 15, (False,) * 15,
                            (True,) * 12 + (False,) * 3)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    vc = S((world,), np.int32, sharding=rep)
    cols = tuple(S((world * cap,), np.int64, sharding=row) for _ in range(15))
    out = {f"{world}dev filter count": text_hash(
        repart._filter_count_fn(mesh, cap),
        (vc, S((world * cap,), np.bool_, sharding=row)))}
    for window in (0, 1024):
        out[f"{world}dev filter mat w{window}"] = text_hash(
            repart._filter_mat_fn(mesh, cap, out_cap, spec, window),
            (vc, S((world * cap,), np.int32, sharding=row), cols,
             (None,) * 15))
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    tree = os.getcwd()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, tree)
    import cylon_tpu
    if not cylon_tpu.__file__.startswith(tree + os.sep):
        raise SystemExit(f"run from the tree's root: cylon_tpu is "
                         f"{cylon_tpu.__file__}, not under {tree}")
    out = hash_all(capture_launches(), os.path.join(tree, "tests"))
    with open(argv[0], "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(len(out), "programs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
