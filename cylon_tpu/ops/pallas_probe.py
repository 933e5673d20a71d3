"""Pallas splitter-probe kernel — the pipelined join's phase-1 probe.

The range-partitioned pipeline (exec/pipeline.py) assigns every probe
row its key-range id by counting how many of the build side's key-group
splitters compare ``<=`` the row's key tuple (``_probe_targets_fn`` —
SURVEY §7 hard-part 2 names "pallas hash-probe" as exactly this later
optimization).  The XLA path materializes the full ``(rows, splitters)``
lexicographic comparison matrix (:func:`cylon_tpu.ops.pack.
rows_ge_splitters`): at 125M rows x R splitters x K operands that is an
O(n*R*K) HBM-resident boolean intermediate, and ``pipe.targets`` was
~1.2 s of the 12.75 s round-5 iteration (an earlier runtime).

This kernel streams the probe rows through VMEM in (8, 128) tiles with
the splitter operands resident in SMEM (scalar prefetch — splitters are
R-1 <= a few dozen scalars per operand), accumulating the ge-count
in-register: no comparison matrix ever touches HBM, and the row operands
are read exactly once.  Same structure as :mod:`cylon_tpu.ops.
pallas_gather` (the proven MXU-kernel route in this repo): interpreter
fallback on CPU rigs, ``ShapeDtypeStruct(vma=)`` on the output,
registered with the trace-safety jaxpr gate through its consumer
(``exec/pipeline._probe_targets_fn[pallas]``).

Bit-equality contract: the kernel implements the IDENTICAL lexicographic
``>=`` algebra as ``rows_ge_splitters`` over int-kind operands (uint32
operands are rebased to int32 through the order-preserving
``x ^ 0x8000_0000`` bijection, which preserves both ``>`` and ``==`` —
so the counts are equal bit-for-bit, asserted for all four join hows in
tests/test_pipeline_packed.py).  Float64 key operands (kind 'f', NaN-aware
compares) are NOT eligible — callers gate on :func:`supported` and keep
the XLA path.

One note beyond the pallas_gather landmine list: the consumer's
shard_map passes ``check_vma=False`` when this kernel is in the program
(the program is still pure-local — the jaxpr gate asserts no collective).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: probe rows per grid step — one (8, 128) int32 tile
TILE = 1024

#: unroll ceiling: splitter loops are statically unrolled S x K compares
#: per tile; past this the XLA matrix path is the better program anyway
MAX_SPLITTERS = 128


def supported(cap: int, n_split: int, kinds: tuple) -> bool:
    """Static eligibility for a per-shard probe of ``cap`` rows against
    ``n_split`` splitters whose operand kinds are ``kinds`` (from
    :class:`cylon_tpu.ops.pack.KeyOps`): int-kind operands only (float
    'f' operands need NaN-aware compares), tile-aligned capacity, and a
    bounded unroll."""
    return (cap % TILE == 0 and cap >= TILE
            and 1 <= int(n_split) <= MAX_SPLITTERS
            and all(k == "i" for k in kinds))


def _kernel(*refs, n_split: int, n_ops: int):
    # refs: n_ops splitter SMEM refs, n_ops row-tile refs, out ref
    sops = refs[:n_ops]
    rows = [refs[n_ops + i][0] for i in range(n_ops)]     # (8, TILE//8)
    out_ref = refs[2 * n_ops]
    cnt = jnp.zeros(rows[0].shape, jnp.int32)
    for j in range(n_split):
        gt = jnp.zeros(rows[0].shape, jnp.bool_)
        eq = jnp.ones(rows[0].shape, jnp.bool_)
        for i in range(n_ops):
            s = sops[i][j]                                # SMEM scalar
            gt = gt | (eq & (rows[i] > s))
            eq = eq & (rows[i] == s)
        cnt = cnt + (gt | eq).astype(jnp.int32)
    out_ref[0] = cnt


def _as_i32(x):
    """Order-preserving int32 rebase of an int-kind operand: uint32 maps
    through ``x ^ 0x8000_0000`` (a monotone bijection onto int32 order —
    ``>`` and ``==`` outcomes are unchanged, so ge-counts stay bit-equal
    to the native unsigned compare); int32 passes through."""
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(
            x ^ jnp.uint32(0x80000000), jnp.int32)
    return x.astype(jnp.int32)


def count_ge_splitters(ops: tuple, sops: tuple,
                       interpret: bool | None = None):
    """(cap,) int32: per row, how many splitter tuples compare ``<=`` the
    row's operand tuple under the lexicographic total order — exactly
    ``jnp.sum(rows_ge_splitters(ko, sops), axis=1, dtype=int32)``.

    ``ops``: K parallel (cap,) int-kind key operands of one shard;
    ``sops``: K parallel (S,) splitter operands.  Caller must ensure
    :func:`supported`.  Runs in interpreter mode off-TPU (CPU test rigs
    exercise the identical kernel logic)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_ops = len(ops)
    n_split = int(sops[0].shape[0])
    cap = ops[0].shape[0]
    G = cap // TILE
    blocks = tuple(_as_i32(o).reshape(G, 8, TILE // 8) for o in ops)
    scalars = tuple(_as_i32(s) for s in sops)
    # index-map literals wrapped in jnp.int32: i64 block indices fail
    # func.func legalization under x64 (see ops/pallas_gather.py)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_ops,
        grid=(G,),
        in_specs=[pl.BlockSpec((1, 8, TILE // 8),
                               lambda j, *_: (j, jnp.int32(0), jnp.int32(0)))
                  for _ in range(n_ops)],
        out_specs=pl.BlockSpec((1, 8, TILE // 8),
                               lambda j, *_: (j, jnp.int32(0),
                                              jnp.int32(0))),
    )
    # under shard_map (check_vma) the output must declare the mesh axes
    # it varies over — the union of the inputs'
    vma = frozenset()
    for a in (*scalars, *blocks):
        vma = vma | getattr(a.aval, "vma", frozenset())
    out_shape = jax.ShapeDtypeStruct((G, 8, TILE // 8), jnp.int32, vma=vma)
    out = pl.pallas_call(
        partial(_kernel, n_split=n_split, n_ops=n_ops),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*scalars, *blocks)
    return out.reshape(cap)
