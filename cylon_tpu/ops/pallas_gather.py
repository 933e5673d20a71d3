"""Pallas windowed row gather — the groupby prefix-diff's hot op.

The grouped-reduce machinery (ops/groupby.grouped_reduce) ends in ONE
``mat[starts]`` gather of a (seg_cap, L) u32 lane matrix at SORTED row
indices.  XLA:TPU lowers that gather to a per-row dynamic-slice loop at a
flat ~21-24 ns/row regardless of row width (measured v5e, 32M rows of a
64M x 8 u32 matrix: 750 ms — the single dominant stage of the fused
join+groupby at bench shape; separate 1-D gathers are 10x worse, scatter
and sort-compaction 6-8x worse).

But ``starts`` is sorted and DENSE (one start per group; at bench shape
~45% of all rows are gathered), so each tile of TILE consecutive output
rows reads from a bounded source window.  That turns the gather into:

  per output tile j:  DMA  mat.T[:, ws_j : ws_j+W]  (HBM -> VMEM, async,
                      double-buffered across the sequential grid)
                      byte-split window (4L x W) @ onehot^T (TILE x W)
                      on the MXU -> (4L, TILE), recombined by sublane
                      slices into the (L, TILE) output block

with the u32 lanes split into four exact-in-bf16 u8 sub-lanes for the
matmul and recombined after.  Selection-by-matmul replaces XLA's per-row
loop with dense MXU/VPU work (~10x at bench shape).

Mosaic landmines this shape navigates (v5e libtpu 2026-07, found
empirically — each violation produced wrong VALUES or failed compiles):
- the source matrix must be TRANSPOSED (L, M) so the dynamic DMA slice
  rides the minor 128-tiled dim; an (M, L<128) input gets lane-padded to
  (M, 128) in HBM (18x memory) and its slices can't align to tiling;
- window starts must be 128-aligned AND hinted via ``pl.multiple_of``
  (arithmetic inside the slice expression fails to legalize);
- index-map literals must be wrapped in jnp.int32 under x64 (i64 block
  indices fail func.func legalization);
- the accumulator must be LANE-MAJOR (4L, TILE): lane-dim slices of a
  (TILE, 4L) result at offset 16 silently zero values < 128 (a Mosaic
  lane-rotation bug); sublane slices are exact.

Skew safety: a tile whose index span exceeds W cannot be served from its
window.  The wrapper computes the span check on device and wraps fast and
plain paths in ``lax.cond`` — degenerate densities (a few huge groups)
fall back to the XLA gather at RUNTIME with no host round-trip.  (Low
densities also mean a small seg_cap, where the plain gather is cheap —
callers only route here when the predicted density clears
:data:`MIN_DENSITY`.)

Reference slot: the type-dispatched aggregation kernels this feeds replace
cpp/src/cylon/groupby/hash_groupby.cpp:340 (single-pass combine) — the
gather is the TPU-native analog of its group-id indexed writes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.stages import staged

#: output rows per grid step
TILE = 256
#: don't attempt the windowed path below this measured density (average
#: tile spans approach MAX_WINDOW and the margin collapses)
MIN_DENSITY = 0.10
MIN_WINDOW, MAX_WINDOW = 1024, 4096


def pick_window(density_est: float) -> int:
    """Static window size for a compile-time density estimate: cover the
    average span TILE/density with ~1.8x margin, clamped to pow2 bounds."""
    from .. import config
    want = int(TILE / max(density_est, 1e-6) * 1.8)
    return max(MIN_WINDOW, min(MAX_WINDOW, config.pow2ceil(want)))


def _kernel(ws_ref, idx_ref, mat_ref, out_ref, win_ref, wb_ref, sem_ref,
            *, window: int, n_lanes: int):
    j = pl.program_id(0)
    nt = pl.num_programs(0)
    L = n_lanes

    def dma(slot, t):
        # int32 everywhere: x64 mode would promote python-int indices to
        # i64, which tpu.memref_slice rejects
        slot = jnp.asarray(slot, jnp.int32)
        start = pl.multiple_of(ws_ref[t], 128)
        return pltpu.make_async_copy(
            mat_ref.at[:, pl.ds(start, window)],
            win_ref.at[slot], sem_ref.at[slot])

    @pl.when(j == 0)
    def _():
        dma(0, jnp.int32(0)).start()

    @pl.when(j + 1 < nt)
    def _():
        dma(jax.lax.rem(j + 1, jnp.int32(2)), j + 1).start()

    slot = jax.lax.rem(j, jnp.int32(2))
    dma(slot, j).wait()

    # u32 -> four u8 planes, exact in bf16 (no direct u32->float cast in
    # Mosaic: hop through i32/f32); assembled in a scratch so one 4L-row
    # matmul serves all planes
    w32 = win_ref[slot]                                    # (L, window)
    for k in range(4):
        wb_ref[pl.ds(k * L, L), :] = ((w32 >> jnp.uint32(8 * k))
                                      & jnp.uint32(0xFF)) \
            .astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)

    # idx block is (1, 8, TILE//8); a lane-crossing reshape to (TILE,) is
    # unsupported in Mosaic, so build the one-hot in (8, TILE//8, W)
    # geometry and merge only the LEADING dims (minor dim intact)
    lidx = idx_ref[0] - ws_ref[j]                          # (8, TILE//8)
    iota = jax.lax.broadcasted_iota(jnp.int32,
                                    (8, TILE // 8, window), 2)
    oh = (iota == lidx[:, :, None]).astype(jnp.bfloat16)
    oh = oh.reshape(TILE, window)
    # (4L, W) x (TILE, W) contracting W -> LANE-MAJOR (4L, TILE)
    accT = jax.lax.dot_general(wb_ref[...], oh, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    u = accT.astype(jnp.int32).astype(jnp.uint32)
    out_ref[...] = (u[0:L] | u[L:2 * L] << jnp.uint32(8)
                    | u[2 * L:3 * L] << jnp.uint32(16)
                    | u[3 * L:4 * L] << jnp.uint32(24))


def _pallas_take(mat_t, idx2, ws, window: int, interpret: bool):
    # idx arrives as (G, 8, TILE//8): a (1, 8, TILE//8) block satisfies the
    # TPU (8, 128)-tiling rule (last dim equals the array's)
    G = idx2.shape[0]
    tile = idx2.shape[1] * idx2.shape[2]
    L, M = mat_t.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, 8, tile // 8),
                         lambda j, ws_ref: (j, jnp.int32(0), jnp.int32(0))),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((L, tile),
                               lambda j, ws_ref: (jnp.int32(0), j)),
        scratch_shapes=[
            pltpu.VMEM((2, L, window), jnp.uint32),
            pltpu.VMEM((4 * L, window), jnp.bfloat16),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # under shard_map (check_vma) the output must declare which mesh axes
    # it varies over — the union of the inputs'
    vma = frozenset()
    for a in (ws, idx2, mat_t):
        vma = vma | getattr(a.aval, "vma", frozenset())
    out_shape = jax.ShapeDtypeStruct((L, G * tile), jnp.uint32, vma=vma)
    return pl.pallas_call(
        partial(_kernel, window=window, n_lanes=L),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="cylon_windowed_take",
    )(ws, idx2, mat_t)


def supported(n_rows: int, seg_cap: int, n_lanes: int, window: int) -> bool:
    """Static eligibility of the windowed path for a gather of ``seg_cap``
    sorted indices into an (n_rows, n_lanes) u32 matrix."""
    return (seg_cap % TILE == 0 and seg_cap >= TILE
            and n_rows >= window and n_lanes >= 1)


@staged("segment_gather")
def windowed_take_t(mat_t, idx, window: int, interpret: bool | None = None):
    """``mat_t[:, idx]`` for SORTED int32 ``idx`` into a LANE-MAJOR (L, M)
    u32 ``mat_t``.  Returns ``(out, ok)``: out is (L, S) — row l holds
    lane l at every index — and ok is a scalar bool.

    The matrix must arrive lane-major: an XLA transpose of an (M, L)
    matrix at bench shape costs ~700 ms on v5e (per-element, like its
    gathers) — callers stack lanes as ROWS instead, which is free.

    When a tile's index span exceeds the window (skewed group sizes), the
    overflowing rows come out as ZEROS and ``ok`` is False — the caller
    must discard the result and redispatch a no-window program.  No
    in-graph fallback: wrapping both paths in ``lax.cond`` forces an XLA
    relayout of the 2 GB operand (~690 ms measured, erasing the win), so
    the mispredict round-trip lives at the host dispatch layer like the
    seg-cap mispredict it already handles.  Caller must ensure
    :func:`supported`.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    L, M = mat_t.shape
    S = idx.shape[0]
    G = S // TILE
    idx = idx.astype(jnp.int32)
    # pad BOTH dims to the DMA tiling: lanes to a sublane multiple (8)
    # and the row count to a lane-tile multiple (128).  The row pad is
    # load-bearing for the tail: with M % 128 != 0, the 128-floored
    # window-start clamp excludes the last rows — exactly where the
    # sentinel index (= n_live) every empty group slot points at lives.
    L8 = -(-L // 8) * 8
    M128 = -(-M // 128) * 128
    if L8 != L or M128 != M:
        mat_t = jnp.pad(mat_t, ((0, L8 - L), (0, M128 - M)))
    heads = idx[::TILE]
    # window starts 128-aligned (the minor-dim DMA slice must match the
    # HBM tiling); clamp so every window stays in-bounds
    ws = jnp.minimum((heads // 128) * 128, jnp.int32(M128 - window))
    lasts = idx[TILE - 1::TILE]
    ok = jnp.all(lasts - ws < window)
    idx2 = idx.reshape(G, 8, TILE // 8)
    out = _pallas_take(mat_t, idx2, ws, window, interpret)[:L]
    return out, ok


# Added BELOW the kernel and its wrapper: Mosaic's serialized kernel body
# embeds the line numbers of the traced frames above (PERF.md, PR 30's
# lesson), so nothing up there moves.

@staged("gather_rows")
def take_rows_t(mat_t, idx, window: int, interpret: bool | None = None):
    """:func:`windowed_take_t`'s rows for a caller that moves whole ROWS
    by a monotone take index (a filter's kept rows keep their order) and
    has MEASURED every tile's span before it dispatched: no ``ok`` to
    pull, and the operations carry stage ``gather_rows`` - ``segment_gather``
    keeps meaning the grouped reduce.  ``mat_t`` arrives at the DMA tiling
    (``ops/lanes.pack_lane_rows(..., 8)``, a row count that is a multiple
    of 128), so the wrapper's ``jnp.pad`` copies nothing."""
    L, M = mat_t.shape
    if L % 8 or M % 128:
        raise ValueError(f"take_rows_t wants an (8k, 128m) matrix, got "
                         f"{mat_t.shape}")
    return windowed_take_t.__wrapped__(mat_t, idx, window, interpret)[0]


def max_tile_span(srt, last):
    """The most source rows any :data:`TILE` of a windowed take at the
    sorted index ``min(srt, last)`` has to hold: the tile's last index
    less its 128-floored first, plus one - what :func:`windowed_take_t`'s
    ``ok`` compares with the window, read before a row is moved (a window
    start clamped to the matrix's end only ever serves).  ``last`` is the
    last real index: slots past it are padding that rides at ``last``,
    and a tile of padding alone spans its own 128."""
    n = srt.shape[0]
    if n % TILE:
        return jnp.int32(n)        # no tile grid: never eligible
    heads = jnp.minimum(srt[::TILE], last)
    lasts = jnp.minimum(srt[TILE - 1::TILE], last)
    return jnp.max(lasts - (heads // 128) * 128 + 1).astype(jnp.int32)
