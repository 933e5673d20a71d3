"""The exchange's two placements (parallel/shuffle.send_fill, recv_place):
rows arrive sorted by target, so the send blocks and the receive buffer are
filled by segment copies at offsets the count matrix gives.  Every case is
held to the order contract by a numpy reference: shard ``d`` of the output
is, for ``src`` ascending, ``src``'s rows for ``d`` in source order, then
zeros up to the receive capacity."""

import re

import numpy as np
import pytest

import jax

from cylon_tpu import config
from cylon_tpu.exec import compiler
from cylon_tpu.parallel import shuffle
from cylon_tpu.utils import timing

_L = 3          # u32 lanes of the packed matrix


def _payload(w: int, cap: int, seed: int):
    """A (w·cap, L) u32 lane matrix and a 1-D float64 side array, no row
    zero (a delivered row is told from the zero padding)."""
    rng = np.random.default_rng(seed)
    mat = rng.integers(1, 1 << 32, (w * cap, _L), dtype=np.uint64)
    side = rng.random(w * cap) + 1.0
    return mat.astype(np.uint32), side


def _reference(tgt, cols, w: int, out_cap: int):
    cap = tgt.shape[0] // w
    outs = []
    for col in cols:
        out = np.zeros((w * out_cap,) + col.shape[1:], col.dtype)
        for d in range(w):
            at = d * out_cap
            for src in range(w):
                rows = col[src * cap:(src + 1) * cap][
                    tgt[src * cap:(src + 1) * cap] == d]
                out[at:at + len(rows)] = rows
                at += len(rows)
        outs.append(out)
    return outs


def _streams(w: int, cap: int, counts, rng):
    """Targets of ``w`` shards of ``cap`` rows from a (w, w) count matrix:
    shard ``s`` holds ``counts[s, d]`` rows for ``d``, shuffled among
    themselves, then padding rows (target ``w``)."""
    tgt = np.full(w * cap, w, np.int32)
    for s in range(w):
        row = np.repeat(np.arange(w, dtype=np.int32), counts[s])
        assert len(row) <= cap
        rng.shuffle(row)
        tgt[s * cap:s * cap + len(row)] = row
    return tgt


def _uniform(w, rng):
    cap = 1000
    tgt = rng.integers(0, w, w * cap).astype(np.int32)
    tgt.reshape(w, cap)[:, 900:] = w            # padding rows present
    return cap, tgt


def _all_to_one(w, rng):
    # 20,000 rows a shard, nine in ten for rank 2, the rest spread: the
    # heavy streams take three rounds of the 8192-row block, the light
    # ones end in the first (later rounds start past their end)
    cap = 20_000
    tgt = np.where(rng.random(w * cap) < 0.9, 2,
                   rng.integers(0, w, w * cap)).astype(np.int32)
    return cap, tgt


def _nothing_for_one(w, rng):
    cap = 600
    tgt = rng.integers(0, w - 1, w * cap).astype(np.int32)
    tgt[tgt == 1] = w - 1                        # rank 1 receives nothing
    return cap, tgt


def _exactly_block(w, rng):
    # the longest stream is exactly the block (256): every slot of its
    # send block is a valid row
    counts = np.full((w, w), 100)
    counts[0, 1] = 256
    return 700, _streams(w, 700, counts, rng)


def _empty_shard(w, rng):
    cap = 500
    tgt = rng.integers(0, w, w * cap).astype(np.int32)
    tgt[2 * cap:3 * cap] = w                     # shard 2: no valid row
    return cap, tgt


def _run_ends_at_cap(w, rng):
    # full shards (no padding row): the last destination's run ends at
    # cap, and its block-wide window passes it
    cap = 1000
    counts = np.full((w, w), cap // w)
    counts[:, 0] += 50
    counts[:, w - 1] -= 50
    return cap, _streams(w, cap, counts, rng)


def _window_ends_at_out_cap(w, rng):
    # rank 0 receives exactly its capacity (1024) in streams shorter than
    # the block (512): the later sources' windows pass out_cap and are
    # taken clamped, the rows read shifted
    counts = np.full((w, w), 10)
    counts[:, 0] = [300] + [(1024 - 300) // (w - 1)] * (w - 1)
    counts[w - 1, 0] += 1024 - counts[:, 0].sum()
    assert counts[:, 0].sum() == 1024
    return 400, _streams(w, 400, counts, rng)


def _nothing_at_all(w, rng):
    return 64, np.full(w * 64, w, np.int32)


CASES = {
    "uniform": ("env4", _uniform, 1),
    "all_to_one_multiround": ("env8", _all_to_one, 3),
    "nothing_for_one": ("env4", _nothing_for_one, 1),
    "exactly_block": ("env4", _exactly_block, 1),
    "empty_shard": ("env4", _empty_shard, 1),
    "run_ends_at_cap": ("env4", _run_ends_at_cap, 1),
    "window_ends_at_out_cap": ("env4", _window_ends_at_out_cap, 1),
    "window_ends_at_out_cap_w8": ("env8", _window_ends_at_out_cap, 1),
    "nothing_at_all": ("env4", _nothing_at_all, 1),
}


def _multiround() -> int:
    return timing.snapshot().get("exchange.multiround", {}).get("n", 0)


@pytest.mark.parametrize("case", list(CASES))
def test_exchange_keeps_the_order_contract(request, case):
    envname, make, rounds = CASES[case]
    env = request.getfixturevalue(envname)
    w = env.world_size
    cap, tgt = make(w, np.random.default_rng(5))
    cols = _payload(w, cap, seed=9)
    counts = shuffle.count_targets(env.mesh, tgt)
    want_counts = np.array(
        [[np.sum(tgt[s * cap:(s + 1) * cap] == d) for d in range(w)]
         for s in range(w)])
    assert (counts == want_counts).all()
    before = _multiround()
    outs, per_dest = shuffle.exchange(env.mesh, tgt, counts, cols)
    assert _multiround() - before == (1 if rounds > 1 else 0)
    max_c = int(counts.max())
    block = config.pow2ceil(min(max(max_c, 1), shuffle.exchange_block_cap(
        int(counts.sum()), w)))
    assert -(-max_c // block) == (rounds if max_c else 0)
    assert (per_dest == want_counts.sum(axis=0)).all()
    out_cap = outs[0].shape[0] // w
    assert out_cap == config.pow2ceil(int(per_dest.max()))
    for got, want in zip(outs, _reference(tgt, cols, w, out_cap)):
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert (got == want).all()


@pytest.mark.parametrize("block,out_cap,rounds", [
    (96, 96, 1),        # block == out_cap: every window is the whole buffer
    (64, 160, 2),       # a window's start passes out_cap in round 2
    (17, 130, 5),       # a block that divides nothing; cap < rounds·block
    (128, 128, 1),      # block > cap: the send windows need the padding
])
def test_round_engine_at_forced_sizes(env4, block, out_cap, rounds):
    """``_round_fn`` with a block and a capacity ``exchange`` would not
    pick for these counts (any block ≤ out_cap with enough rounds is
    legal): the clamping cases at sizes a failure can be read at."""
    w, cap = 4, 80
    counts = np.array([[20, 0, 45, 10], [3, 70, 5, 1], [0, 0, 0, 0],
                       [30, 20, 25, 5]])
    assert counts.max() <= rounds * block
    assert counts.sum(axis=0).max() <= out_cap
    tgt = _streams(w, cap, counts, np.random.default_rng(3))
    cols = _payload(w, cap, seed=4)
    mesh = env4.mesh
    srt = shuffle.sort_by_target(mesh, w, tgt, cols)
    outs = tuple(shuffle._alloc_fn(mesh, out_cap, str(c.dtype),
                                   c.shape[1:])() for c in cols)
    outs = shuffle._round_fn(mesh, w, block, out_cap, rounds)(
        counts.astype(np.int32), outs, srt)
    for got, want in zip(outs, _reference(tgt, cols, w, out_cap)):
        assert (np.asarray(got) == want).all()


def _dispatches() -> dict:
    from cylon_tpu.obs import metrics
    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith("exchange_dispatches")}


def _one_source_to_one_dest(w, rng):
    # shard 1 sends its 20,000 rows to rank 2 and nobody else has a row:
    # three rounds of the 8192-row block, three source shards empty
    cap = 20_000
    tgt = np.full(w * cap, w, np.int32)
    tgt[cap:2 * cap] = 2
    return cap, tgt


def _every_row_to_one(w, rng):
    return 900, np.full(w * 900, 1, np.int32)


_RIDE = 'exchange_dispatches{path="ride"}'
_PERM = 'exchange_dispatches{path="perm",reason="%s"}'

#: case -> (u32 lanes, side array's dtype or None, traffic, rounds,
#:          why the rows do not ride or None, sort operands)
PATHS = {
    "1_lane": (1, None, _uniform, 1, None, 2),
    "2_lanes": (2, None, _uniform, 1, None, 3),
    "4_lanes_3_rounds": (4, None, _one_source_to_one_dest, 3, None, 5),
    "6_lanes": (6, None, _uniform, 1, None, 7),
    "2_lanes_and_i32_sidecar": (2, np.int32, _uniform, 1, None, 4),
    "2_lanes_empty_shard": (2, None, _empty_shard, 1, None, 3),
    "4_lanes_every_row_to_one": (4, None, _every_row_to_one, 1, None, 5),
    "7_lanes": (7, None, _uniform, 1, "over_operand_budget", 2),
    "6_lanes_and_i32_sidecar": (6, np.int32, _empty_shard, 1,
                                "over_operand_budget", 2),
    "3_lanes_and_f64_side": (3, np.float64, _uniform, 1, "not_32bit", 2),
    "f64_side_3_rounds": (2, np.float64, _one_source_to_one_dest, 3,
                          "not_32bit", 2),
}


def _cols(rng, rows: int, lanes: int, side):
    cols = (rng.integers(1, 1 << 32, (rows, lanes),
                         dtype=np.uint64).astype(np.uint32),)
    if side is np.float64:
        cols += (rng.random(rows) + 1.0,)
    elif side is not None:
        cols += (rng.integers(1, 1 << 31, rows, side),)
    return cols


def _exchange_spans(fn):
    """``fn()`` and the arguments of the ``exchange.<route>`` spans it
    opened."""
    from cylon_tpu.obs import trace
    rec = trace.arm(capacity=256)
    try:
        out = fn()
        return out, [e[6] for e in rec.events()
                     if e[2] == "X" and e[3].startswith("exchange.")]
    finally:
        trace.disarm()


def _bumped(before: dict) -> dict:
    return {k: v - before[k] for k, v in _dispatches().items()
            if v != before[k]}


@pytest.mark.parametrize("case", list(PATHS))
def test_riding_and_perm_paths_are_bit_equal(env4, case):
    """How the rows reach destination order is ``shuffle.ride_rule``'s to
    say, from the arrays' shapes and dtypes, and changes nothing that is
    delivered: the path ``exchange`` takes (counted, and on its span), the
    other one forced, and the riding sort's stable form (what a world too
    wide for the one-word key gets) fill bit-equal receive buffers — the
    numpy reference's."""
    lanes, side, make, rounds, why, sort_ops = PATHS[case]
    mesh, w = env4.mesh, env4.world_size
    rng = np.random.default_rng(11)
    cap, tgt = make(w, rng)
    cols = _cols(rng, w * cap, lanes, side)
    assert shuffle.ride_rule(cols) == (sort_ops, why)
    counts = shuffle.count_targets(mesh, tgt)
    before = _dispatches()
    (outs, per_dest), (span,) = _exchange_spans(
        lambda: shuffle.exchange(mesh, tgt, counts, cols))
    assert _bumped(before) == {_PERM % why if why else _RIDE: 1}
    assert span["path"] == ("perm" if why else "ride")
    assert span["sort_operands"] == sort_ops and span["rounds"] == rounds
    assert (per_dest == counts.sum(axis=0)).all()
    out_cap = outs[0].shape[0] // w
    want = _reference(tgt, cols, w, out_cap)
    for fn in (shuffle._prep_fn(mesh, w, True),
               shuffle._prep_fn(mesh, w, False),
               shuffle._prep_fn(mesh, 1 << 32, True)):   # the stable form
        alloc = tuple(shuffle._alloc_fn(mesh, out_cap, str(c.dtype),
                                        c.shape[1:])() for c in cols)
        forced = shuffle._round_fn(mesh, w, span["block"], out_cap, rounds)(
            counts.astype(np.int32), alloc, fn(tgt, cols))
        for got, taken, ref in zip(forced, outs, want):
            got, taken = np.asarray(got), np.asarray(taken)
            assert got.dtype == taken.dtype == ref.dtype
            assert (got == ref).all() and (taken == ref).all()


@pytest.mark.parametrize("lanes,side,hops", [
    (2, None, {_RIDE: 2}),                 # hop 1: 1 + 2 + the sidecar
    (6, None, {_PERM % "over_operand_budget": 1, _RIDE: 1}),
    (3, np.float64, {_PERM % "not_32bit": 2}),
])
def test_two_hop_hops_take_the_same_rule(two_tier, lanes, side, hops):
    """Each hop of the two-hop route target-sorts through
    ``shuffle.sort_by_target``: hop 1 carries the final target as one
    more 32-bit operand (riding with the lanes where the budget holds
    both), hop 2 the table's own arrays; order-equal to the flat plan's
    contract either way."""
    mesh, w = two_tier.mesh, two_tier.world_size
    rng = np.random.default_rng(17)
    cap, tgt = _uniform(w, rng)
    cols = _cols(rng, w * cap, lanes, side)
    counts = shuffle.count_targets(mesh, tgt)
    before = _dispatches()
    (outs, per_dest), (span,) = _exchange_spans(
        lambda: shuffle.exchange(mesh, tgt, counts, cols))
    assert _bumped(before) == hops
    ops, why = shuffle.ride_rule(cols)      # the span says hop 2's
    assert (span["sort_operands"], span["path"]) == (
        ops, "perm" if why else "ride")
    out_cap = outs[0].shape[0] // w
    for got, want in zip(outs, _reference(tgt, cols, w, out_cap)):
        assert (np.asarray(got) == want).all()


def test_block_over_receive_capacity_is_refused(env4):
    w = 4
    out = np.zeros(w * 4, np.int64)
    with pytest.raises(ValueError, match="exceeds the receive capacity"):
        shuffle._round_fn(env4.mesh, w, 8, 4, 1)(
            np.zeros((w, w), np.int32), (out,), (np.zeros(w * 8, np.int64),))


def _lowered(prog, *args) -> str:
    """StableHLO of a built program as jax lowers it (before XLA:CPU
    expands a scatter into a loop)."""
    fn = compiler._unwrap_program(prog)
    target = fn._fn if isinstance(fn, compiler._Program) else fn
    return target.lower(*args).as_text()


@pytest.mark.parametrize("rounds", [1, 3])
def test_round_program_holds_no_scatter_and_one_all_to_all(env4, rounds):
    w, cap, block, out_cap = 4, 4096, 1024, 8192
    S = jax.ShapeDtypeStruct
    text = _lowered(
        shuffle._round_fn(env4.mesh, w, block, out_cap, rounds),
        S((w, w), np.int32),
        (S((w * out_cap, 2), np.uint32),), (S((w * cap, 2), np.uint32),))
    assert "scatter" not in text
    assert len(re.findall(r"\ball_to_all\b", text)) == 1
    # unconditional: under a static-trip loop at most, never a branch
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert ("stablehlo.while" in text) == (rounds > 1)


def test_count_program_holds_no_scatter(env4):
    text = _lowered(shuffle._count_fn(env4.mesh, 4),
                    jax.ShapeDtypeStruct((4 * 4096,), np.int32))
    assert "scatter" not in text


@pytest.mark.parametrize("hop", [1, 2])
def test_tier_round_program_shares_the_placements(env8, hop):
    """The two-hop route's grouped rounds are the same body
    (shuffle.exchange_rounds): no scatter, the one grouped all_to_all."""
    from cylon_tpu.topo import exchange as topo_exchange
    w, cap, block, out_cap = 8, 2048, 512, 4096
    S = jax.ShapeDtypeStruct
    text = _lowered(
        topo_exchange._tier_round_fn(env8.mesh, w, 2, hop, block, out_cap,
                                     2),
        S((w, w), np.int32),
        (S((w * out_cap,), np.float64),), (S((w * cap,), np.float64),))
    assert "scatter" not in text
    assert len(re.findall(r"\ball_to_all\b", text)) == 1
