"""Primitive microbenchmarks at bench scale — refreshes docs/DESIGN.md's
measured cost model on the current chip. Not part of the suite.

Each timing ends in a tiny host pull of the result, which waits for the
device like ``jax.block_until_ready``."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

_pull = jax.jit(lambda x: x.reshape(-1)[:2].astype(jnp.float32).sum())


def sync(out):
    leaves = jax.tree.leaves(out)
    np.asarray(_pull(leaves[0]))


def timed(label, fn, *args, iters=3):
    f = jax.jit(fn)
    sync(f(*args))
    best = 1e9
    for _ in range(iters):
        t0 = time.perf_counter()
        sync(f(*args))
        best = min(best, time.perf_counter() - t0)
    n = args[0].shape[0]
    print(f"{label:44s} {best*1e3:9.1f} ms  {best/n*1e9:6.2f} ns/row",
          flush=True)


def main():
    n = 128_000_000
    m = 80_000_000
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 1 << 31, n, dtype=np.int32))
    x2 = jnp.asarray(rng.integers(0, 1 << 31, n, dtype=np.int32))
    x64 = jnp.asarray(rng.integers(0, 1 << 62, n, dtype=np.int64))
    idx_r = jnp.asarray(rng.integers(0, n, m, dtype=np.int32))
    idx_m = jnp.asarray(np.sort(rng.integers(0, n, m, dtype=np.int32)))
    idx_n = jnp.asarray(rng.integers(0, m, n, dtype=np.int32))
    pos = jnp.arange(n, dtype=jnp.int32)

    timed("sort 1op i32", lambda a: jax.lax.sort((a,), num_keys=1), x)
    timed("sort 1key+1payload", lambda a, b: jax.lax.sort(
        (a, b), num_keys=1, is_stable=True), x, x2)
    timed("sort 1key+3payload", lambda a, b, c, d: jax.lax.sort(
        (a, b, c, d), num_keys=1, is_stable=True), x, x2, pos, pos)
    # distinct payload arrays per operand — XLA CSEs identical operands,
    # which would understate the per-lane payload cost
    timed("sort 1key+5payload", lambda a, b, c, d: jax.lax.sort(
        (a, b, c, d, b + 1, c + 1), num_keys=1, is_stable=True),
        x, x2, pos, pos)
    timed("sort 2key+2payload", lambda a, b, c, d: jax.lax.sort(
        (a, b, c, d), num_keys=2, is_stable=True), x, x2, pos, pos)
    timed("sort i64 key + payload", lambda a, b: jax.lax.sort(
        (a, b), num_keys=1, is_stable=True), x64, pos)
    timed("cumsum i32", jnp.cumsum, x)
    timed("cummax i32", jax.lax.cummax, x)
    timed("gather 1-D rand (m from n)", lambda i, a: a[i], idx_r, x)
    timed("gather 1-D monotone", lambda i, a: a[i], idx_m, x)
    timed("gather (n,2) rand", lambda i, a, b: jnp.stack([a, b], 1)[i],
          idx_r, x, x2)
    timed("gather (n,4) rand",
          lambda i, a, b: jnp.stack([a, b, a, b], 1)[i], idx_r, x, x2)
    timed("gather (n,6) rand",
          lambda i, a, b: jnp.stack([a, b, a, b, a, b], 1)[i], idx_r, x, x2)
    timed("gather (n,6) monotone",
          lambda i, a, b: jnp.stack([a, b, a, b, a, b], 1)[i], idx_m, x, x2)
    timed("stack (n,6) only",
          lambda a, b: jnp.stack([a, b, a, b, a, b], 1), x, x2)
    timed("gather 6 separate 1-D rand",
          lambda i, a, b: (a[i], b[i], a[i] + 1, b[i] + 1, a[i] + 2,
                           b[i] + 2), idx_r, x, x2)
    timed("scatter-max n->m slots",
          lambda i, p: jnp.zeros(m, jnp.int32).at[i].max(p, mode="drop"),
          idx_n, pos)
    timed("scatter-set m->n slots",
          lambda i, p: jnp.zeros(n, jnp.int32).at[i].set(p[:m], mode="drop"),
          idx_m, pos)
    timed("scatter-add m->n slots",
          lambda i, p: jnp.zeros(n, jnp.int32).at[i].add(p[:m], mode="drop"),
          idx_m, pos)
    timed("cumsum i64", jnp.cumsum, x64)
    timed("elementwise 3-op", lambda a, b: a * 2 + b, x, x2)
    timed("searchsorted m in n-sorted",
          lambda a, v: jnp.searchsorted(a, v, method="compare_all"),
          jnp.sort(x)[:n], idx_r)


if __name__ == "__main__":
    main()
