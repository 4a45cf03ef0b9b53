"""In-VMEM bitonic sort for Pallas TPU kernels (shared by ``dedup_compact``
and ``knn_topk``).

The keys live in VMEM refs and are sorted in place, one (8, 128) vreg tile
at a time, so the kernel's code grows with log^2(W) stages and not with the
width W:

* ``flat`` layout — a ref of (W/128, 128) holds ONE sequence, position
  ``row * 128 + lane``; a tile is an 8-row group of 1024 positions;
* ``rows`` layout — a ref of (8, W) holds eight independent sequences, one
  per sublane, position ``lane``; a tile is a 128-lane block.

A stage (k, j) pairs position i with ``i ^ j``.  Pairs inside a tile are
exchanged with two ``pltpu.roll`` rotates and a select (lane rotates for
j < 128, sublane rotates above that); pairs ``j`` apart across tiles
exchange whole tiles in a ``fori_loop``.  The order is lexicographic over
the key list (first key primary), ascending.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUB = 8


def stages(W: int):
    """The bitonic network: (k, j) compare-exchange stages for width W."""
    out = []
    k = 2
    while k <= W:
        j = k // 2
        while j >= 1:
            out.append((k, j))
            j //= 2
        k *= 2
    return out


def _le(a, b):
    """Elementwise lexicographic ``a <= b`` over key lists."""
    le = a[-1] <= b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        le = (x < y) | ((x == y) & le)
    return le


def _exchange(xs, idx, k: int, j: int):
    """Stage (k, j) on tiles whose partners lie in the same tile."""
    lower = (idx & j) == 0
    asc = (idx & k) == 0
    axis, n, s = (1, LANES, j) if j < LANES else (0, SUB, j // LANES)
    ps = [jnp.where(lower, pltpu.roll(x, n - s, axis), pltpu.roll(x, s, axis))
          for x in xs]
    keep = _le(xs, ps) == (lower == asc)
    return [jnp.where(keep, x, p) for x, p in zip(xs, ps)]


def for_tiles(n: int, body, init):
    """``fori_loop(0, n, body, init)``, inlined when there is one tile."""
    return body(0, init) if n == 1 else jax.lax.fori_loop(0, n, body, init)


def sort_refs(refs, *, flat: bool):
    """Sort the same-shape key refs in place (see the module docstring)."""
    span = SUB * LANES if flat else LANES
    n_tiles = (refs[0].shape[0] // SUB if flat else refs[0].shape[1] // LANES)
    W = n_tiles * span

    def at(t):
        if flat:
            return (pl.ds(pl.multiple_of(t * SUB, SUB), SUB), slice(None))
        return (slice(None), pl.ds(pl.multiple_of(t * LANES, LANES), LANES))

    def load(t):
        return [r[at(t)] for r in refs]

    def store(t, xs):
        for r, x in zip(refs, xs):
            r[at(t)] = x

    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 1)
    if flat:
        lane = lane + jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0) * LANES

    def in_tile(kjs):
        def body(t, c):
            xs, idx = load(t), t * span + lane
            for k, j in kjs:
                xs = _exchange(xs, idx, k, j)
            store(t, xs)
            return c
        for_tiles(n_tiles, body, 0)

    def across(k: int, jt: int):
        def body(p, c):
            t = (p // jt) * (2 * jt) + p % jt
            a, b = load(t), load(t + jt)
            keep_a = _le(a, b) == (((t * span) & k) == 0)
            store(t, [jnp.where(keep_a, x, y) for x, y in zip(a, b)])
            store(t + jt, [jnp.where(keep_a, y, x) for x, y in zip(a, b)])
            return c
        for_tiles(n_tiles // 2, body, 0)

    in_tile(stages(min(W, span)))            # every tile sorted on its own
    k = 2 * span
    while k <= W:
        j = k // 2
        while j >= span:
            across(k, j // span)
            j //= 2
        in_tile([(k, jj) for kk, jj in stages(k) if kk == k and jj < span])
        k *= 2
