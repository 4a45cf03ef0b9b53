"""Sorted-index probe Pallas TPU kernel (the primary-index BTree of §3.1).

Hardware adaptation (DESIGN.md §7): a cached high-fanout BTree probe is a
pointer-chasing log(N) walk — hostile to a vector unit.  On TPU the index is
a *sorted array* and the left-insertion position is ``count(keys < q)``,
computed by streaming the key array block-by-block through VMEM and summing
vectorized compares.  The access pattern is a perfect sequential prefetch.

Layout: keys are viewed as (N/128, 128) rows and streamed in (bk/128, 128)
blocks; queries and their windows are (Q, 1) columns, so every compare is a
(bq, 128) query-by-lane tile and the per-query count accumulates in a
(bq, 128) VMEM scratch, reduced across lanes once after the last key block.

Grid: (query_blocks, key_blocks); the key dimension is the innermost
(sequential) axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32MAX = 2**31 - 1
LANES = 128


def _probe_kernel(k_ref, q_ref, lo_ref, hi_ref, o_ref, acc_ref, *, kr: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qs, lo, hi = q_ref[...], lo_ref[...], hi_ref[...]           # (bq, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    acc = acc_ref[...]
    for r in range(kr):
        keys = k_ref[r:r + 1, :]                                 # (1, 128)
        pos = (kb * kr + r) * LANES + lane                       # global index
        lt = (keys < qs) & (pos >= lo) & (pos < hi)              # (bq, 128)
        acc = acc + lt.astype(jnp.int32)
    acc_ref[...] = acc

    @pl.when(kb == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = jnp.sum(acc_ref[...], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def searchsorted_left_ranged(keys, queries, lo, hi, *, block_q: int = 512,
                             block_k: int = 2048, interpret: bool = False):
    """Per-query windowed probe over a block-major array of sorted runs.

    The primary index is shard-major: ``keys`` holds S independently sorted
    blocks back to back.  Each query carries its own window ``[lo, hi)`` (its
    shard's block); the result is the left insertion position *within* the
    window, i.e. ``count(keys[lo:hi] < q)`` — one streamed pass over the key
    array serves every shard at once (the batched analogue of A1 probing S
    BTrees with one wave of RDMA reads).

    keys: (N,) i32, sorted within each window; queries/lo/hi: (Q,) i32.
    Returns (Q,) i32 window-relative positions.
    """
    n, q = keys.shape[0], queries.shape[0]
    bq = min(block_q, pl.cdiv(q, 8) * 8)
    bk = max(8 * LANES, min(block_k, pl.cdiv(n, 8 * LANES) * 8 * LANES))
    bk = pl.cdiv(bk, 8 * LANES) * 8 * LANES
    qp = pl.cdiv(q, bq) * bq
    np_ = pl.cdiv(n, bk) * bk
    # padded keys sit past every window (pos >= n >= hi), so they never count
    keys_p = jnp.pad(keys, (0, np_ - n), constant_values=I32MAX)
    col = lambda a, fill: jnp.pad(a.astype(jnp.int32), (0, qp - q),
                                  constant_values=fill)[:, None]
    # padded queries get an empty window: count stays 0
    qs_p, lo_p, hi_p = col(queries, I32MAX), col(lo, 0), col(hi, 0)
    qspec = pl.BlockSpec((bq, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        functools.partial(_probe_kernel, kr=bk // LANES),
        grid=(qp // bq, np_ // bk),
        in_specs=[pl.BlockSpec((bk // LANES, LANES), lambda i, j: (j, 0)),
                  qspec, qspec, qspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.int32)],
        interpret=interpret,
    )(keys_p.reshape(np_ // LANES, LANES), qs_p, lo_p, hi_p)
    return out[:q, 0]


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def searchsorted_left(keys, queries, *, block_q: int = 512,
                      block_k: int = 2048, interpret: bool = False):
    """keys: (N,) sorted i32; queries: (Q,) i32.

    Returns (Q,) i32 left insertion positions: the whole array is every
    query's window.
    """
    n, q = keys.shape[0], queries.shape[0]
    return searchsorted_left_ranged(
        keys, queries, jnp.zeros((q,), jnp.int32), jnp.full((q,), n, jnp.int32),
        block_q=block_q, block_k=block_k, interpret=interpret)
