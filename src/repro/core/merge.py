"""Sort-free merge of a small unsorted delta into a large sorted run.

Both compactions (edge CSR + delta log, ``edges.compact``; primary index +
index delta, ``index.compact_index``) fold a delta of at most a few
thousand entries into a base run of up to tens of millions that is already
sorted.  Sorting the concatenation costs a full-width sort per compaction —
and XLA's TPU sort takes minutes to *compile* at tens of millions of
entries.  The merge here needs no sort:

1. each delta entry's rank among the delta (a counting rank: an all-pairs
   compare over the small delta, reduced in one fused pass);
2. each delta entry's insertion point in the base (a vectorized binary
   search, one gather per key per step);
3. every entry's merged position follows from (1) and (2), the survivors'
   final positions from one prefix sum, and one scatter per field places
   them.

Keys compare lexicographically over a tuple of i32 arrays.  Among equal
keys base entries come first, then delta entries in log order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _lex_lt(a, b):
    """Elementwise lexicographic ``a < b`` over key tuples."""
    lt = a[-1] < b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        lt = (x < y) | ((x == y) & lt)
    return lt


def _delta_rank(keys):
    """Position of each delta entry in the stable sort of the delta."""
    n = keys[0].shape[0]
    a = tuple(k[:, None] for k in keys)
    b = tuple(k[None, :] for k in keys)
    before = _lex_lt(b, a)
    idx = jnp.arange(n, dtype=jnp.int32)
    tie = jnp.ones((n, n), bool)
    for x, y in zip(a, b):
        tie = tie & (x == y)
    before = before | (tie & (idx[None, :] < idx[:, None]))
    return jnp.sum(before.astype(jnp.int32), axis=1)


def _upper_bound(base, queries):
    """Per query: the number of base entries whose key is <= the query's
    (the base is sorted)."""
    n = base[0].shape[0]
    lo = jnp.zeros(queries[0].shape, jnp.int32)
    hi = jnp.full(queries[0].shape, n, jnp.int32)

    def step(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        at = tuple(b[jnp.minimum(mid, n - 1)] for b in base)
        go_right = (lo < hi) & ~_lex_lt(queries, at)      # base[mid] <= q
        return (jnp.where(go_right, mid + 1, lo),
                jnp.where(go_right | (lo >= hi), hi, mid))

    lo, _ = jax.lax.fori_loop(0, max(1, n).bit_length(), step, (lo, hi))
    return lo


def merge_runs(base_keys, base_vals, base_live, delta_keys, delta_vals,
               delta_live, cap: int, fills):
    """Merge a sorted base run with an unsorted delta, keep live entries.

    ``base_keys``/``delta_keys`` are equal-length tuples of i32 key arrays
    (the base sorted lexicographically); ``*_vals`` are tuples of payload
    arrays, aligned with ``fills`` (the value of an empty output slot).
    Returns ``(keys, vals, n_live)``: the first ``min(n_live, cap)`` live
    entries in key order, then fill; ``n_live > cap`` is overflow.
    """
    nb = base_keys[0].shape[0]
    nd = delta_keys[0].shape[0]
    rank = _delta_rank(delta_keys)
    ub = _upper_bound(base_keys, delta_keys)
    ub_sorted = jnp.zeros((nd,), jnp.int32).at[rank].set(ub)
    b_idx = jnp.arange(nb, dtype=jnp.int32)
    pos_b = b_idx + jnp.searchsorted(ub_sorted, b_idx,
                                     side="right").astype(jnp.int32)
    pos_d = ub + rank
    live = (jnp.zeros((nb + nd,), jnp.int32)
            .at[pos_b].set(base_live.astype(jnp.int32))
            .at[pos_d].set(delta_live.astype(jnp.int32)))
    dest = jnp.cumsum(live) - 1
    out_b = jnp.where(base_live, dest[pos_b], cap)       # cap = dropped
    out_d = jnp.where(delta_live, dest[pos_d], cap)

    def place(b, d, fill):
        out = jnp.full((cap,), fill, b.dtype)
        return out.at[out_b].set(b, mode="drop").at[out_d].set(d, mode="drop")

    fk = tuple(f for f in fills[:len(base_keys)])
    keys = tuple(place(b, d, f) for b, d, f in zip(base_keys, delta_keys, fk))
    vals = tuple(place(b, d, f) for b, d, f in
                 zip(base_vals, delta_vals, fills[len(base_keys):]))
    return keys, vals, jnp.sum(live)
