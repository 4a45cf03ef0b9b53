"""Primary index: the BTree of §3.1-3.2, as per-shard sorted arrays.

A1 looks a vertex up by (type, primary-key) through a distributed BTree whose
internal nodes are aggressively cached, so a probe is ~one RDMA read.  The
TPU-native equivalent of a high-fanout cached BTree is a *sorted array* probed
with vectorized binary search (the ``sorted_lookup`` Pallas kernel): zero
pointer chasing, one streamed memory pass, perfectly batched.

Entries are sorted by a 32-bit mix ``h(vtype,key)``; equal-hash runs are
resolved by a short window scan (hash collisions within one shard are
~n^2/2^33).  The index has the same two-tier shape as edge lists: a compacted
sorted main array plus a small append delta, merged by the async compaction
task.  Entries carry MVCC intervals so index probes are snapshot reads.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_mod
from repro.core.addressing import NULL, TS_INF, StoreConfig
from repro.core.merge import merge_runs
from repro.core.store import GraphStore, visible, window_shard_major

_C1 = np.int32(-1640531527)   # 2654435769: Knuth multiplicative
_C2 = np.int32(-2048144789)   # murmur3 c1-ish odd constant
_WINDOW = 16                  # max same-hash run scanned on probe


def mix32(vtype, key):
    """Deterministic 32-bit mix of (vtype, key); int32 wrap-around arithmetic."""
    h = key * _C1
    h = h ^ (vtype * _C2)
    h = h ^ ((h >> 15) & 0x1FFFF)
    return h


def route(vtype, key, n_shards: int):
    """Index shard for a (vtype, key) pair."""
    h = mix32(vtype, key)
    return (h % n_shards + n_shards) % n_shards


def mix32_host(vtype: int, key: int) -> int:
    """Pure-python mirror of :func:`mix32` (no numpy overflow warnings)."""
    M = 0xFFFFFFFF
    h = ((key & M) * 2654435769) & M
    h ^= ((vtype & M) * 2246822507) & M
    h ^= (h >> 15) & 0x1FFFF
    return h - 2**32 if h >= 2**31 else h


def route_host(vtype: int, key: int, n_shards: int) -> int:
    return mix32_host(vtype, key) % n_shards


def lookup(store: GraphStore, cfg: StoreConfig, vtypes, keys, valid, read_ts,
           backend: backend_mod.Backend = backend_mod.REF,
           xd_win: int = None):
    """Batched primary-index probe at a snapshot (global-array mode).

    Returns (gids, found): gid of the live vertex for each (vtype, key), or
    NULL.  Two-tier: binary search of the sorted main index + linear scan of
    the delta.  Later (newer create_ts) entries win, so an uncompacted
    re-insert after delete resolves correctly.

    ``read_ts`` is a scalar snapshot, or a ``(Q,)`` vector of per-query
    snapshots (the multi-query planner fuses queries pinned at different
    MVCC timestamps into one probe wave).

    ``xd_win`` is a static per-shard window on the index-delta scan: the
    delta fills prefix-first per shard (host count mirrors are exact), so
    scanning ``[:W]`` of each shard block sees every live entry — slots
    beyond the fill hold ``xd_gid == NULL`` and can never match.  ``None``
    scans the full ``cap_idx_delta`` (identical results, more work); callers
    pass ``planner.index_window(db)``, pow2-rounded so program-cache keys
    only change when the fill band crosses a boundary.

    The pallas backend probes every shard block in one streamed pass of the
    sorted_lookup kernel (window-ranged compare-and-count); the ref backend
    binary-searches each query's block.  Both produce the same positions, so
    the window scan below is shared and results are bit-identical.
    """
    S, cap_x, cap_xd = cfg.n_shards, cfg.cap_idx, cfg.cap_idx_delta
    q = vtypes.shape[0]
    h = mix32(vtypes, keys)
    shard = route(vtypes, keys, S)
    base = shard * cap_x

    # main index is shard-major and sorted by mix32 hash (empty slots pad with
    # INT32_MAX); recompute the hash column identically to the compaction sort.
    ix_h = jnp.where(store.ix_gid >= 0, mix32(store.ix_vtype, store.ix_key),
                     jnp.int32(2**31 - 1))

    pos0 = backend_mod.searchsorted_blocked(ix_h, h, base, block=cap_x,
                                            backend=backend)
    best_g = jnp.full((q,), NULL, jnp.int32)
    best_ts = jnp.full((q,), -1, jnp.int32)
    for w in range(_WINDOW):
        p = jnp.minimum(pos0 + w, cap_x - 1)
        row = base + p
        hit = ((store.ix_gid[row] >= 0)
               & (store.ix_vtype[row] == vtypes)
               & (store.ix_key[row] == keys)
               & visible(store.ix_create[row], store.ix_delete[row],
                         read_ts))
        newer = hit & (store.ix_create[row] > best_ts)
        best_g = jnp.where(newer, store.ix_gid[row], best_g)
        best_ts = jnp.where(newer, store.ix_create[row], best_ts)
    g_main = jnp.where(valid, best_g, NULL)
    ts_main = jnp.where(valid, best_ts, -1)

    # delta scan (small): (Q, S*W) match matrix, newest visible entry wins
    W = cap_xd if xd_win is None else min(int(xd_win), cap_xd)
    xd_vt, xd_k, xd_g, xd_c, xd_d = window_shard_major(
        (store.xd_vtype, store.xd_key, store.xd_gid,
         store.xd_create, store.xd_delete), S, cap_xd, W)
    xd_shard = jnp.arange(S * W, dtype=jnp.int32) // W
    rts_row = read_ts[:, None] if jnp.ndim(read_ts) == 1 else read_ts
    m = (valid[:, None]
         & (xd_vt[None, :] == vtypes[:, None])
         & (xd_k[None, :] == keys[:, None])
         & (xd_shard[None, :] == shard[:, None])
         & (xd_g >= 0)[None, :]
         & visible(xd_c[None, :], xd_d[None, :], rts_row))
    ts_d = jnp.where(m, xd_c[None, :], -1)
    best_d = jnp.argmax(ts_d, axis=1)
    ts_delta = jnp.max(ts_d, axis=1)
    g_delta = jnp.where(ts_delta >= 0, xd_g[best_d], NULL)

    use_delta = ts_delta > ts_main
    gids = jnp.where(use_delta, g_delta, g_main)
    return gids, gids >= 0


@partial(jax.jit, static_argnames=("cfg",))
def compact_index(store: GraphStore, cfg: StoreConfig, gc_ts) -> dict:
    """Merge the index delta into the sorted main index (all shards).

    The main index is sorted by (mix32 hash, vtype, key), so the delta is
    merged in (``core/merge.py``) rather than re-sorting the whole index.
    Returns the replaced store fields only."""
    S, cap_x, cap_xd = cfg.n_shards, cfg.cap_idx, cfg.cap_idx_delta

    def hashed(vt, k, g):
        return jnp.where(g >= 0, mix32(vt, k), jnp.int32(2**31 - 1))

    def one(vt_m, k_m, g_m, c_m, d_m, vt_d, k_d, g_d, c_d, d_d):
        (_, vt, k), (g, c, d), n_live = merge_runs(
            (hashed(vt_m, k_m, g_m), vt_m, k_m), (g_m, c_m, d_m),
            (g_m >= 0) & (d_m > gc_ts),
            (hashed(vt_d, k_d, g_d), vt_d, k_d), (g_d, c_d, d_d),
            (g_d >= 0) & (d_d > gc_ts),
            cap_x, (2**31 - 1, TS_INF, TS_INF, NULL, TS_INF, TS_INF))
        return vt, k, g, c, d, n_live

    vt, k, g, c, d, n = jax.vmap(one)(
        store.ix_vtype.reshape(S, cap_x), store.ix_key.reshape(S, cap_x),
        store.ix_gid.reshape(S, cap_x), store.ix_create.reshape(S, cap_x),
        store.ix_delete.reshape(S, cap_x),
        store.xd_vtype.reshape(S, cap_xd), store.xd_key.reshape(S, cap_xd),
        store.xd_gid.reshape(S, cap_xd), store.xd_create.reshape(S, cap_xd),
        store.xd_delete.reshape(S, cap_xd))

    XD = S * cap_xd
    return dict(
        ix_vtype=vt.reshape(-1), ix_key=k.reshape(-1), ix_gid=g.reshape(-1),
        ix_create=c.reshape(-1), ix_delete=d.reshape(-1),
        ix_count=n.astype(jnp.int32),
        xd_vtype=jnp.full((XD,), TS_INF, jnp.int32),
        xd_key=jnp.full((XD,), TS_INF, jnp.int32),
        xd_gid=jnp.full((XD,), NULL, jnp.int32),
        xd_create=jnp.full((XD,), TS_INF, jnp.int32),
        xd_delete=jnp.full((XD,), TS_INF, jnp.int32),
        xd_count=jnp.zeros((S,), jnp.int32))
