"""Vector index: flat per-type embedding entries (the `Nearest` substrate).

A1 at Bing sat next to ranking infrastructure; the hybrid "k-NN seeds ->
multi-hop expand" workload (ROADMAP item 2) needs the vector half to live
*inside* the store so it rides the same MVCC snapshots, mutation waves, and
compaction lifecycle as everything else — the GDI argument (PAPERS.md)
against bolting on a sidecar ANN service.

Layout (``store.vx_*``): a flat shard-major ``(S*cap_vec,)`` entry pool.
Each entry is ``(gid, vtype, create_ts, delete_ts, emb)`` where ``emb`` is
the vertex's full f32 payload row at write time.  Entries live on the
vertex's owning shard (``gid % S``) and fill prefix-first per shard with an
exact host count mirror (``db.vx_count``) — the same prefix-fill invariant
as the delta logs, so the planner scans only the ``vindex_window`` prefix.

Maintenance is *versioned, not in-place* (d-HNSW's immutable segments, here
as MVCC intervals): a payload update tombstones the old entry at the wave's
``ts`` and appends a fresh one at the same ``ts``, so at any snapshot at
most one entry per gid is visible and `Nearest` at an old ``read_ts`` still
sees the old vector.  Deleted vertices age out at ``gc_ts`` when the fold
(:func:`run_compaction`) prefix-compacts each shard — wired into the PR 6
background-compaction lifecycle as the ``"vindex"`` kind.

Registration is per vertex type (``GraphDB.vector_index(name)``); vertices
alive at registration are backfilled with ``create_ts = max(v_create,
vdata_ts)``, so snapshots older than a vertex's last payload write do not
see its (backfilled) vector — the documented backfill caveat.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.addressing import NULL, TS_INF, StoreConfig
from repro.core.store import GraphStore, window_shard_major

I32MAX = 2**31 - 1


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _bucket(n: int) -> int:
    """Pad counts to pow2 buckets so the scatter jit-caches a few shapes."""
    return _pow2ceil(n) if n else 0


# ---------------------------------------------------------------------------
# registration + backfill
# ---------------------------------------------------------------------------

def register(db, vtype_name: str):
    """Register a vertex type for vector indexing; backfill live vertices."""
    vt = db.vt(vtype_name)
    if db.cfg.cap_vec <= 0:
        raise ValueError("vector index disabled: StoreConfig.cap_vec == 0")
    if vt.type_id in db._vindexed:
        return vt
    db._vindexed.add(vt.type_id)
    _backfill(db, vt.type_id)
    return vt


def _backfill(db, vtid: int) -> None:
    cfg = db.cfg
    vtypes = np.asarray(db.store.vtype)
    cr = np.asarray(db.store.v_create)
    dl = np.asarray(db.store.v_delete)
    dts = np.asarray(db.store.vdata_ts)
    now = db.clock
    rows = np.where((vtypes == vtid) & (cr <= now) & (now < dl))[0]
    # only the indexed rows' payloads cross to the host
    vdf = np.asarray(db.store.vdata_f[jnp.asarray(rows, jnp.int32)])
    appends = []
    for row, emb in zip(rows, vdf):
        shard, slot = int(row) // cfg.cap_v, int(row) % cfg.cap_v
        gid = slot * cfg.n_shards + shard
        pos = _alloc(db, gid)
        db._vx_pos[gid] = (pos, vtid)
        appends.append((pos, gid, vtid, int(max(cr[row], dts[row])), emb))
    _device_apply(db, appends, [], 0)


def _alloc(db, gid: int) -> int:
    """Claim the next prefix position on the gid's owning shard."""
    s = int(gid) % db.cfg.n_shards
    p = int(db.vx_count[s])
    if p >= db.cfg.cap_vec:
        from repro.core.writes import CapacityError
        raise CapacityError(f"vector index full on shard {s}")
    db.vx_count[s] = p + 1
    return s * db.cfg.cap_vec + p


# ---------------------------------------------------------------------------
# write-wave maintenance (called from writes.commit_wave per applied chunk)
# ---------------------------------------------------------------------------

def wave_demand(db, txns) -> np.ndarray:
    """Exact per-shard append demand of a winner batch (capacity backstop).

    Creates of indexed types and payload updates of indexed vertices each
    append one entry (updates additionally tombstone, which frees nothing
    until the fold).  Same-batch created-then-updated gids are tracked so
    the count stays exact across chunks.
    """
    S = db.cfg.n_shards
    need = np.zeros(S, np.int64)
    fresh: set = set()
    for t in txns:
        for gid, vtid, *_ in t.create_v:
            if vtid in db._vindexed:
                need[int(gid) % S] += 1
                fresh.add(gid)
        for gid, _f, _i in t.update_v:
            if gid in db._vx_pos or gid in fresh:
                need[int(gid) % S] += 1
    return need


def apply_wave(db, chunk, ts: int) -> None:
    """Fold one applied mutation chunk into the vector index at ``ts``.

    Runs after the chunk's store-apply program: create of an indexed type
    appends an entry; update of an indexed vertex tombstones its entry at
    ``ts`` and appends the new payload at ``ts`` (disjoint MVCC intervals —
    at most one entry per gid visible at any snapshot); delete tombstones.
    """
    if not db._vindexed:
        return
    appends = []   # (pos, gid, vtid, create_ts, emb row)
    tombs = []     # positions whose delete_ts becomes `ts`
    for t in chunk:
        for gid, vtid, _key, f, _i in t.create_v:
            if vtid in db._vindexed:
                pos = _alloc(db, gid)
                db._vx_pos[gid] = (pos, vtid)
                appends.append((pos, gid, vtid, ts, f))
        for gid, f, _i in t.update_v:
            ent = db._vx_pos.get(gid)
            if ent is not None:
                tombs.append(ent[0])
                pos = _alloc(db, gid)
                db._vx_pos[gid] = (pos, ent[1])
                appends.append((pos, gid, ent[1], ts, f))
        for gid, *_ in t.delete_v:
            ent = db._vx_pos.pop(gid, None)
            if ent is not None:
                tombs.append(ent[0])
    _device_apply(db, appends, tombs, ts)


def _device_apply(db, appends, tombs, ts: int) -> None:
    if not appends and not tombs:
        return
    d = db.cfg.d_f32
    A, T = _bucket(len(appends)), _bucket(len(tombs))
    a_pos = np.full(A, I32MAX, np.int32)
    a_gid = np.zeros(A, np.int32)
    a_vt = np.zeros(A, np.int32)
    a_ts = np.zeros(A, np.int32)
    a_emb = np.zeros((A, d), np.float32)
    for j, (pos, gid, vtid, cts, f) in enumerate(appends):
        a_pos[j], a_gid[j], a_vt[j], a_ts[j] = pos, gid, vtid, cts
        a_emb[j] = np.asarray(f, np.float32)
    t_pos = np.full(T, I32MAX, np.int32)
    for j, pos in enumerate(tombs):
        t_pos[j] = pos
    g, vt, cr, dl, emb = _scatter(
        db.store.vx_gid, db.store.vx_vtype, db.store.vx_create,
        db.store.vx_delete, db.store.vx_emb,
        jnp.asarray(a_pos), jnp.asarray(a_gid), jnp.asarray(a_vt),
        jnp.asarray(a_ts), jnp.asarray(a_emb),
        jnp.asarray(t_pos), jnp.int32(ts))
    db.store = dataclasses.replace(
        db.store, vx_gid=g, vx_vtype=vt, vx_create=cr, vx_delete=dl,
        vx_emb=emb, vx_count=jnp.asarray(db.vx_count, jnp.int32))


@jax.jit
def _scatter(vx_gid, vx_vtype, vx_create, vx_delete, vx_emb,
             a_pos, a_gid, a_vt, a_ts, a_emb, t_pos, t_ts):
    # tombstones first; append positions are fresh (disjoint), pads drop
    vx_delete = vx_delete.at[t_pos].set(t_ts, mode="drop")
    vx_gid = vx_gid.at[a_pos].set(a_gid, mode="drop")
    vx_vtype = vx_vtype.at[a_pos].set(a_vt, mode="drop")
    vx_create = vx_create.at[a_pos].set(a_ts, mode="drop")
    vx_delete = vx_delete.at[a_pos].set(TS_INF, mode="drop")
    vx_emb = vx_emb.at[a_pos].set(a_emb, mode="drop")
    return vx_gid, vx_vtype, vx_create, vx_delete, vx_emb


# ---------------------------------------------------------------------------
# compaction fold (the "vindex" kind of the background lifecycle)
# ---------------------------------------------------------------------------

def run_compaction(db) -> None:
    """Fold: drop entries dead at ``gc_ts`` (or orphaned), stable
    prefix-compact each shard, rebuild the host position map.

    Host-side numpy over the small ``vx_*`` arrays — the fold is rare
    (watermark- or backstop-triggered) and synchronous at handoff, so no
    shadow/epoch machinery is needed: entry *positions* are referenced only
    by ``db._vx_pos``, which is rebuilt here.
    """
    cfg = db.cfg
    if cfg.cap_vec <= 0:
        return
    gc = db.gc_ts()
    S, cap = cfg.n_shards, cfg.cap_vec
    g = np.asarray(db.store.vx_gid).reshape(S, cap)
    vt = np.asarray(db.store.vx_vtype).reshape(S, cap)
    cr = np.asarray(db.store.vx_create).reshape(S, cap)
    dl = np.asarray(db.store.vx_delete).reshape(S, cap)
    emb = np.asarray(db.store.vx_emb).reshape(S, cap, -1)
    ng = np.full_like(g, NULL)
    nvt = np.full_like(vt, NULL)
    ncr = np.full_like(cr, TS_INF)
    ndl = np.full_like(dl, TS_INF)
    nemb = np.zeros_like(emb)
    pos = {}
    for s in range(S):
        keep = np.where((g[s] >= 0) & (dl[s] > gc))[0]
        n = len(keep)
        ng[s, :n] = g[s, keep]
        nvt[s, :n] = vt[s, keep]
        ncr[s, :n] = cr[s, keep]
        ndl[s, :n] = dl[s, keep]
        nemb[s, :n] = emb[s, keep]
        db.vx_count[s] = n
        for j, src in enumerate(keep):
            if dl[s, src] == TS_INF:
                pos[int(g[s, src])] = (s * cap + j, int(vt[s, src]))
    db._vx_pos = pos
    db.store = dataclasses.replace(
        db.store,
        vx_gid=jnp.asarray(ng.reshape(-1)),
        vx_vtype=jnp.asarray(nvt.reshape(-1)),
        vx_create=jnp.asarray(ncr.reshape(-1)),
        vx_delete=jnp.asarray(ndl.reshape(-1)),
        vx_emb=jnp.asarray(nemb.reshape(S * cap, -1)),
        vx_count=jnp.asarray(db.vx_count, jnp.int32))
    db.stats["vindex_compactions"] += 1


# ---------------------------------------------------------------------------
# read-side windowing (planner probe wave)
# ---------------------------------------------------------------------------

def vindex_window(db) -> int:
    """Pow2 prefix window covering every live entry (static cache key)."""
    if not db._vindexed:
        return 0
    fill = int(db.vx_count.max(initial=0))
    return min(_pow2ceil(max(fill, 1)), db.cfg.cap_vec)


def window_arrays(store: GraphStore, cfg: StoreConfig, W: int):
    """Slice the vx_* pool to its ``(S*W,)`` fill-window prefix."""
    S, cap = cfg.n_shards, cfg.cap_vec
    g, vt, cr, dl = window_shard_major(
        (store.vx_gid, store.vx_vtype, store.vx_create, store.vx_delete),
        S, cap, W)
    emb = store.vx_emb.reshape(S, cap, -1)[:, :W].reshape(S * W, -1)
    return g, vt, cr, dl, emb
