"""GraphStore: the sharded in-memory graph storage (FaRM + A1 layout, §2-3).

Layout decisions mirror the paper:

* A vertex is a *header* (type, key, MVCC timestamps, degree bookkeeping) plus
  schematized *data* columns.  Header and data live in the same shard — the
  paper's locality between header/data/edge-list within one region is
  structural here: everything keyed by the vertex's local slot.
* Edges are *half-edges* stored on both endpoints (outgoing CSR on the source
  shard, incoming CSR on the destination shard), so vertex deletion can always
  find and retire the opposite half (no dangling edges, §3.2).
* The two-tier edge list (inline array -> global BTree) becomes a two-tier
  TPU structure: a compacted CSR pool (tier 1, bulk of the data, sorted by
  (slot, etype, dst)) plus an append-only *delta log* (tier 2) absorbing
  recent mutations.  An asynchronous compaction task merges delta -> CSR,
  mirroring A1's asynchronous workflows and geometric edge-list growth.
* Every record carries (create_ts, delete_ts] MVCC interval timestamps from
  the FaRMv2 global clock; snapshot reads at ``read_ts`` see a record iff
  ``create_ts <= read_ts < delete_ts``.  Data updates keep a cur/prev version
  pair (FaRMv2 keeps old versions until readers drain; two versions bound the
  in-flight snapshot window, see DESIGN.md §2).

All arrays are flat and shard-major: row ``shard * cap + slot`` so that a
``PartitionSpec(('data','model'))`` on axis 0 puts each shard's block on one
device, and inside ``shard_map`` each device sees exactly its local block.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.addressing import NULL, TS_INF, StoreConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GraphStore:
    """Device-resident graph storage.  A pure pytree of arrays."""

    # -- vertex headers -----------------------------------------------------
    vtype: jax.Array      # (S*cap_v,)  i32, NULL = empty slot
    vkey: jax.Array       # (S*cap_v,)  i32 primary key
    v_create: jax.Array   # (S*cap_v,)  i32 MVCC create ts
    v_delete: jax.Array   # (S*cap_v,)  i32 MVCC delete ts (TS_INF = live)
    v_edgever: jax.Array  # (S*cap_v,)  i32 edge-list object version (FaRM
                          #             versions the edge list separately)
    # -- vertex data (schematized columns, Bond analogue) --------------------
    vdata_f: jax.Array    # (S*cap_v, d_f32) f32  current version
    vdata_i: jax.Array    # (S*cap_v, d_i32) i32  current version
    vdata_ts: jax.Array   # (S*cap_v,)  i32 ts of current data version
    vprev_f: jax.Array    # (S*cap_v, d_f32) f32  previous version
    vprev_i: jax.Array    # (S*cap_v, d_i32) i32  previous version
    vprev_ts: jax.Array   # (S*cap_v,)  i32 ts of previous data version
    # -- outgoing half-edges: compacted CSR (tier 1) -------------------------
    oe_indptr: jax.Array  # (S*(cap_v+1),) i32 per-shard CSR offsets into pool
    oe_dst: jax.Array     # (S*cap_e,) i32 destination gid
    oe_type: jax.Array    # (S*cap_e,) i32 edge type
    oe_create: jax.Array  # (S*cap_e,) i32
    oe_delete: jax.Array  # (S*cap_e,) i32
    oe_data: jax.Array    # (S*cap_e, d_ef32) f32 edge attributes
    # -- incoming half-edges: compacted CSR (tier 1) -------------------------
    ie_indptr: jax.Array  # (S*(cap_v+1),) i32
    ie_src: jax.Array     # (S*cap_e,) i32 source gid
    ie_type: jax.Array    # (S*cap_e,) i32
    ie_create: jax.Array  # (S*cap_e,) i32
    ie_delete: jax.Array  # (S*cap_e,) i32
    # -- edge delta logs (tier 2, append-only until compaction) --------------
    dl_slot: jax.Array    # (S*cap_delta,) i32 local src slot (out log)
    dl_nbr: jax.Array     # (S*cap_delta,) i32 neighbor gid
    dl_type: jax.Array    # (S*cap_delta,) i32
    dl_create: jax.Array  # (S*cap_delta,) i32 MVCC create ts
    dl_delete: jax.Array  # (S*cap_delta,) i32 MVCC delete ts (TS_INF live)
    dl_count: jax.Array   # (S,) i32 entries used per shard
    il_slot: jax.Array    # (S*cap_delta,) i32 local dst slot (in log)
    il_nbr: jax.Array     # (S*cap_delta,) i32 source gid
    il_type: jax.Array    # (S*cap_delta,) i32
    il_create: jax.Array  # (S*cap_delta,) i32
    il_delete: jax.Array  # (S*cap_delta,) i32
    il_count: jax.Array   # (S,) i32
    # -- primary index: sorted (vtype, key) -> gid per shard (BTree analogue)
    ix_vtype: jax.Array   # (S*cap_idx,) i32 sorted lexicographically
    ix_key: jax.Array     # (S*cap_idx,) i32
    ix_gid: jax.Array     # (S*cap_idx,) i32
    ix_create: jax.Array  # (S*cap_idx,) i32
    ix_delete: jax.Array  # (S*cap_idx,) i32
    ix_count: jax.Array   # (S,) i32
    # -- primary index delta --------------------------------------------------
    xd_vtype: jax.Array   # (S*cap_idx_delta,) i32
    xd_key: jax.Array     # (S*cap_idx_delta,) i32
    xd_gid: jax.Array     # (S*cap_idx_delta,) i32
    xd_create: jax.Array  # (S*cap_idx_delta,) i32
    xd_delete: jax.Array  # (S*cap_idx_delta,) i32
    xd_count: jax.Array   # (S,) i32
    # -- vector index: flat per-type embedding entries (core/vindex.py) ------
    vx_gid: jax.Array     # (S*cap_vec,) i32 entry's vertex gid (NULL = empty)
    vx_vtype: jax.Array   # (S*cap_vec,) i32 entry's vertex type
    vx_create: jax.Array  # (S*cap_vec,) i32 MVCC create ts
    vx_delete: jax.Array  # (S*cap_vec,) i32 MVCC delete ts (TS_INF = live)
    vx_emb: jax.Array     # (S*cap_vec, d_f32) f32 embedding payload
    vx_count: jax.Array   # (S,) i32 entries used per shard (prefix fill)

    def nbytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(self))


def _full(shape, fill, dtype=jnp.int32):
    return jnp.full(shape, fill, dtype=dtype)


def make_store(cfg: StoreConfig, sharding=None) -> GraphStore:
    """Allocate an empty store (all device arrays).

    ``sharding`` (e.g. ``NamedSharding(mesh, P(axes))``) places every
    shard-major array across the mesh as it is created, so no single
    device ever holds the whole store."""
    if sharding is not None:
        return jax.jit(partial(make_store, cfg), out_shardings=sharding)()
    S = cfg.n_shards
    V, E, D, X, XD = (S * cfg.cap_v, S * cfg.cap_e, S * cfg.cap_delta,
                      S * cfg.cap_idx, S * cfg.cap_idx_delta)
    VX = S * cfg.cap_vec
    P = S * (cfg.cap_v + 1)
    return GraphStore(
        vtype=_full(V, NULL), vkey=_full(V, 0),
        v_create=_full(V, TS_INF), v_delete=_full(V, TS_INF),
        v_edgever=_full(V, 0),
        vdata_f=jnp.zeros((V, cfg.d_f32), jnp.float32),
        vdata_i=jnp.zeros((V, cfg.d_i32), jnp.int32),
        vdata_ts=_full(V, 0),
        vprev_f=jnp.zeros((V, cfg.d_f32), jnp.float32),
        vprev_i=jnp.zeros((V, cfg.d_i32), jnp.int32),
        vprev_ts=_full(V, 0),
        oe_indptr=_full(P, 0), oe_dst=_full(E, NULL), oe_type=_full(E, NULL),
        oe_create=_full(E, TS_INF), oe_delete=_full(E, TS_INF),
        oe_data=jnp.zeros((E, cfg.d_ef32), jnp.float32),
        ie_indptr=_full(P, 0), ie_src=_full(E, NULL), ie_type=_full(E, NULL),
        ie_create=_full(E, TS_INF), ie_delete=_full(E, TS_INF),
        dl_slot=_full(D, NULL), dl_nbr=_full(D, NULL), dl_type=_full(D, NULL),
        dl_create=_full(D, TS_INF), dl_delete=_full(D, TS_INF), dl_count=_full(S, 0),
        il_slot=_full(D, NULL), il_nbr=_full(D, NULL), il_type=_full(D, NULL),
        il_create=_full(D, TS_INF), il_delete=_full(D, TS_INF), il_count=_full(S, 0),
        ix_vtype=_full(X, TS_INF), ix_key=_full(X, TS_INF), ix_gid=_full(X, NULL),
        ix_create=_full(X, TS_INF), ix_delete=_full(X, TS_INF), ix_count=_full(S, 0),
        xd_vtype=_full(XD, TS_INF), xd_key=_full(XD, TS_INF), xd_gid=_full(XD, NULL),
        xd_create=_full(XD, TS_INF), xd_delete=_full(XD, TS_INF), xd_count=_full(S, 0),
        vx_gid=_full(VX, NULL), vx_vtype=_full(VX, NULL),
        vx_create=_full(VX, TS_INF), vx_delete=_full(VX, TS_INF),
        vx_emb=jnp.zeros((VX, cfg.d_f32), jnp.float32), vx_count=_full(S, 0),
    )


def make_store_shapes(cfg: StoreConfig) -> GraphStore:
    """ShapeDtypeStruct mirror of :func:`make_store` (dry-run, no allocation)."""
    S = cfg.n_shards
    V, E, D, X, XD = (S * cfg.cap_v, S * cfg.cap_e, S * cfg.cap_delta,
                      S * cfg.cap_idx, S * cfg.cap_idx_delta)
    VX = S * cfg.cap_vec
    P = S * (cfg.cap_v + 1)
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    return GraphStore(
        vtype=sds((V,), i32), vkey=sds((V,), i32),
        v_create=sds((V,), i32), v_delete=sds((V,), i32),
        v_edgever=sds((V,), i32),
        vdata_f=sds((V, cfg.d_f32), f32), vdata_i=sds((V, cfg.d_i32), i32),
        vdata_ts=sds((V,), i32),
        vprev_f=sds((V, cfg.d_f32), f32), vprev_i=sds((V, cfg.d_i32), i32),
        vprev_ts=sds((V,), i32),
        oe_indptr=sds((P,), i32), oe_dst=sds((E,), i32), oe_type=sds((E,), i32),
        oe_create=sds((E,), i32), oe_delete=sds((E,), i32),
        oe_data=sds((E, cfg.d_ef32), f32),
        ie_indptr=sds((P,), i32), ie_src=sds((E,), i32), ie_type=sds((E,), i32),
        ie_create=sds((E,), i32), ie_delete=sds((E,), i32),
        dl_slot=sds((D,), i32), dl_nbr=sds((D,), i32), dl_type=sds((D,), i32),
        dl_create=sds((D,), i32), dl_delete=sds((D,), i32), dl_count=sds((S,), i32),
        il_slot=sds((D,), i32), il_nbr=sds((D,), i32), il_type=sds((D,), i32),
        il_create=sds((D,), i32), il_delete=sds((D,), i32), il_count=sds((S,), i32),
        ix_vtype=sds((X,), i32), ix_key=sds((X,), i32), ix_gid=sds((X,), i32),
        ix_create=sds((X,), i32), ix_delete=sds((X,), i32), ix_count=sds((S,), i32),
        xd_vtype=sds((XD,), i32), xd_key=sds((XD,), i32), xd_gid=sds((XD,), i32),
        xd_create=sds((XD,), i32), xd_delete=sds((XD,), i32), xd_count=sds((S,), i32),
        vx_gid=sds((VX,), i32), vx_vtype=sds((VX,), i32),
        vx_create=sds((VX,), i32), vx_delete=sds((VX,), i32),
        vx_emb=sds((VX, cfg.d_f32), f32), vx_count=sds((S,), i32),
    )


# ---------------------------------------------------------------------------
# Visibility & gathers (snapshot reads, §5.2)
# ---------------------------------------------------------------------------

def visible(create_ts, delete_ts, read_ts):
    """MVCC visibility: created at-or-before the snapshot, not yet deleted."""
    return (create_ts <= read_ts) & (read_ts < delete_ts)


def window_shard_major(arrs, S: int, cap: int, W: int):
    """Slice shard-major ``(S*cap,)`` delta arrays to their ``(S*W,)``
    fill-window prefix.

    All delta logs (edge ``dl_*``/``il_*``, index ``xd_*``) fill
    prefix-first per shard with exact host count mirrors, so scanning
    ``[:W]`` of each shard block sees every live entry — the invariant
    behind ``planner.delta_window`` / ``planner.index_window``."""
    return tuple(a.reshape(S, cap)[:, :W].reshape(-1) for a in arrs)


def gather_headers(store: GraphStore, cfg: StoreConfig, gids, read_ts):
    """Read vertex headers for an array of gids at snapshot ``read_ts``.

    Returns (vtype, key, alive) with NULL/False for invalid or invisible ids.
    Equivalent of the paper's single one-sided RDMA read of a vertex header.
    """
    ok = gids >= 0
    rows = cfg.row_of_gid(jnp.where(ok, gids, 0))
    vt = store.vtype[rows]
    alive = ok & visible(store.v_create[rows], store.v_delete[rows], read_ts)
    return jnp.where(alive, vt, NULL), jnp.where(alive, store.vkey[rows], NULL), alive


def gather_data(store: GraphStore, cfg: StoreConfig, gids, read_ts):
    """Read vertex data columns at a snapshot (second RDMA read of the pair).

    Chooses the current or previous data version by timestamp.
    """
    ok = gids >= 0
    rows = cfg.row_of_gid(jnp.where(ok, gids, 0))
    use_cur = store.vdata_ts[rows] <= read_ts
    f = jnp.where(use_cur[:, None], store.vdata_f[rows], store.vprev_f[rows])
    i = jnp.where(use_cur[:, None], store.vdata_i[rows], store.vprev_i[rows])
    alive = ok & visible(store.v_create[rows], store.v_delete[rows], read_ts)
    return f * alive[:, None], i * alive[:, None], alive


def local_block(arr: jax.Array, shard: int, per_shard: int):
    """Host-side helper: slice one shard's block out of a flat array."""
    return arr[shard * per_shard:(shard + 1) * per_shard]


@partial(jax.jit, static_argnames=("cap",))
def replay_log_tail(dst, src, w, n, *, cap: int):
    """Copy each shard's log tail ``[w_s, n_s)`` from ``src`` onto ``dst``'s
    prefix ``[0, n_s - w_s)``.  Flat shard-major ``(S*cap,)`` arrays.

    The compaction-handoff primitive (§2.2 concurrent GC): ``dst`` is the
    shadow store's freshly emptied delta log, ``src`` the live log, ``w``
    the per-shard fill at shadow-build time and ``n`` the fill now.
    Entries appended while the background build ran are replayed onto the
    shadow so the merged store loses nothing; positions past the tail keep
    ``dst``'s empty-log fill, preserving the prefix-fill invariant behind
    ``planner.delta_window``.
    """
    S = w.shape[0]
    OOB = jnp.int32(2**31 - 1)
    k = jnp.arange(cap, dtype=jnp.int32)[None, :]
    base = (jnp.arange(S, dtype=jnp.int32) * cap)[:, None]
    src_pos = w[:, None] + k
    valid = src_pos < n[:, None]
    vals = src[(base + jnp.where(valid, src_pos, 0)).reshape(-1)]
    dst_rows = jnp.where(valid, base + k, OOB).reshape(-1)
    return dst.at[dst_rows].set(vals, mode="drop")
