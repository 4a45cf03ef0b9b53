"""Edge enumeration and compaction (the two-tier edge lists of §3.2).

Enumeration merges the compacted CSR (tier 1) with the append-only delta log
(tier 2) at a snapshot timestamp.  Expansion over a ragged frontier is the
vectorized form of A1's "edge enumeration" operator: every output position
finds its frontier item with a branchless ``searchsorted`` over the cumulative
degree — the same access pattern the ``edge_expand`` Pallas kernel implements
with scalar-prefetched CSR spans.

Compaction is the asynchronous-workflow analogue (§3.3): merge delta into CSR,
drop records dead before ``gc_ts`` (versions are only GC'd once no running
query can see them), and rebuild the per-slot offsets.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend as backend_mod
from repro.core.addressing import NULL, TS_INF, StoreConfig
from repro.core.merge import merge_runs
from repro.core.store import GraphStore, visible, window_shard_major

ANY_TYPE = jnp.int32(-1)

TILE = 128          # edge_expand lane width (the TPU vector-lane count)


def _tiled_csr_expand(qids, deg, start, pools, etype, read_ts, cap_out: int,
                      backend: backend_mod.Backend):
    """Kernel-backed CSR expansion, scattered back to the reference layout.

    The edge_expand kernel streams whole CSR spans tile-by-tile (scalar-
    prefetched span starts drive the DMA pipeline) instead of the reference
    path's one searchsorted + 4 gathers *per output slot*.  Its tile-padded
    output is consumed in place: the edge-visibility/type mask is evaluated
    directly on the tile buffers and surviving lanes are scattered into the
    dense (cap_out,) frontier buffer at exactly the position the reference
    path would have written, so downstream (dedup, checks, results) is
    bit-identical between backends.  Tile-padding therefore never inflates
    the dedup sort width — cap_tiles is sized so that any expansion the
    reference path accepts (total <= cap_out) also fits the tile plan.

    pools = (nbr, typ, create, delete); returns (out_q, out_n) of (cap_out,).
    """
    F = deg.shape[0]
    cap_tiles = F + (cap_out + TILE - 1) // TILE
    (nbr_t, typ_t, cre_t, del_t), item, tw, _ = backend_mod.expand_tiles(
        start, deg, pools, tile=TILE, cap_tiles=cap_tiles, backend=backend)
    item_c = jnp.minimum(item, F - 1)
    excl = jnp.cumsum(deg) - deg                      # dense span offsets
    lane = jnp.arange(TILE, dtype=jnp.int32)
    shape = (cap_tiles, TILE)
    nbr_t, typ_t = nbr_t.reshape(shape), typ_t.reshape(shape)
    cre_t, del_t = cre_t.reshape(shape), del_t.reshape(shape)
    # invalid lanes carry -1 in every pool: visible(-1, -1, ts) is False,
    # so the reference e_ok predicate needs no extra lane mask here
    e_ok = (visible(cre_t, del_t, read_ts)
            & ((etype < 0) | (typ_t == etype))
            & (nbr_t >= 0))
    pos = excl[item_c][:, None] + tw[:, None] * TILE + lane[None, :]
    pos = jnp.where(e_ok, pos, cap_out)               # drop masked lanes
    out_q = jnp.full((cap_out,), NULL, jnp.int32).at[pos.reshape(-1)].set(
        jnp.broadcast_to(qids[item_c][:, None], shape).reshape(-1),
        mode="drop")
    out_n = jnp.full((cap_out,), NULL, jnp.int32).at[pos.reshape(-1)].set(
        nbr_t.reshape(-1), mode="drop")
    return out_q, out_n


# ---------------------------------------------------------------------------
# Ragged CSR expansion
# ---------------------------------------------------------------------------

def _csr_arrays(store: GraphStore, direction: str):
    if direction == "out":
        return (store.oe_indptr, store.oe_dst, store.oe_type,
                store.oe_create, store.oe_delete)
    elif direction == "in":
        return (store.ie_indptr, store.ie_src, store.ie_type,
                store.ie_create, store.ie_delete)
    raise ValueError(direction)


def _delta_arrays(store: GraphStore, direction: str):
    if direction == "out":
        return (store.dl_slot, store.dl_nbr, store.dl_type,
                store.dl_create, store.dl_delete)
    elif direction == "in":
        return (store.il_slot, store.il_nbr, store.il_type,
                store.il_create, store.il_delete)
    raise ValueError(direction)


def expand(store: GraphStore, cfg: StoreConfig, qids, gids, valid, *,
           etype, direction: str, read_ts, cap_out: int,
           backend: backend_mod.Backend = backend_mod.REF,
           dwin: Optional[int] = None):
    """Enumerate edges of ``gids`` (global-array mode).

    Args:
      qids, gids, valid: frontier of shape (F,): query ids, vertex gids, mask.
      etype: int32 edge type to follow, or ANY_TYPE.
      direction: 'out' or 'in'.
      read_ts: snapshot timestamp.
      cap_out: static capacity for the CSR expansion segment.
      backend: read-path backend; the pallas path streams spans through the
        edge_expand kernel and produces bit-identical output (same layout).
      dwin: static per-shard delta-log window (``planner.delta_window``):
        only the filled prefix of each shard's log is matched against the
        frontier; None scans the whole log.

    Returns:
      (out_qids, out_nbr, out_valid, overflow): the expansion, shape
      (cap_out + F*S*dwin,), plus a bool overflow flag (fast-fail).
    """
    S, cap_v, cap_e = cfg.n_shards, cfg.cap_v, cfg.cap_e
    indptr, nbr, typ, ecre, edel = _csr_arrays(store, direction)

    safe_g = jnp.where(valid, gids, 0)
    shard = safe_g % S
    slot = safe_g // S
    iprow = shard * (cap_v + 1) + slot
    start = indptr[iprow] + shard * cap_e           # absolute pool offset
    deg = (indptr[iprow + 1] - indptr[iprow]) * valid

    cum = jnp.cumsum(deg)
    total = cum[-1] if deg.shape[0] > 0 else jnp.int32(0)
    overflow = total > cap_out

    if backend.is_pallas:
        out_q, out_n = _tiled_csr_expand(qids, deg, start,
                                         (nbr, typ, ecre, edel), etype,
                                         read_ts, cap_out, backend)
    else:
        k = jnp.arange(cap_out, dtype=jnp.int32)
        item = jnp.searchsorted(cum, k, side="right").astype(jnp.int32)
        item_c = jnp.minimum(item, deg.shape[0] - 1)
        base = cum[item_c] - deg[item_c]
        epos = start[item_c] + (k - base)
        in_range = k < total
        epos = jnp.where(in_range, epos, 0)

        e_ok = (in_range
                & visible(ecre[epos], edel[epos], read_ts)
                & ((etype < 0) | (typ[epos] == etype))
                & (nbr[epos] >= 0))
        out_q = jnp.where(e_ok, qids[item_c], NULL)
        out_n = jnp.where(e_ok, nbr[epos], NULL)

    # ---- tier 2: delta-log merge (recent, not yet compacted edges) --------
    W = cfg.cap_delta if dwin is None else min(int(dwin), cfg.cap_delta)
    dslot, dnbr, dtyp, dts, ddel = window_shard_major(
        _delta_arrays(store, direction), S, cfg.cap_delta, W)
    D = dslot.shape[0]
    d_shard = jnp.arange(D, dtype=jnp.int32) // W
    d_gid = dslot * S + d_shard                       # gid of the delta's owner
    # match matrix: frontier item x delta entry
    m = (valid[:, None]
         & (d_gid[None, :] == safe_g[:, None])
         & visible(dts, ddel, read_ts)[None, :]
         & ((etype < 0) | (dtyp[None, :] == etype))
         & (dnbr[None, :] >= 0))
    dq = jnp.where(m, qids[:, None], NULL).reshape(-1)
    dn = jnp.where(m, dnbr[None, :] + jnp.zeros_like(qids)[:, None], NULL).reshape(-1)

    out_qids = jnp.concatenate([out_q, dq])
    out_nbr = jnp.concatenate([out_n, dn])
    return out_qids, out_nbr, out_nbr >= 0, overflow


def degrees(store: GraphStore, cfg: StoreConfig, gids, valid, *, etype,
            direction: str, read_ts):
    """Visible degree of each frontier vertex (CSR span + delta matches)."""
    S, cap_v, cap_e = cfg.n_shards, cfg.cap_v, cfg.cap_e
    indptr, nbr, typ, ecre, edel = _csr_arrays(store, direction)
    safe_g = jnp.where(valid, gids, 0)
    shard, slot = safe_g % S, safe_g // S
    iprow = shard * (cap_v + 1) + slot
    start, end = indptr[iprow], indptr[iprow + 1]
    # CSR spans can contain dead or other-type edges; count exactly by scanning
    # a bounded window is avoided here — this helper reports the raw span size
    # (used for capacity planning), not the filtered degree.
    return (end - start) * valid


# ---------------------------------------------------------------------------
# Compaction (async workflow, §3.3)
# ---------------------------------------------------------------------------

def _compact_one_shard(slot_c, nbr_c, typ_c, cre_c, del_c,      # CSR (cap_e,)
                       slot_d, nbr_d, typ_d, ts_d, del_d,       # delta (cap_d,)
                       gc_ts, cap_v: int):
    """Merge one shard's CSR pool with its delta log; returns new CSR arrays.

    Entries dead at ``gc_ts`` are dropped; survivors stay sorted by
    (slot, etype, nbr) so future enumerations are contiguous.  The CSR is
    already in that order, so the delta is merged in (``core/merge.py``)
    instead of re-sorting the whole pool.
    """
    cap_e = nbr_c.shape[0]
    (new_slot, new_typ, new_nbr), (new_cre, new_del), n_live = merge_runs(
        (slot_c, typ_c, nbr_c), (cre_c, del_c),
        (nbr_c >= 0) & (del_c > gc_ts),
        (slot_d, typ_d, nbr_d), (ts_d, del_d),
        (nbr_d >= 0) & (del_d > gc_ts),
        cap_e, (cap_v, NULL, NULL, TS_INF, TS_INF))
    overflow = n_live > cap_e
    counts = jax.ops.segment_sum((new_slot < cap_v).astype(jnp.int32),
                                 jnp.minimum(new_slot, cap_v),
                                 num_segments=cap_v + 1)[:cap_v]
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)])
    return indptr, new_nbr, new_typ, new_cre, new_del, overflow


def _slot_of_pool(indptr, cap_e):
    """Recover per-entry slot from an indptr (entries below indptr[-1])."""
    k = jnp.arange(cap_e, dtype=jnp.int32)
    return jnp.searchsorted(indptr[1:], k, side="right").astype(jnp.int32)


@partial(jax.jit, static_argnames=("cfg",))
def compact(store: GraphStore, cfg: StoreConfig, gc_ts) -> dict:
    """Compact both edge CSRs (all shards, vmapped): returns the replaced
    store fields — the compacted CSRs and emptied delta logs — only, so the
    untouched arrays are neither copied nor held twice."""
    S, cap_v, cap_e, cap_d = cfg.n_shards, cfg.cap_v, cfg.cap_e, cfg.cap_delta

    def per_direction(indptr, nbr, typ, cre, dele, dslot, dnbr, dtyp, dts, ddel):
        ip = indptr.reshape(S, cap_v + 1)
        slot_c = jax.vmap(_slot_of_pool, in_axes=(0, None))(ip, cap_e)
        fn = jax.vmap(partial(_compact_one_shard, gc_ts=gc_ts, cap_v=cap_v))
        nip, nnbr, ntyp, ncre, ndel, ovf = fn(
            slot_c, nbr.reshape(S, cap_e), typ.reshape(S, cap_e),
            cre.reshape(S, cap_e), dele.reshape(S, cap_e),
            dslot.reshape(S, cap_d), dnbr.reshape(S, cap_d),
            dtyp.reshape(S, cap_d), dts.reshape(S, cap_d),
            ddel.reshape(S, cap_d))
        return (nip.reshape(-1), nnbr.reshape(-1), ntyp.reshape(-1),
                ncre.reshape(-1), ndel.reshape(-1), jnp.any(ovf))

    o_ip, o_nbr, o_typ, o_cre, o_del, _ = per_direction(
        store.oe_indptr, store.oe_dst, store.oe_type, store.oe_create,
        store.oe_delete, store.dl_slot, store.dl_nbr, store.dl_type,
        store.dl_create, store.dl_delete)
    i_ip, i_nbr, i_typ, i_cre, i_del, _ = per_direction(
        store.ie_indptr, store.ie_src, store.ie_type, store.ie_create,
        store.ie_delete, store.il_slot, store.il_nbr, store.il_type,
        store.il_create, store.il_delete)

    D = store.dl_slot.shape[0]
    empty_d = dict(
        dl_slot=jnp.full((D,), NULL), dl_nbr=jnp.full((D,), NULL),
        dl_type=jnp.full((D,), NULL), dl_create=jnp.full((D,), TS_INF),
        dl_delete=jnp.full((D,), TS_INF), dl_count=jnp.zeros((S,), jnp.int32),
        il_slot=jnp.full((D,), NULL), il_nbr=jnp.full((D,), NULL),
        il_type=jnp.full((D,), NULL), il_create=jnp.full((D,), TS_INF),
        il_delete=jnp.full((D,), TS_INF), il_count=jnp.zeros((S,), jnp.int32),
    )

    return dict(oe_indptr=o_ip, oe_dst=o_nbr, oe_type=o_typ,
                oe_create=o_cre, oe_delete=o_del,
                ie_indptr=i_ip, ie_src=i_nbr, ie_type=i_typ,
                ie_create=i_cre, ie_delete=i_del, **empty_d)
