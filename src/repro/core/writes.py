"""Write path: batched mutation waves behind ``GraphDB.write()`` (§3, §2.2).

The write analogue of the read planner.  Reads got wave fusion in PRs 3-5;
this module gives mutations the same treatment:

* **Typed mutation-op records** (:class:`CreateVertex` ... :class:`DeleteEdge`)
  are the write-side IR.  ``GraphDB.write(ops)`` is the single entry point —
  the historical per-op methods (``create_vertex`` et al.) are thin staging
  wrappers over these records, and ``commit``/``commit_many`` are
  DeprecationWarning shims.  Per-op results (gid / status / abort reason)
  come back positionally in a :class:`WriteResult`, mirroring ``QueryResult``.

* **One OCC validation wave** per commit batch: every transaction's read set
  is concatenated, padded to a pow2 bucket, and validated by a single jitted
  gather (``last_write_ts`` over per-read snapshot timestamps) instead of the
  historical chunked host loop.  §3's first-wins intra-batch resolution is
  unchanged.

* **One fused apply program per mutation-shape group**: the op arrays of a
  winner chunk are padded to pow2 buckets per op kind, and the jitted
  ``apply_batch`` trace is cached on that canonical shape tuple — LRU-bounded
  with observable :data:`CACHE_STATS`, exactly like the read planner's
  program cache.  A steady write mix (e.g. the serving loop's ingest waves)
  keeps hitting one program; small commits no longer pay the full
  ``BatchCaps``-padded scatter.

* **Compaction moves off the commit path**: the wave only compacts inline as
  an overflow *backstop*; crossing the fill watermark schedules the
  two-phase background task (``tasks.background_compaction_task``), which
  builds a compacted shadow store and hands it off under the MVCC pin
  contract (see ``GraphDB.begin_compaction`` / ``try_handoff``).

Semantics are exactly the historical ``commit_many``: strict serializability,
first-wins intra-batch conflicts, per-chunk commit timestamps, replication
log appends per chunk.  ``tests/test_writes.py`` pins the bit-identity.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import txn as txn_mod
from repro.core.addressing import TS_INF


class CapacityError(RuntimeError):
    """A store/log/batch static capacity would be exceeded."""


# ---------------------------------------------------------------------------
# Typed mutation-op records (the write-side IR)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CreateVertex:
    vtype: str
    key: int
    attrs: Optional[dict] = None
    hint: Optional[int] = None        # FaRM locality hint (co-locate shard)


@dataclasses.dataclass(frozen=True)
class UpdateVertex:
    gid: int
    vtype: str
    attrs: dict


@dataclasses.dataclass(frozen=True)
class DeleteVertex:
    gid: int


@dataclasses.dataclass(frozen=True)
class CreateEdge:
    src: int
    dst: int
    etype: str
    check: bool = True                # False = bulk-load fast path (§3)


@dataclasses.dataclass(frozen=True)
class DeleteEdge:
    src: int
    dst: int
    etype: str


WriteOp = Union[CreateVertex, UpdateVertex, DeleteVertex, CreateEdge,
                DeleteEdge]
_OP_TYPES = (CreateVertex, UpdateVertex, DeleteVertex, CreateEdge, DeleteEdge)


@dataclasses.dataclass
class WriteResult:
    """Per-entry outcomes of one ``GraphDB.write`` call, positionally aligned
    with the input list (the write twin of ``QueryResult``).

    ``statuses[i]`` is ``"COMMITTED"`` / ``"ABORTED"`` / ``"STAGED"`` (op
    records staged into an open transaction).  ``gids[i]`` is the allocated
    vertex gid for ``CreateVertex`` entries (−1 otherwise, and −1 when the
    batch aborted).  ``reasons[i]`` carries the abort reason, ``None`` when
    the entry succeeded.  ``ts`` is the clock after the wave (−1 for
    stage-only calls).
    """
    statuses: list
    gids: list
    reasons: list
    ts: int = -1

    @property
    def failed(self) -> bool:
        return any(s == "ABORTED" for s in self.statuses)


# ---------------------------------------------------------------------------
# Staging: op record -> Transaction (the wrappers' logic, shared)
# ---------------------------------------------------------------------------

def stage(db, op: WriteOp, t, exists: Optional[bool] = None) -> int:
    """Stage one mutation-op record into an open transaction.

    Performs the record's read-validate round-trips at ``t.read_ts`` (reads
    recorded for OCC), raises ``ValueError`` on contract violations exactly
    as the historical per-op methods did, and returns the allocated gid for
    ``CreateVertex`` (−1 for every other kind).  ``exists`` is a
    ``CreateVertex``'s index probe when the caller batched it
    (:func:`_stage_all`).
    """
    if isinstance(op, CreateVertex):
        vt = db.vt(op.vtype)
        found = exists
        if found is None:
            _, found = db.lookup_vertex(op.vtype, int(op.key),
                                        read_ts=t.read_ts)
        if found:
            raise ValueError(f"vertex ({op.vtype}, {op.key}) already exists")
        f, i = db._encode_attrs(vt, op.attrs or {})
        gid = db._alloc_vertex(op.hint)
        t.create_v.append((gid, vt.type_id, int(op.key), f, i))
        return gid
    if isinstance(op, UpdateVertex):
        vt = db.vt(op.vtype)
        cur_f, cur_i = db._read_data_host(op.gid, t.read_ts)
        t.record_read(op.gid)
        f, i = db._encode_attrs(vt, op.attrs, base_f=cur_f, base_i=cur_i)
        t.update_v.append((op.gid, f, i))
        return -1
    if isinstance(op, DeleteVertex):
        # §3.2 cascade: the incoming list names every source whose outgoing
        # half-edge must also be retired
        gid = op.gid
        vtid, key, alive = db._read_header_host(gid, t.read_ts)
        t.record_read(gid)
        if not alive:
            raise ValueError(f"vertex {gid} not found")
        outs = db.get_edges(gid, direction="out", read_ts=t.read_ts)
        ins = db.get_edges(gid, direction="in", read_ts=t.read_ts)
        for nbr, et in outs:
            t.delete_e.append((gid, int(nbr), int(et)))
        for nbr, et in ins:
            t.delete_e.append((int(nbr), gid, int(et)))
        t.delete_v.append((gid, int(vtid), int(key)))
        return -1
    if isinstance(op, CreateEdge):
        et = db.et(op.etype)
        if op.check:
            for g in (op.src, op.dst):
                _, _, alive = db._read_header_host(g, t.read_ts)
                t.record_read(g)
                if not alive:
                    raise ValueError(f"endpoint {g} not found")
            # single-edge-per-(src,type,dst) invariant (§3)
            existing = db.get_edges(op.src, direction="out",
                                    read_ts=t.read_ts, etype=et.type_id)
            t.reads.append((int(op.src), "e"))
            if any(int(n) == int(op.dst) for n, _ in existing):
                raise ValueError("edge already exists")
        t.create_e.append((int(op.src), int(op.dst), et.type_id))
        return -1
    if isinstance(op, DeleteEdge):
        et = db.et(op.etype)
        t.reads.append((int(op.src), "e"))
        t.delete_e.append((int(op.src), int(op.dst), et.type_id))
        return -1
    raise TypeError(f"not a mutation-op record: {type(op).__name__}")


# ---------------------------------------------------------------------------
# Program cache (the read planner's idiom: shape-canonical keys, LRU,
# observable hit/miss counters)
# ---------------------------------------------------------------------------

CACHE_MAX_PROGRAMS = 64
_CACHE: collections.OrderedDict = collections.OrderedDict()
CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def reset_stats() -> None:
    """Zero the module-global counters (the traced programs stay cached).

    Stats are process-global while programs are shared across ``GraphDB``
    instances, so a fresh server/bench run must reset explicitly or its
    hit-rate telemetry inherits every prior instance's traffic."""
    for k in CACHE_STATS:
        CACHE_STATS[k] = 0


def _cache_get(key):
    fn = _CACHE.get(key)
    if fn is not None:
        _CACHE.move_to_end(key)
        CACHE_STATS["hits"] += 1
    return fn


def _cache_put(key, fn):
    CACHE_STATS["misses"] += 1
    _CACHE[key] = fn
    while len(_CACHE) > CACHE_MAX_PROGRAMS:
        _CACHE.popitem(last=False)
        CACHE_STATS["evictions"] += 1


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _bucket(n: int) -> int:
    """Shape canonicalization: 0 stays 0, everything else pow2-rounds."""
    return 0 if n == 0 else _pow2ceil(n)


def _validate_program(cfg, P: int):
    """One jitted OCC validation wave over ``P`` padded reads.

    Returns per-read conflict flags: the read object's last write landed
    after the owning transaction's snapshot.  Padded rows (gid −1, rts 0)
    report ``last_write_ts == 0 > 0 == False`` and never conflict.
    """
    key = ("validate", cfg, P)
    fn = _cache_get(key)
    if fn is None:
        def prog(store, gids, kinds, read_ts):
            lw = txn_mod.last_write_ts(store, cfg, gids, kinds)
            return lw > read_ts
        fn = jax.jit(prog)
        _cache_put(key, fn)
    return fn


def _apply_program(cfg, shapes: tuple, sharding=None):
    """The fused apply program of one mutation-shape group.

    ``shapes`` is the canonical ``(create_v, update_v, delete_v, create_e,
    delete_e)`` pow2 bucket tuple; each distinct tuple traces (and donates
    through) its own jitted instance so LRU eviction actually frees the
    trace.  ``sharding`` keeps a mesh-placed store where it is.
    """
    key = ("apply", cfg, shapes, sharding)
    fn = _cache_get(key)
    if fn is None:
        fn = jax.jit(lambda store, ts, *ops:
                     txn_mod.apply_batch_impl(store, cfg, ts, *ops),
                     donate_argnums=(0,), out_shardings=sharding)
        _cache_put(key, fn)
    return fn


# ---------------------------------------------------------------------------
# The commit wave
# ---------------------------------------------------------------------------

def commit_wave(db, txns: Sequence, caps=None):
    """Validate + apply a batch of transactions as fused mutation waves.

    Returns ``(statuses, reasons)`` per transaction.  Semantics are the
    historical ``commit_many`` bit-for-bit; the mechanics differ:

    1. one vectorized OCC validation wave over *all* read sets (per-read
       snapshot timestamps, so mixed-snapshot batches validate in one pass);
    2. host-side first-wins intra-batch resolution (unchanged);
    3. inline compaction only as the overflow *backstop* — and the check
       counts ``delete_e`` entries too: tombstones occupy no fresh slots,
       but a tombstone-laden log can only reclaim space at compaction, so
       delete-heavy batches trigger the fold before the log saturates;
    4. winners chunked under the static ``BatchCaps``, each chunk applied by
       the shape-canonical fused program at its own commit timestamp.

    After the wave, crossing the delta-log fill watermark schedules the
    background compaction task (never compacts inline here).
    """
    caps = caps or db.caps
    cfg = db.cfg
    txns = list(txns)

    # 1) OCC validation: one wave over every transaction's read set ---------
    gids, kinds, owner, rts = [], [], [], []
    for i, t in enumerate(txns):
        for g, kind in t.reads:
            gids.append(g)
            kinds.append(1 if kind == "e" else 0)
            owner.append(i)
            rts.append(t.read_ts)
    status = ["COMMITTED"] * len(txns)
    reason: list = [None] * len(txns)
    if gids:
        P = _pow2ceil(len(gids))
        fn = _validate_program(cfg, P)
        conflict = np.asarray(fn(
            db.store, txn_mod.pad_i32(gids, P),
            txn_mod.pad_i32(kinds, P, fill=0),
            txn_mod.pad_i32(rts, P, fill=0)))
        for i, c in zip(owner, conflict[:len(gids)]):
            if bool(c) and status[i] == "COMMITTED":
                status[i] = "ABORTED"
                reason[i] = "stale read (OCC validation)"

    # 2) intra-batch conflicts, first-wins (§3): a later txn aborts if it
    #    writes an object an earlier winner wrote, or reads an object an
    #    earlier winner wrote — every winner reads pre-batch state and the
    #    batch serializes in any order.
    taken: set = set()
    for i, t in enumerate(txns):
        if status[i] == "ABORTED":
            continue
        wk = t.write_keys()
        if wk & taken:
            status[i] = "ABORTED"
            reason[i] = "intra-batch write-write conflict (first wins)"
        elif t.read_keys() & taken:
            status[i] = "ABORTED"
            reason[i] = "intra-batch read-write conflict (first wins)"
        else:
            taken |= wk
    winners = [t for i, t in enumerate(txns) if status[i] == "COMMITTED"]
    for i, t in enumerate(txns):
        t.status = status[i]
    if not winners:
        db.stats["aborts"] += len(txns)
        return status, reason

    # 3) capacity backstop: inline-compact only if the logs would overflow --
    _ensure_capacity(db, winners)

    # 4) apply winners, chunked under the static batch caps; winners are
    #    mutually conflict-free, so chunked application at increasing
    #    timestamps preserves the batch's serializable order.  Each chunk
    #    becomes one *wave record* — physical gids plus the logical
    #    identities resolved at commit time — the unit of fleet
    #    replication (§4): ``replay_wave`` re-applies it on a replica,
    #    ``ReplicationLog.append_wave`` ships it durably.
    for chunk in _chunks(winners, caps):
        ts = db.clock + 1
        _apply_chunk(db, chunk, ts)
        seq = db.wave_seq + 1
        rec = wave_record(db, chunk, ts, seq)
        db.wave_seq = seq
        db.wave_log.append(rec)
        _remember_rids(db, chunk, ts)
        if db.replication_log is not None:
            db.replication_log.append_wave(rec)
    db.stats["commits"] += len(winners)
    db.stats["aborts"] += len(txns) - len(winners)
    db.stats["write_waves"] += 1
    db._maybe_schedule_compaction()
    return status, reason


def _ensure_capacity(db, winners) -> None:
    """Step 3 of the wave: inline-compact only as the overflow backstop."""
    cfg = db.cfg
    n_ce = sum(len(t.create_e) for t in winners)
    n_de = sum(len(t.delete_e) for t in winners)
    n_cv = sum(len(t.create_v) for t in winners)
    n_dv = sum(len(t.delete_v) for t in winners)
    if (db.dl_count.max(initial=0) + n_ce + n_de > cfg.cap_delta
            or db.il_count.max(initial=0) + n_ce + n_de > cfg.cap_delta):
        db.run_compaction()
    if db.xd_count.max(initial=0) + n_cv + n_dv > cfg.cap_idx_delta:
        db.run_index_compaction()
    if db._vindexed:
        from repro.core import vindex as vindex_mod
        need = vindex_mod.wave_demand(db, winners)
        if np.any(db.vx_count + need > cfg.cap_vec):
            db.run_vindex_compaction()
            if np.any(db.vx_count + need > cfg.cap_vec):
                raise CapacityError("vector index full; raise cap_vec")


def _apply_chunk(db, chunk, ts: int) -> None:
    """Apply one winner chunk at commit timestamp ``ts`` (the fused
    program dispatch + host bookkeeping shared by commit and replay)."""
    shapes, args = _build_wave(db, chunk)
    fn = _apply_program(db.cfg, shapes, db.sharding)
    db.store = fn(db.store, jnp.int32(ts), *args)
    db.clock = max(db.clock, ts)
    if db._vindexed:
        from repro.core import vindex as vindex_mod
        vindex_mod.apply_wave(db, chunk, ts)
    if any(t.delete_e for t in chunk):
        db.epochs["delete_e"] += 1
    if any(t.delete_v for t in chunk):
        db.epochs["delete_v"] += 1


def _remember_rids(db, chunk, ts: int) -> None:
    """Record each committed txn's client rid -> outcome.  A promoted
    replica answers ``write_by_rid`` lookups from this map, and a
    re-admitted request whose rid is already here returns the ORIGINAL
    result instead of committing twice (exactly-once across failover)."""
    for t in chunk:
        rid = getattr(t, "rid", None)
        if rid is None:
            continue
        db.applied_rids[rid] = {
            "ts": int(ts), "gids": [int(g) for g, *_ in t.create_v]}
    while len(db.applied_rids) > 4096:
        db.applied_rids.popitem(last=False)


# ---------------------------------------------------------------------------
# Wave records: the unit of fleet replication (§4)
# ---------------------------------------------------------------------------

def _idents(db, chunk, ts: int) -> dict:
    """gid -> (vtype, key) of every vertex a chunk's record names, in two
    batched header reads: at ``ts``, then at ``ts - 1`` for vertices the
    chunk itself deleted (their pre-state)."""
    gids = sorted({int(g) for t in chunk for g in
                   [x[0] for x in t.update_v]
                   + [v for e in t.create_e + t.delete_e for v in e[:2]]})
    if not gids:
        return {}
    vt, key, alive = db.read_headers(gids, ts)
    dead = [g for g, a in zip(gids, alive) if not a]
    out = {g: (int(v), int(k)) for g, v, k in zip(gids, vt, key)}
    if dead:
        vt, key, _ = db.read_headers(dead, ts - 1)
        out.update({g: (int(v), int(k)) for g, v, k in zip(dead, vt, key)})
    return out


def wave_record(db, chunk, ts: int, seq: int) -> dict:
    """One committed chunk as a JSON-safe record.

    Carries the physical op arrays (gids are primary-assigned and ship
    verbatim — replicas replay them so physical ids agree fleet-wide)
    *plus* the logical identities resolved at commit time (update targets,
    edge endpoints), so a db-less consumer (the frontend's durable
    :class:`~repro.core.replication.ReplicationLog`) can derive the
    logical log entries without a store to read headers from."""
    ident = _idents(db, chunk, ts)
    txns = []
    for t in chunk:
        uv = []
        for gid, f, i in t.update_v:
            vt, key = ident[int(gid)]
            uv.append([int(gid), int(vt), int(key),
                       np.asarray(f).tolist(), np.asarray(i).tolist()])
        txns.append({
            "rid": getattr(t, "rid", None),
            "create_v": [[int(g), int(vt), int(k),
                          np.asarray(f).tolist(), np.asarray(i).tolist()]
                         for g, vt, k, f, i in t.create_v],
            "update_v": uv,
            "delete_v": [[int(g), int(vt), int(k)]
                         for g, vt, k in t.delete_v],
            "create_e": [[int(s), int(d), int(et),
                          *ident[int(s)], *ident[int(d)]]
                         for s, d, et in t.create_e],
            "delete_e": [[int(s), int(d), int(et),
                          *ident[int(s)], *ident[int(d)]]
                         for s, d, et in t.delete_e],
        })
    return {"seq": int(seq), "ts": int(ts),
            "epoch": int(getattr(db, "config_epoch", 0)), "txns": txns}


def replay_wave(db, rec: dict) -> int:
    """Apply one shipped wave record on a replica (the tail-replay step).

    Idempotent: a record at or below the local wave frontier is skipped
    (the rid-cache / retransmit path can deliver duplicates).  A gap means
    the replica fell off the bounded wave log and needs a full resync —
    that is an error, not a silent hole.  Replay runs at the record's
    ORIGINAL commit timestamp, so MVCC snapshots are fleet-identical:
    a read at ``read_ts`` answers the same rows on every coordinator.
    Returns 1 when applied, 0 when skipped."""
    seq = int(rec["seq"])
    if seq <= db.wave_seq:
        return 0
    if seq != db.wave_seq + 1:
        raise ValueError(
            f"replication gap: local frontier {db.wave_seq}, got {seq}; "
            "full resync required")
    ts = int(rec["ts"])
    chunk = []
    for tr in rec["txns"]:
        t = txn_mod.Transaction(read_ts=0)
        t.rid = tr.get("rid")
        t.status = "COMMITTED"
        for g, vt, k, f, i in tr["create_v"]:
            t.create_v.append((int(g), int(vt), int(k),
                               np.asarray(f, np.float32),
                               np.asarray(i, np.int32)))
        for g, vt, k, f, i in tr["update_v"]:
            t.update_v.append((int(g), np.asarray(f, np.float32),
                               np.asarray(i, np.int32)))
        t.delete_v = [(int(g), int(vt), int(k))
                      for g, vt, k in tr["delete_v"]]
        t.create_e = [(int(s), int(d), int(et))
                      for s, d, et, *_ in tr["create_e"]]
        t.delete_e = [(int(s), int(d), int(et))
                      for s, d, et, *_ in tr["delete_e"]]
        chunk.append(t)
    _ensure_capacity(db, chunk)
    # reserve primary-assigned gids: if this replica is later promoted it
    # must never re-allocate a slot the old primary already handed out
    S = db.cfg.n_shards
    for t in chunk:
        for g, *_ in t.create_v:
            sh, slot = int(g) % S, int(g) // S
            if db.v_next[sh] <= slot:
                db.v_next[sh] = slot + 1
            elif slot in db.v_free[sh]:
                db.v_free[sh].remove(slot)
    _apply_chunk(db, chunk, ts)
    db.wave_seq = seq
    db.wave_log.append(rec)
    db.config_epoch = max(db.config_epoch, int(rec.get("epoch", 0)))
    _remember_rids(db, chunk, ts)
    db.stats["replayed_waves"] = db.stats.get("replayed_waves", 0) + 1
    db._maybe_schedule_compaction()
    return 1


def _chunks(winners, caps):
    out, acc = [], []
    ncv = nuv = ndv = nce = nde = 0
    for t in winners:
        if acc and (ncv + len(t.create_v) > caps.create_v
                    or nuv + len(t.update_v) > caps.update_v
                    or ndv + len(t.delete_v) > caps.delete_v
                    or nce + len(t.create_e) > caps.create_e
                    or nde + len(t.delete_e) > caps.delete_e):
            out.append(acc)
            acc, ncv, nuv, ndv, nce, nde = [], 0, 0, 0, 0, 0
        acc.append(t)
        ncv += len(t.create_v)
        nuv += len(t.update_v)
        ndv += len(t.delete_v)
        nce += len(t.create_e)
        nde += len(t.delete_e)
        if (len(t.create_v) > caps.create_v or len(t.update_v) > caps.update_v
                or len(t.delete_v) > caps.delete_v
                or len(t.create_e) > caps.create_e
                or len(t.delete_e) > caps.delete_e):
            raise CapacityError(
                "single transaction exceeds batch caps; raise BatchCaps")
    if acc:
        out.append(acc)
    return out


def _build_wave(db, chunk):
    """Pad one winner chunk's op arrays to their canonical shape bucket and
    assign host-side log positions (delta/index fill mirrors advance here).

    Returns ``(shapes, args)`` where ``shapes`` keys the fused program and
    ``args`` is the padded argument tuple ``apply_batch`` expects.
    """
    cfg = db.cfg
    S = cfg.n_shards
    cv, uv, dv, ce, de = [], [], [], [], []
    for t in chunk:
        cv += t.create_v
        uv += t.update_v
        dv += t.delete_v
        ce += t.create_e
        de += t.delete_e
    shapes = (_bucket(len(cv)), _bucket(len(uv)), _bucket(len(dv)),
              _bucket(len(ce)), _bucket(len(de)))
    bcv, buv, bdv, bce, bde = shapes

    # index-delta positions for creates (host-assigned, per index shard)
    from repro.core import index as index_mod
    xpos = []
    for gid, vtid, key, f, i in cv:
        sh = index_mod.route_host(vtid, key, S)
        xpos.append(sh * cfg.cap_idx_delta + int(db.xd_count[sh]))
        db.xd_count[sh] += 1
    # delta-log positions for edge creates
    opos, ipos = [], []
    for s, d, et in ce:
        so, sd = s % S, d % S
        opos.append(so * cfg.cap_delta + int(db.dl_count[so]))
        db.dl_count[so] += 1
        ipos.append(sd * cfg.cap_delta + int(db.il_count[sd]))
        db.il_count[sd] += 1

    p32 = txn_mod.pad_i32
    args = (
        p32([x[0] for x in cv], bcv),
        p32([x[1] for x in cv], bcv),
        p32([x[2] for x in cv], bcv),
        txn_mod.pad_f32([x[3] for x in cv], bcv, cfg.d_f32),
        txn_mod.pad_i32_2d([x[4] for x in cv], bcv, cfg.d_i32),
        p32(xpos, bcv),
        p32([x[0] for x in uv], buv),
        txn_mod.pad_f32([x[1] for x in uv], buv, cfg.d_f32),
        txn_mod.pad_i32_2d([x[2] for x in uv], buv, cfg.d_i32),
        p32([x[0] for x in dv], bdv),
        p32([x[1] for x in dv], bdv),
        p32([x[2] for x in dv], bdv),
        p32([x[0] for x in ce], bce),
        p32([x[1] for x in ce], bce),
        p32([x[2] for x in ce], bce),
        p32(opos, bce),
        p32(ipos, bce),
        p32([x[0] for x in de], bde),
        p32([x[1] for x in de], bde),
        p32([x[2] for x in de], bde),
        jnp.asarray(db.dl_count, jnp.int32),
        jnp.asarray(db.il_count, jnp.int32),
        jnp.asarray(db.xd_count, jnp.int32),
    )
    return shapes, args


# ---------------------------------------------------------------------------
# The entry point (exported as GraphDB.write)
# ---------------------------------------------------------------------------

def _stage_all(db, ops, t) -> list:
    """Stage op records in order, with every ``CreateVertex``'s existence
    probe batched into one index lookup at the transaction's snapshot (the
    probes read committed state only, so batching them changes nothing)."""
    cv = [op for op in ops if isinstance(op, CreateVertex)]
    found = iter(())
    if cv:
        _, f = db.lookup_vertices([db.vt(op.vtype).type_id for op in cv],
                                  [int(op.key) for op in cv], t.read_ts)
        found = iter(bool(x) for x in f)
    return [stage(db, op, t, next(found) if isinstance(op, CreateVertex)
                  else None) for op in ops]


def write(db, ops, *, txn=None, caps=None) -> WriteResult:
    """Execute a batch of mutations (see ``GraphDB.write`` for the API doc).

    ``ops`` is either a list of mutation-op records or a list of staged
    ``Transaction`` objects (never mixed).  Op records with ``txn=`` stage
    only; without, they form one implicit atomic transaction committed
    immediately.  Transactions commit as one fused mutation wave.  Staging
    contract violations (duplicate key, missing endpoint, ...) raise
    ``ValueError`` synchronously; commit-time OCC outcomes come back as
    per-entry statuses + abort reasons.
    """
    ops = list(ops)
    if not ops:
        raise ValueError("write() needs at least one op or transaction")
    if isinstance(ops[0], txn_mod.Transaction):
        if txn is not None:
            raise ValueError("txn= only applies to mutation-op records")
        if not all(isinstance(o, txn_mod.Transaction) for o in ops):
            raise TypeError("cannot mix transactions and op records")
        statuses, reasons = commit_wave(db, ops, caps)
        return WriteResult(statuses=statuses, gids=[-1] * len(ops),
                           reasons=reasons, ts=db.clock)
    for op in ops:
        if not isinstance(op, _OP_TYPES):
            raise TypeError(f"not a mutation-op record: {type(op).__name__}")
    if txn is not None:
        t, _ = db._txn(txn)
        gids = _stage_all(db, ops, t)
        return WriteResult(statuses=["STAGED"] * len(ops), gids=gids,
                           reasons=[None] * len(ops), ts=-1)
    # implicit transaction: the whole op list commits atomically (§3's
    # "a transaction is implicitly created for that operation", batched)
    t = db.create_transaction()
    gids = _stage_all(db, ops, t)
    statuses, reasons = commit_wave(db, [t], caps)
    committed = statuses[0] == "COMMITTED"
    return WriteResult(
        statuses=[statuses[0]] * len(ops),
        gids=gids if committed else [-1] * len(ops),
        reasons=[reasons[0]] * len(ops), ts=db.clock)
