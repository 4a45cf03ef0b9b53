"""Recovery from ObjectStore (§4) + fast restart (§5.3).

Two recovery modes, exactly the paper's semantics:

* **consistent**: rebuild from the versioned tables at the durable watermark
  t_R — the most recent *transactionally consistent* snapshot.  A partially
  replicated transaction (some entries above t_R unshipped) is excluded
  wholesale.
* **best-effort**: rebuild from the LWW tables — every vertex/edge that made
  it to durable storage, regardless of transaction boundaries, then repair
  internal consistency: an edge whose endpoint is missing is dropped (no
  dangling edges).  Always at-least-as-fresh as consistent recovery.

Fast restart: the region memory lives in a *process-external* holder (PyCo
kernel driver in the paper; a host-RAM cache object here).  A restarted
serving process re-attaches the arrays instead of re-loading from durable
storage — an order of magnitude less downtime (§5.3).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.core.addressing import StoreConfig
from repro.core.graphdb import GraphDB
from repro.core.replication import TOMBSTONE, ObjectStore


# ---------------------------------------------------------------------------
# rebuild helpers
# ---------------------------------------------------------------------------

def _rebuild(db: GraphDB, vrows: dict, erows: dict, *,
             drop_dangling: bool) -> GraphDB:
    """Load logical rows through the transactional write path."""
    from repro.core.writes import CreateEdge, CreateVertex
    id2name = {vt.type_id: name
               for name, vt in db.catalog.tenants[db.tenant][db.graph]
               .vtypes.items()}
    e2name = {et.type_id: name
              for name, et in db.catalog.tenants[db.tenant][db.graph]
              .etypes.items()}

    def load(ops, chunk):
        gids = []
        for off in range(0, len(ops), chunk):
            res = db.write(ops[off:off + chunk])
            assert not res.failed
            gids += res.gids
        return gids

    v_ops, v_keys = [], []
    for (vtid, key), (val, ts) in sorted(vrows.items()):
        if val == TOMBSTONE:
            continue
        f, i = val
        name = id2name[vtid]
        vt = db.vt(name)
        attrs = {}
        for a in vt.attrs:
            attrs[a.name] = (f[a.col] if a.kind == "f32" else i[a.col])
        v_ops.append(CreateVertex(name, key, attrs))
        v_keys.append((vtid, key))
    gid_of = dict(zip(v_keys, load(v_ops, 200)))

    e_ops = []
    for ekey, (val, ts) in sorted(erows.items()):
        if val == TOMBSTONE:
            continue
        svt, sk, et, dvt, dk = ekey
        s = gid_of.get((svt, sk))
        d = gid_of.get((dvt, dk))
        if s is None or d is None:
            if drop_dangling:
                continue                  # internal consistency repair
            raise ValueError(f"dangling edge {ekey} in consistent recovery")
        # endpoints were just validated against the recovered row set —
        # the bulk-load fast path applies, like the original apply stream
        e_ops.append(CreateEdge(s, d, e2name[int(et)], check=False))
    load(e_ops, 400)
    db.run_compaction()
    db.run_index_compaction()
    return db


def _clone_schema(src_db: GraphDB, cfg: StoreConfig) -> GraphDB:
    db = GraphDB(cfg)
    meta = src_db.catalog.tenants[src_db.tenant][src_db.graph]
    for name, vt in meta.vtypes.items():
        f = [a.name for a in vt.attrs if a.kind == "f32"]
        i = [a.name for a in vt.attrs if a.kind == "i32"]
        db.vertex_type(name, f, i)
    for name in meta.etypes:
        db.edge_type(name)
    return db


def best_effort_recover(store: ObjectStore, schema_db: GraphDB,
                        cfg: StoreConfig, *, graph: str = "g") -> GraphDB:
    """LWW tables -> fresh GraphDB; dangling edges dropped (§4)."""
    db = _clone_schema(schema_db, cfg)
    vrows = {k: v for k, v in store.scan(f"{graph}.vertices").items()}
    erows = {k: v for k, v in store.scan(f"{graph}.edges").items()}
    return _rebuild(db, vrows, erows, drop_dangling=True)


def consistent_recover(store: ObjectStore, schema_db: GraphDB,
                       cfg: StoreConfig, *, graph: str = "g") -> GraphDB:
    """Versioned tables filtered at t_R -> transactionally consistent DB."""
    t_r = store.get_meta(f"{graph}.t_R", 0)
    vrows: dict = {}
    for (vt, key, ts), (val, _) in store.scan(
            f"{graph}.vertices.versions").items():
        if ts > t_r:
            continue
        cur = vrows.get((vt, key))
        if cur is None or ts >= cur[1]:
            vrows[(vt, key)] = (val, ts)
    erows: dict = {}
    for row, (val, _) in store.scan(f"{graph}.edges.versions").items():
        *ekey, ts = row
        if ts > t_r:
            continue
        ekey = tuple(ekey)
        cur = erows.get(ekey)
        if cur is None or ts >= cur[1]:
            erows[ekey] = (val, ts)
    db = _clone_schema(schema_db, cfg)
    return _rebuild(db, vrows, erows, drop_dangling=False)


# ---------------------------------------------------------------------------
# fast restart (§5.3)
# ---------------------------------------------------------------------------

def _wire_db(s: dict, store) -> GraphDB:
    """Wire a fresh GraphDB around an already-materialized store tree plus
    the held coordinator metadata (the common core of :meth:`restart` and
    :func:`attach_shared`)."""
    db = GraphDB.__new__(GraphDB)
    db.cfg = s["cfg"]
    db.sharding = None
    db.caps = __import__("repro.core.txn", fromlist=["BatchCaps"]
                         ).BatchCaps()
    db.store = store
    db.catalog = s["catalog"]
    db.tenant, db.graph = "default", "g"
    db.clock = s["clock"]
    db.v_next = s["v_next"].copy()
    db.v_free = [list(x) for x in s["v_free"]]
    db._rr = 0
    db.dl_count = s["dl_count"].copy()
    db.il_count = s["il_count"].copy()
    db.xd_count = s["xd_count"].copy()
    # the vector-index slots live inside the held store tree; only the
    # host-side mirrors need re-attaching (pre-vindex holds lack them)
    db.vx_count = s.get("vx_count", np.zeros(db.cfg.n_shards, np.int64)).copy()
    db._vindexed = set(s.get("vindexed", ()))
    db._vx_pos = dict(s.get("vx_pos", {}))
    db.replication_log = None
    db.stats = {"commits": 0, "aborts": 0, "compactions": 0,
                "write_waves": 0, "bg_compactions": 0,
                "compaction_rebuilds": 0, "vindex_compactions": 0}
    db.active_query_ts = []
    db.epochs = {"delete_e": 0, "delete_v": 0,
                 "compact_edges": 0, "compact_index": 0}
    db.task_queue = None
    db.compaction_watermark = 0.5
    db._bg_compaction_pending = False
    db.faults = None
    db.backend = None
    # fleet replication state (`.get`: pre-membership holds lack these)
    import collections
    db.config_epoch = 0
    db.wave_seq = int(s.get("wave_seq", 0))
    db.wave_log = collections.deque(maxlen=512)
    db.wave_inbox = collections.deque()
    db.applied_rids = collections.OrderedDict(
        (k, dict(v)) for k, v in dict(s.get("applied_rids", {})).items())
    db.fleet_pins = []
    return db


def attach_shared(manifest: dict) -> GraphDB:
    """Re-attach a serving process to an :meth:`export_shared` segment.

    The worker maps the exporter's shared-memory pages (zero host copies —
    every coordinator reads the *same* CSR/index bytes) and materializes
    device arrays from the views: one ``device_put`` per field, the §5.3
    re-attach cost.  On the CPU backend the device arrays are themselves
    copies, so mutation by one worker can never corrupt a sibling — the
    shared segment is the one *host* copy of record, exactly the
    process-external PyCo region of the paper.

    The returned db's ``_shm_handle`` keeps the mapping alive for the
    db's lifetime; the exporter owns unlinking (via ``drop``)."""
    from multiprocessing import shared_memory
    # attaching does not register with the resource tracker (only the
    # creator does), so worker exit never unlinks the exporter's segment
    shm = shared_memory.SharedMemory(name=manifest["segment"])
    kw = {}
    for fname, (off, shape, dtype) in manifest["fields"].items():
        view = np.ndarray(shape, dtype=np.dtype(dtype),
                          buffer=shm.buf, offset=off)
        kw[fname] = jax.numpy.asarray(view)
    from repro.core.store import GraphStore
    db = _wire_db(manifest["meta"], GraphStore(**kw))
    db._shm_handle = shm
    return db


class FastRestartCache:
    """Process-external region holder (the PyCo analogue).

    Keeps the store arrays (as host numpy) + coordinator metadata.  A
    restarted process re-attaches in O(device_put) instead of replaying
    durable storage.  Does not survive a host power cycle — that's the
    disaster-recovery path's job, exactly as in the paper.
    """

    def __init__(self):
        self._slots: dict = {}
        self._shm: dict = {}             # name -> exported SharedMemory

    def hold(self, name: str, db: GraphDB) -> None:
        store_np = jax.tree.map(np.asarray, db.store)
        self._slots[name] = dict(
            store=store_np,
            clock=db.clock,
            v_next=db.v_next.copy(),
            v_free=[list(x) for x in db.v_free],
            dl_count=db.dl_count.copy(),
            il_count=db.il_count.copy(),
            xd_count=db.xd_count.copy(),
            vx_count=db.vx_count.copy(),
            vindexed=set(db._vindexed),
            vx_pos=dict(db._vx_pos),
            catalog=db.catalog,
            cfg=db.cfg,
            wave_seq=int(getattr(db, "wave_seq", 0)),
            applied_rids={k: dict(v) for k, v in
                          dict(getattr(db, "applied_rids", {})).items()},
        )

    def restart(self, name: str) -> Optional[GraphDB]:
        """Re-attach: returns a fresh GraphDB wired to the held regions."""
        s = self._slots.get(name)
        if s is None:
            return None                  # regions lost -> disaster recovery
        return _wire_db(s, jax.tree.map(jax.numpy.asarray, s["store"]))

    def export_shared(self, name: str) -> dict:
        """Publish a held slot as ONE POSIX shared-memory segment.

        This is the cluster front's store seam: the exporting frontend
        keeps the single host copy of the CSR/index arrays; every
        coordinator worker :func:`attach_shared`-maps the same pages and
        pays only its own device transfer — N workers never hold N host
        copies of the graph.  Returns a picklable manifest (segment name +
        per-field offset/shape/dtype + the coordinator metadata) that
        travels to spawned workers as a plain argument.  The segment lives
        until :meth:`drop` (or exporter exit) unlinks it."""
        from multiprocessing import shared_memory
        s = self._slots[name]
        if name in self._shm:
            raise ValueError(f"slot {name!r} already exported")
        store = s["store"]
        arrs = {f.name: np.ascontiguousarray(getattr(store, f.name))
                for f in dataclasses.fields(store)}
        fields, off = {}, 0
        for fname, a in arrs.items():
            off = (off + 63) & ~63                   # 64B-align each field
            fields[fname] = (off, a.shape, a.dtype.str)
            off += a.nbytes
        shm = shared_memory.SharedMemory(create=True, size=max(off, 1))
        for fname, a in arrs.items():
            o = fields[fname][0]
            np.ndarray(a.shape, dtype=a.dtype, buffer=shm.buf,
                       offset=o)[...] = a
        self._shm[name] = shm
        meta = {k: v for k, v in s.items() if k != "store"}
        return {"segment": shm.name, "fields": fields, "meta": meta}

    def drop(self, name: str) -> None:
        self._slots.pop(name, None)
        shm = self._shm.pop(name, None)
        if shm is not None:
            shm.close()
            shm.unlink()
