"""GraphDB: the A1 database facade (data-plane + control-plane APIs, §3).

The host process plays the role of an A1 *backend machine acting as
coordinator*: it owns the catalog, the global clock, allocation metadata, and
drives jitted device programs for everything data-touching.  The device arrays
are "the cluster's memory"; the host never holds vertex data (only allocation
bookkeeping), matching the coprocessor split of §2.2.

Data-plane ops stage into :class:`Transaction` objects and are committed in
batches (see txn.py).  If no transaction is supplied, each call runs under an
implicit transaction committed immediately (§3: "a transaction is implicitly
created for that operation").
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import edges as edges_mod
from repro.core import index as index_mod
from repro.core import txn as txn_mod
from repro.core import writes as writes_mod
from repro.core.addressing import NULL, TS_INF, StoreConfig, gid_of
from repro.core.catalog import Catalog, EdgeType, VertexType
from repro.core.store import (GraphStore, gather_data, gather_headers,
                              make_store, replay_log_tail)
from repro.core.writes import CapacityError

_lookup = jax.jit(index_mod.lookup, static_argnames=("cfg", "backend",
                                                     "xd_win"))
_headers = jax.jit(gather_headers, static_argnames=("cfg",))
_data = jax.jit(gather_data, static_argnames=("cfg",))
_expand = jax.jit(edges_mod.expand, static_argnames=("cfg", "direction",
                                                     "cap_out"))


@functools.lru_cache(maxsize=None)
def _placed(fn, sharding):
    return jax.jit(fn, static_argnames=("cfg",), out_shardings=sharding)


class GraphDB:
    """One graph's storage + transactional data plane."""

    def __init__(self, cfg: StoreConfig, *, catalog: Optional[Catalog] = None,
                 tenant: str = "default", graph: str = "g",
                 caps: Optional[txn_mod.BatchCaps] = None,
                 replication_log=None, backend: Optional[str] = None,
                 sharding=None):
        cfg.validate()
        self.cfg = cfg
        # placement of the shard-major store arrays across a mesh (None =
        # the default device); every program that rebuilds store arrays
        # keeps it, and the ``store`` setter re-places stragglers
        self.sharding = sharding
        self.caps = caps or txn_mod.BatchCaps()
        # read-path backend ('ref'|'pallas'|'auto'|None = env/auto); resolved
        # by the query executors per call — host conveniences (lookup_vertex,
        # get_edges) always use the cheap jnp reference path
        self.backend = backend
        self.store: GraphStore = make_store(cfg, sharding)
        self.catalog = catalog or Catalog()
        if tenant not in self.catalog.tenants:
            self.catalog.create_tenant(tenant)
        if graph not in self.catalog.tenants[tenant]:
            self.catalog.create_graph(tenant, graph)
        self.tenant, self.graph = tenant, graph

        # -- coordinator metadata (host-side, checkpointed) -------------------
        self.clock: int = 1                          # FaRMv2 global clock
        S = cfg.n_shards
        self.v_next = np.zeros(S, np.int64)          # next fresh slot per shard
        self.v_free: list[list[int]] = [[] for _ in range(S)]   # vacuumed slots
        self._rr = 0                                 # round-robin shard cursor
        self.dl_count = np.zeros(S, np.int64)        # delta-log fill mirrors
        self.il_count = np.zeros(S, np.int64)
        self.xd_count = np.zeros(S, np.int64)
        self.vx_count = np.zeros(S, np.int64)        # vector-index fill mirror
        self._vindexed: set[int] = set()             # vector-indexed type_ids
        self._vx_pos: dict[int, tuple[int, int]] = {}  # gid -> (pos, type_id)
        self.replication_log = replication_log       # recovery hook (§4)
        self.stats = {"commits": 0, "aborts": 0, "compactions": 0,
                      "write_waves": 0, "bg_compactions": 0,
                      "compaction_rebuilds": 0, "vindex_compactions": 0}
        self.active_query_ts: list[int] = []         # pins for GC (§2.2)
        # -- background compaction (§2.2 concurrent GC; §3.3 tasks) -----------
        # Structural epochs: a shadow compaction built at epoch E can only be
        # handed off if the epochs it depends on are still E — deletes
        # tombstone CSR/index positions that shift under compaction, and a
        # concurrent inline compaction makes the shadow's base stale.
        self.epochs = {"delete_e": 0, "delete_v": 0,
                       "compact_edges": 0, "compact_index": 0}
        self.task_queue = None              # attached by the serving tier
        self.compaction_watermark = 0.5     # delta fill fraction that triggers
        self._bg_compaction_pending = False
        self.faults = None                  # FaultInjector (chaos tests only)
        # -- fleet replication (§4: primary-backup over committed waves) ------
        self.config_epoch = 0               # membership epoch last adopted
        self.wave_seq = 0                   # last wave applied here (frontier)
        self.wave_log: collections.deque = collections.deque(maxlen=512)
        self.wave_inbox: collections.deque = collections.deque()
        self.applied_rids: collections.OrderedDict = collections.OrderedDict()
        self.fleet_pins: list[int] = []     # frontend-of-record snapshot pins

    @property
    def store(self) -> GraphStore:
        return self._store

    @store.setter
    def store(self, store: GraphStore) -> None:
        sh = getattr(self, "sharding", None)
        if sh is not None:              # host-built counters, mostly
            store = jax.tree.map(
                lambda a: a if a.sharding == sh else jax.device_put(a, sh),
                store)
        self._store = store

    def placed(self, fn):
        """``fn`` (jitted with a static ``cfg``) with its array outputs
        placed like the store."""
        if self.sharding is None:
            return fn
        return _placed(fn, self.sharding)

    # ------------------------------------------------------------------
    # schema (control plane; each call = its own implicit txn, §3)
    # ------------------------------------------------------------------
    def vertex_type(self, name: str, f_attrs=(), i_attrs=()) -> VertexType:
        return self.catalog.create_vertex_type(
            self.tenant, self.graph, name, f_attrs, i_attrs,
            max_f_cols=self.cfg.d_f32, max_i_cols=self.cfg.d_i32)

    def edge_type(self, name: str) -> EdgeType:
        return self.catalog.create_edge_type(self.tenant, self.graph, name)

    def vt(self, name: str) -> VertexType:
        return self.catalog.proxy(self.tenant, self.graph, "v", name)

    def vector_index(self, name: str) -> VertexType:
        """Register a vertex type for `Nearest` queries (core/vindex.py).

        The type's f32 payload row becomes its embedding; vertices alive now
        are backfilled, future mutation waves maintain the index inline."""
        from repro.core import vindex as vindex_mod
        return vindex_mod.register(self, name)

    def et(self, name: str) -> EdgeType:
        return self.catalog.proxy(self.tenant, self.graph, "e", name)

    # ------------------------------------------------------------------
    # transactions
    # ------------------------------------------------------------------
    def create_transaction(self) -> txn_mod.Transaction:
        return txn_mod.Transaction(read_ts=self.clock)

    def snapshot_ts(self) -> int:
        return self.clock

    # ------------------------------------------------------------------
    # allocation (FaRM Alloc with locality hint)
    # ------------------------------------------------------------------
    def _alloc_vertex(self, hint_gid: Optional[int] = None) -> int:
        S = self.cfg.n_shards
        if hint_gid is not None and hint_gid >= 0:
            order = [int(hint_gid) % S] + [s for s in range(S)
                                           if s != int(hint_gid) % S]
        else:
            order = [(self._rr + i) % S for i in range(S)]
            self._rr = (self._rr + 1) % S
        for s in order:
            if self.v_free[s]:
                return gid_of(s, self.v_free[s].pop(), S)
            if self.v_next[s] < self.cfg.cap_v:
                slot = int(self.v_next[s])
                self.v_next[s] += 1
                return gid_of(s, slot, S)
        raise CapacityError("vertex store full on all shards")

    # ------------------------------------------------------------------
    # writes (the one entry point; per-op methods are staging wrappers)
    # ------------------------------------------------------------------
    def write(self, ops, *, txn=None, caps=None) -> writes_mod.WriteResult:
        """Execute a batch of mutations — the write twin of :meth:`query`.

        ``ops`` is either a list of mutation-op records
        (:class:`~repro.core.writes.CreateVertex` et al.) or a list of staged
        :class:`~repro.core.txn.Transaction` objects (never mixed):

        * op records + ``txn=`` — stage into the open transaction, return
          per-op ``STAGED`` statuses and created gids positionally;
        * op records alone — one implicit atomic transaction, committed
          immediately (§3);
        * transactions — fuse them into batched mutation waves: one jitted
          OCC-validation wave over all read sets, one fused apply program per
          mutation-shape group (programs cached like the read planner's),
          per-txn status/abort-reason positionally.

        Staging contract violations (duplicate key, missing endpoint, ...)
        raise ``ValueError`` synchronously; OCC outcomes come back as
        statuses.  ``caps=`` overrides the per-chunk :class:`BatchCaps`.
        """
        return writes_mod.write(self, ops, txn=txn, caps=caps)

    def create_vertex(self, vtype: str, key: int, attrs: Optional[dict] = None,
                      txn: Optional[txn_mod.Transaction] = None,
                      hint: Optional[int] = None) -> int:
        return self.write([writes_mod.CreateVertex(vtype, int(key), attrs,
                                                   hint)], txn=txn).gids[0]

    def update_vertex(self, gid: int, vtype: str, attrs: dict,
                      txn: Optional[txn_mod.Transaction] = None) -> None:
        self.write([writes_mod.UpdateVertex(int(gid), vtype, attrs)], txn=txn)

    def delete_vertex(self, gid: int, txn: Optional[txn_mod.Transaction] = None
                      ) -> None:
        """Delete a vertex and all its half-edges (§3.2 cascade)."""
        self.write([writes_mod.DeleteVertex(int(gid))], txn=txn)

    def create_edge(self, src: int, dst: int, etype: str,
                    txn: Optional[txn_mod.Transaction] = None,
                    check: bool = True) -> None:
        """``check=False`` skips the endpoint/duplicate reads — the bulk-load

        fast path (the paper's daily map-reduce KG build bypasses the
        read-validate round-trips too; uniqueness is then the loader's
        contract)."""
        self.write([writes_mod.CreateEdge(int(src), int(dst), etype, check)],
                   txn=txn)

    def delete_edge(self, src: int, dst: int, etype: str,
                    txn: Optional[txn_mod.Transaction] = None) -> None:
        self.write([writes_mod.DeleteEdge(int(src), int(dst), etype)],
                   txn=txn)

    # ------------------------------------------------------------------
    # queries (A1QL v2: the one entry point)
    # ------------------------------------------------------------------
    def query(self, queries: list[dict], **kw):
        """Execute a batch of A1QL queries (chains and star patterns).

        The unified entry point (``core.query.engine.execute``): parses each
        document to the logical-plan IR and routes internally — local vs
        SPMD (``mesh=``), per-plan-shape vs fused multi-query waves
        (``fused=None`` auto, ``True`` forces per-query ``failed_q``
        flags).  ``budget="shared"`` pools all queries' frontiers into one
        shared-capacity pool (O(F*sqrt(Q)) peak memory — the serving-cap
        shape; overflow is owner-attributed fast-fail).  Accepts ``caps=``,
        ``backend=``, ``read_ts=`` (scalar or per-query), ``parsed=``;
        returns a ``QueryResult``."""
        from repro.core.query.engine import execute
        return execute(self, queries, **kw)

    # ------------------------------------------------------------------
    # reads (host conveniences; bulk reads go through the query engine)
    # ------------------------------------------------------------------
    def lookup_vertex(self, vtype: str, key: int, read_ts: Optional[int] = None
                      ) -> tuple[int, bool]:
        g, found = self.lookup_vertices([self.vt(vtype).type_id], [key],
                                        read_ts)
        return int(g[0]), bool(found[0])

    def lookup_vertices(self, type_ids, keys, read_ts: Optional[int] = None
                        ) -> tuple[np.ndarray, np.ndarray]:
        """Batched primary-index probe: ``(gids, found)`` for each
        ``(type_id, key)`` at one snapshot — one jitted program per pow2
        batch size, so staging a write of many creates costs one device
        round trip, not one per vertex."""
        n = len(keys)
        P = 1 << max(0, n - 1).bit_length()
        pad = lambda xs: np.pad(np.asarray(xs, np.int32), (0, P - n))
        rts = self.clock if read_ts is None else read_ts
        g, found = _lookup(self.store, self.cfg, jnp.asarray(pad(type_ids)),
                           jnp.asarray(pad(keys)), jnp.arange(P) < n,
                           jnp.int32(rts))
        return np.asarray(g)[:n], np.asarray(found)[:n]

    def read_headers(self, gids, read_ts: int):
        """Batched vertex-header read at a snapshot: ``(vtype, key, alive)``
        host arrays, one device round trip for the whole batch."""
        n = len(gids)
        P = 1 << max(0, n - 1).bit_length()
        g = np.pad(np.asarray(gids, np.int32), (0, P - n), constant_values=-1)
        vt, key, alive = _headers(self.store, self.cfg, jnp.asarray(g),
                                  jnp.int32(read_ts))
        return (np.asarray(vt)[:n], np.asarray(key)[:n],
                np.asarray(alive)[:n])

    def get_vertex(self, vtype: str, key: int) -> Optional[dict]:
        vt = self.vt(vtype)
        gid, found = self.lookup_vertex(vtype, key)
        if not found:
            return None
        f, i = self._read_data_host(gid, self.clock)
        out = {"gid": gid, "key": key}
        for a in vt.attrs:
            out[a.name] = float(f[a.col]) if a.kind == "f32" else int(i[a.col])
        return out

    def get_edges(self, gid: int, *, direction: str = "out",
                  read_ts: Optional[int] = None, etype: int = -1,
                  cap: int = 4096) -> list[tuple[int, int]]:
        rts = self.clock if read_ts is None else read_ts
        q, n, v, ovf = _expand(
            self.store, self.cfg,
            jnp.zeros((1,), jnp.int32), jnp.asarray([gid], jnp.int32),
            jnp.asarray([True]), etype=jnp.int32(etype), direction=direction,
            read_ts=jnp.int32(rts), cap_out=cap)
        if bool(ovf):
            raise CapacityError("edge enumeration overflow; raise cap")
        # recover edge types by re-expanding per type is wasteful; instead
        # return (nbr, etype) pairs from a typed expansion
        nbrs = np.asarray(n)
        valid = np.asarray(v)
        types = np.asarray(self._expand_types(gid, direction, rts, cap))
        out = []
        for nbr, ok, et in zip(nbrs, valid, types):
            if ok:
                out.append((int(nbr), int(et)))
        return out

    def _expand_types(self, gid, direction, rts, cap):
        """Edge types aligned with expand()'s output layout."""
        st, cfg = self.store, self.cfg
        S, cap_v, cap_e = cfg.n_shards, cfg.cap_v, cfg.cap_e
        if direction == "out":
            indptr, typ = st.oe_indptr, st.oe_type
            dslot, dtyp, dnbr = st.dl_slot, st.dl_type, st.dl_nbr
        else:
            indptr, typ = st.ie_indptr, st.ie_type
            dslot, dtyp, dnbr = st.il_slot, st.il_type, st.il_nbr
        sh, sl = gid % S, gid // S
        start = int(indptr[sh * (cap_v + 1) + sl]) + sh * cap_e
        k = jnp.arange(cap, dtype=jnp.int32)
        csr_t = np.asarray(typ[jnp.minimum(start + k, S * cap_e - 1)])
        D = dslot.shape[0]
        d_shard = np.arange(D) // cfg.cap_delta
        d_gid = np.asarray(dslot) * S + d_shard
        dt = np.where(d_gid == gid, np.asarray(dtyp), -1)
        return np.concatenate([csr_t, dt])

    # ------------------------------------------------------------------
    # commit (deprecated shims; the wave lives in core/writes.py)
    # ------------------------------------------------------------------
    def commit(self, txn: txn_mod.Transaction) -> str:
        """Deprecated: use ``write([txn])``."""
        warnings.warn(
            "GraphDB.commit is deprecated; use GraphDB.write([txn])",
            DeprecationWarning, stacklevel=2)
        return self.write([txn]).statuses[0]

    def commit_many(self, txns: Sequence[txn_mod.Transaction]) -> list[str]:
        """Deprecated: use ``write(txns)``.  Returns per-txn status."""
        warnings.warn(
            "GraphDB.commit_many is deprecated; use GraphDB.write(txns)",
            DeprecationWarning, stacklevel=2)
        txns = list(txns)
        if not txns:
            return []
        return self.write(txns).statuses

    # ------------------------------------------------------------------
    # maintenance (invoked by the Task framework)
    # ------------------------------------------------------------------
    def gc_ts(self) -> int:
        """Records with delete_ts <= gc_ts are invisible to every running or

        future query (visibility is ``rts < delete_ts``), so they may be
        reclaimed — the paper GC's versions once no query pins them (§2.2).

        Fleet pins count too: in a cluster the frontend is pin-of-record
        for routed continuations, and it ships that list to every worker
        (heartbeat/replicate frames) so no replica GCs a snapshot some
        *other* coordinator's client is still paging."""
        pins = list(self.active_query_ts) + list(self.fleet_pins)
        return min(pins) if pins else self.clock

    def run_compaction(self) -> None:
        """Inline (stop-the-world) edge compaction — overflow backstop."""
        self.store = dataclasses.replace(self.store, **self.placed(
            edges_mod.compact)(self.store, self.cfg, jnp.int32(self.gc_ts())))
        self.dl_count[:] = 0
        self.il_count[:] = 0
        self.stats["compactions"] += 1
        self.epochs["compact_edges"] += 1

    def run_index_compaction(self) -> None:
        self.store = dataclasses.replace(self.store, **self.placed(
            index_mod.compact_index)(self.store, self.cfg,
                                     jnp.int32(self.gc_ts())))
        self.xd_count[:] = 0
        self.epochs["compact_index"] += 1

    def run_vindex_compaction(self) -> None:
        """Fold the vector index: age out entries dead before gc_ts."""
        from repro.core import vindex as vindex_mod
        vindex_mod.run_compaction(self)

    # -- background compaction: build a shadow, hand it off (§2.2) ----------
    def _kinds_needed(self) -> list:
        """Compaction kinds whose delta fill crossed the watermark."""
        kinds = []
        wm = self.compaction_watermark
        fill = max(self.dl_count.max(initial=0), self.il_count.max(initial=0))
        if fill >= wm * self.cfg.cap_delta:
            kinds.append("edges")
        if self.xd_count.max(initial=0) >= wm * self.cfg.cap_idx_delta:
            kinds.append("index")
        if (self._vindexed
                and self.vx_count.max(initial=0) >= wm * self.cfg.cap_vec):
            kinds.append("vindex")
        return kinds

    def _maybe_schedule_compaction(self) -> None:
        """Called after every write wave: threshold-trigger the background
        task instead of compacting on the commit path.  Without an attached
        task queue the inline overflow backstop still guarantees capacity."""
        if self.task_queue is None or self._bg_compaction_pending:
            return
        if self._kinds_needed():
            from repro.core.tasks import background_compaction_task
            self._bg_compaction_pending = True
            self.task_queue.enqueue(background_compaction_task())

    def begin_compaction(self, kinds=("edges", "index")) -> dict:
        """Phase 1 of background compaction: build compacted shadow state.

        Folds the delta logs into base CSR/index at ``gc_ts()`` (respecting
        ``active_query_ts`` pins, §2.2) *without* touching the live store —
        ``edges.compact``/``index.compact_index`` are pure and return only
        the fields they rebuild.  Returns a handle carrying those shadow
        fields, the per-shard fill watermarks at build time, and the
        structural-epoch snapshot that :meth:`try_handoff` validates.
        """
        handle = {"gc_ts": self.gc_ts(), "kinds": tuple(kinds),
                  "epochs": dict(self.epochs), "shadow": {}, "marks": {}}
        if "edges" in kinds:
            handle["shadow"]["edges"] = self.placed(edges_mod.compact)(
                self.store, self.cfg, jnp.int32(handle["gc_ts"]))
            handle["marks"]["dl"] = self.dl_count.copy()
            handle["marks"]["il"] = self.il_count.copy()
        if "index" in kinds:
            handle["shadow"]["index"] = self.placed(index_mod.compact_index)(
                self.store, self.cfg, jnp.int32(handle["gc_ts"]))
            handle["marks"]["xd"] = self.xd_count.copy()
        # "vindex" builds no shadow: the fold is a cheap host-side prefix
        # compaction whose positions are referenced only by host metadata,
        # so it runs synchronously at handoff and cannot go stale
        return handle

    def try_handoff(self, handle: dict) -> dict:
        """Phase 2: merge the shadow into the live store, or refuse.

        Per kind, succeeds only if the structural epochs the shadow depends
        on are unchanged since the build (edge/vertex deletes tombstone
        CSR/index *positions*, which the fold moved; an inline compaction
        staled the base).  On success the store keeps its live vertex-data
        arrays, adopts the shadow's compacted CSR/index, and replays the
        delta-log tail appended since the build (``replay_log_tail``), so
        concurrent create-only ingest loses nothing.  MVCC pin safety: any
        pin taken after the build is >= the build's ``gc_ts``, so every
        record the fold dropped was already invisible to it.

        Only the shadow's *compacted* fields are read here: the emptied delta
        logs are rebuilt by replaying the live log's tail onto them.

        Returns ``{kind: bool}``; a ``False`` kind needs a rebuild.
        """
        out = {}
        for kind in handle["kinds"]:
            if kind == "edges":
                ok = (self.epochs["delete_e"] == handle["epochs"]["delete_e"]
                      and self.epochs["compact_edges"]
                      == handle["epochs"]["compact_edges"])
                if ok:
                    self._handoff_edges(handle)
                out[kind] = ok
            elif kind == "index":
                ok = (self.epochs["delete_v"] == handle["epochs"]["delete_v"]
                      and self.epochs["compact_index"]
                      == handle["epochs"]["compact_index"])
                if ok:
                    self._handoff_index(handle)
                out[kind] = ok
            elif kind == "vindex":
                self.run_vindex_compaction()
                out[kind] = True
        return out

    def _handoff_edges(self, handle: dict) -> None:
        sh = handle["shadow"]["edges"]
        cap = self.cfg.cap_delta
        w_dl = jnp.asarray(handle["marks"]["dl"], jnp.int32)
        w_il = jnp.asarray(handle["marks"]["il"], jnp.int32)
        n_dl = jnp.asarray(self.dl_count, jnp.int32)
        n_il = jnp.asarray(self.il_count, jnp.int32)
        repl = {f: sh[f] for f in (
            "oe_indptr", "oe_dst", "oe_type", "oe_create", "oe_delete",
            "ie_indptr", "ie_src", "ie_type", "ie_create", "ie_delete")}
        for f in ("dl_slot", "dl_nbr", "dl_type", "dl_create", "dl_delete"):
            repl[f] = replay_log_tail(sh[f], getattr(self.store, f),
                                      w_dl, n_dl, cap=cap)
        for f in ("il_slot", "il_nbr", "il_type", "il_create", "il_delete"):
            repl[f] = replay_log_tail(sh[f], getattr(self.store, f),
                                      w_il, n_il, cap=cap)
        self.dl_count -= handle["marks"]["dl"]
        self.il_count -= handle["marks"]["il"]
        repl["dl_count"] = jnp.asarray(self.dl_count, jnp.int32)
        repl["il_count"] = jnp.asarray(self.il_count, jnp.int32)
        self.store = dataclasses.replace(self.store, **repl)
        self.epochs["compact_edges"] += 1
        self.stats["bg_compactions"] += 1

    def _handoff_index(self, handle: dict) -> None:
        sh = handle["shadow"]["index"]
        cap = self.cfg.cap_idx_delta
        w_xd = jnp.asarray(handle["marks"]["xd"], jnp.int32)
        n_xd = jnp.asarray(self.xd_count, jnp.int32)
        repl = {f: sh[f] for f in (
            "ix_vtype", "ix_key", "ix_gid", "ix_create", "ix_delete",
            "ix_count")}
        for f in ("xd_vtype", "xd_key", "xd_gid", "xd_create", "xd_delete"):
            repl[f] = replay_log_tail(sh[f], getattr(self.store, f),
                                      w_xd, n_xd, cap=cap)
        self.xd_count -= handle["marks"]["xd"]
        repl["xd_count"] = jnp.asarray(self.xd_count, jnp.int32)
        self.store = dataclasses.replace(self.store, **repl)
        self.epochs["compact_index"] += 1
        self.stats["bg_compactions"] += 1

    def vacuum(self) -> int:
        """Reclaim vertex slots dead before gc_ts (offline GC of tombstones)."""
        gc = self.gc_ts()
        v_delete = np.asarray(self.store.v_delete)
        vtype = np.asarray(self.store.vtype)
        S, cap_v = self.cfg.n_shards, self.cfg.cap_v
        n = 0
        for s in range(S):
            blk = slice(s * cap_v, (s + 1) * cap_v)
            dead = np.where((v_delete[blk] <= gc) & (vtype[blk] >= 0))[0]
            for slot in dead:
                if int(slot) < self.v_next[s]:
                    self.v_free[s].append(int(slot))
                    n += 1
        if n:
            # wipe headers so reclaimed slots read as empty
            rows = []
            for s in range(S):
                rows += [s * cap_v + sl for sl in self.v_free[s]]
            r = jnp.asarray(rows, jnp.int32)
            self.store = dataclasses.replace(
                self.store,
                vtype=self.store.vtype.at[r].set(NULL),
                v_create=self.store.v_create.at[r].set(TS_INF),
                v_delete=self.store.v_delete.at[r].set(TS_INF))
        return n

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _txn(self, txn):
        if txn is None:
            return self.create_transaction(), True
        if txn.status != "OPEN":
            raise txn_mod.Aborted(f"transaction is {txn.status}")
        return txn, False

    def _encode_attrs(self, vt: VertexType, attrs: dict,
                      base_f=None, base_i=None):
        f = np.zeros(self.cfg.d_f32, np.float32) if base_f is None \
            else np.array(base_f, np.float32)
        i = np.zeros(self.cfg.d_i32, np.int32) if base_i is None \
            else np.array(base_i, np.int32)
        for name, val in attrs.items():
            a = vt.attr(name)
            if a.kind == "f32":
                f[a.col] = float(val)
            else:
                i[a.col] = int(val)
        return f, i

    def _read_header_host(self, gid: int, rts: int):
        vt, key, alive = self.read_headers([gid], rts)
        return int(vt[0]), int(key[0]), bool(alive[0])

    def _read_data_host(self, gid: int, rts: int):
        f, i, alive = _data(
            self.store, self.cfg, jnp.asarray([gid], jnp.int32),
            jnp.int32(rts))
        return np.asarray(f[0]), np.asarray(i[0])
