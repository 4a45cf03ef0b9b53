"""Cluster front (launch/cluster.py): SLB routing over a shared store.

Contract under test (core/README.md "cluster front"): an
:class:`A1Frontend` runs N coordinators over ONE store — inproc fleets
literally share the rehydrated ``GraphDB`` object, process fleets map one
POSIX shared-memory segment — fresh queries route least-loaded,
continuation tokens route to their stamped owner, the frontend answers
exhausted budgets locally, and writes are fleet-visible the moment their
wave commits.  The transport layer round-trips every message through real
length-prefixed JSON frames even in-process.
"""
import time

import numpy as np
import pytest

from repro.core.query.executor import QueryCaps
from repro.core.writes import CreateEdge, CreateVertex
from repro.launch.cluster import A1Frontend
from repro.launch.transport import (FrameBuffer, decode_frame,
                                    decode_write_op, encode_frame,
                                    encode_write_op)

from test_backend_parity import q_chain
from test_serve import SEL, busy_db, full_rows
from test_vector import build_vdb, q_near, q_scan

CAPS = QueryCaps(frontier=128, expand=512, results=8)


def mk_fleet(db=None, n=4, **kw):
    db = db or busy_db()
    kw.setdefault("caps", CAPS)
    return A1Frontend(db, n, **kw)


# ---------------------------------------------------------------------------
# transport codecs
# ---------------------------------------------------------------------------

def test_frame_codec_roundtrips_numpy_payloads():
    msg = {"op": "result", "n": np.int64(3), "ok": np.bool_(True),
           "rows": np.arange(3), "ms": np.float32(1.5)}
    assert decode_frame(encode_frame(msg)) == {
        "op": "result", "n": 3, "ok": True, "rows": [0, 1, 2], "ms": 1.5}


def test_frame_buffer_reassembles_partial_feeds():
    blob = b"".join(encode_frame({"i": i}) for i in range(3))
    buf, got = FrameBuffer(), []
    for off in range(0, len(blob), 5):        # worst-case 5-byte reads
        got += buf.feed(blob[off:off + 5])
    assert got == [{"i": i} for i in range(3)]


def test_write_op_codec_roundtrips():
    ops = [CreateVertex("actor", 7, {"age": 31}),
           CreateEdge(2, 3, "film.actor", check=False)]
    assert [decode_write_op(encode_write_op(o)) for o in ops] == ops
    with pytest.raises(TypeError):
        encode_write_op({"not": "an op"})


# ---------------------------------------------------------------------------
# shared store + routing
# ---------------------------------------------------------------------------

def test_inproc_fleet_shares_one_graphdb():
    """The seam: every coordinator wraps the SAME rehydrated GraphDB —
    no CSR/index duplication across the fleet."""
    with mk_fleet(n=4) as fe:
        assert {id(w.coord.server.db) for w in fe.workers.values()} \
            == {id(fe.db)}


def test_routed_queries_match_oracle_and_spread():
    db = busy_db()
    with mk_fleet(db, n=4, read_batch=1) as fe:
        for i in range(8):
            pub = fe.submit_query(q_chain(i % 3))
            solo = fe.db.query([q_chain(i % 3)], caps=CAPS)
            row = fe.query_result(pub)
            assert row["status"] == "OK"
            assert row["count"] == int(solo.counts[0])
        st = fe.cluster_stats()
        assert fe.stats["routed_queries"] == 8
        admitted = [w["admitted"] for w in st["workers"].values()]
        assert sum(admitted) == 8
        # least-loaded routing spread the traffic, not pinned one worker
        assert sum(1 for a in admitted if a > 0) >= 2
        # the load signal piggybacked back on responses
        assert any(v > 0 for v in fe._load.values())


def test_continuations_route_to_their_owner():
    db = busy_db()
    with mk_fleet(db, n=4, page_size=2) as fe:
        want = full_rows(fe.db, SEL)
        page, tok = fe.select_paged(SEL)
        owner = fe._tokmeta[tok]["cid"]
        got = list(page)
        while tok is not None:
            assert fe._tokmeta[tok]["cid"] == owner   # never re-homed
            page, tok = fe.next_page(tok)
            got.extend(page)
        assert sorted(int(x) for x in got) == want
        assert fe.stats["continuation_routes"] >= 2
        assert fe.stats["stale_routes"] == 0
        assert fe.stats["takeovers"] == 0
        assert not fe.db.active_query_ts          # pin-of-record released


def test_frontend_answers_exhausted_budget_locally():
    with mk_fleet(n=2) as fe:
        t0 = time.perf_counter()
        pub = fe.submit_query(q_chain(0), budget_ms=0.0)
        row = fe.query_result(pub)
        dt_ms = (time.perf_counter() - t0) * 1e3
        assert row == {"status": "OK", "failed": False, "rows": [],
                       "truncated": True, "budget_exhausted": True}
        assert fe.stats["budget_exhausted_frontend"] == 1
        assert fe.stats["routed_queries"] == 0    # never cost a frame
        assert dt_ms < 50.0                       # pure dict work


# ---------------------------------------------------------------------------
# the acceptance workload: mixed read/write/nearest over 4 coordinators
# ---------------------------------------------------------------------------

def test_mixed_read_write_nearest_traffic_four_coordinators():
    db, emb, rng = build_vdb()
    with A1Frontend(db, 4, caps=CAPS, read_batch=2, write_batch=1) as fe:
        vec = rng.normal(size=4).astype(np.float32)
        # reads + nearest through the SLB, batched into waves.  Explicit
        # wide budgets: first-wave jit compiles must not budget-truncate
        # the queued members (cold-compile time is not client time)
        docs = [1, 2, 4]
        pubs = [fe.submit_query(q_scan(k), budget_ms=1e6) for k in docs]
        near = fe.submit_query(q_near(vec, k=4), budget_ms=1e6)
        fe.flush()
        for k, pub in zip(docs, pubs):
            solo = fe.db.query([q_scan(k)], caps=CAPS)
            assert fe.query_result(pub)["count"] == int(solo.counts[0])
        solo = fe.db.query([q_near(vec, k=4)], caps=CAPS)
        got = fe.query_result(near)
        assert sorted(got["rows"]) == sorted(
            int(x) for x in solo.rows_gid[0] if x >= 0)
        # a write routed through the SLB commits into the SHARED store:
        # a doc at exactly the probe vector becomes every coordinator's
        # nearest answer immediately
        attrs = {f"f{i}": float(vec[i]) for i in range(4)}
        wid = fe.submit_write([CreateVertex(
            "doc", 999, {**attrs, "x": 999, "y": 0})])
        wrow = fe.write_result(wid)
        assert wrow["status"] == "COMMITTED"
        new_gid = wrow["gids"][0]
        for _ in range(4):                        # hit several coordinators
            pub = fe.submit_query(q_near(vec, k=1), budget_ms=1e6)
            fe.flush()
            assert fe.query_result(pub)["rows"] == [new_gid]
        assert fe.stats["routed_writes"] == 1
        st = fe.cluster_stats()
        assert sum(w["admitted"] for w in st["workers"].values()) == 8
        assert sum(st["budget_spend_ms"]["queue"]) >= 8
        # membership/replication are /stats-visible: one primary at epoch
        # 1, every lease alive, and a shared store is never behind itself
        assert st["membership"]["epoch"] == 1
        assert st["membership"]["primary"] == 0
        assert all(l["state"] == "alive"
                   for l in st["membership"]["leases"].values())
        assert st["replication"]["shipped_seq"] >= 1
        assert st["replication"]["max_lag"] == 0


# ---------------------------------------------------------------------------
# wire dispatch + fleet stats
# ---------------------------------------------------------------------------

def test_wire_handle_dispatch_and_stats_aggregation():
    with mk_fleet(n=2, read_batch=1) as fe:
        resp = fe.handle({"op": "query", "doc": q_chain(0)})
        assert resp["status"] == "OK"
        res = fe.handle({"op": "result", "qid": resp["qid"]})
        assert res["result"]["status"] == "OK"
        page = fe.handle({"op": "select_paged", "doc": dict(SEL)})
        assert page["status"] == "OK" and page["rows"]
        bad = fe.handle({"op": "nope"})
        assert bad["status"] == "ERROR"
        st = fe.handle({"op": "stats"})["stats"]
        assert st["frontend"]["routed_queries"] == 1
        assert st["budget_spend_ms"] is not None
        assert sum(st["budget_spend_ms"]["queue"]) >= 1
        assert st["frontend"]["frames_sent"] > 0


# ---------------------------------------------------------------------------
# transport resilience: a hung worker must not wedge the frontend (S1)
# ---------------------------------------------------------------------------

def test_worker_client_recv_timeout_suspect_then_recovers():
    """A worker that accepts the frame but never answers: the client's
    recv is bounded, the worker is flagged ``suspect`` (hung, not dead),
    the desynced stream is rebuilt with a bounded jittered reconnect, and
    the next clean round trip clears the suspicion."""
    from repro.launch.transport import WorkerClient, serve_worker
    state = {"n": 0}

    def handler(msg):
        state["n"] += 1
        if state["n"] == 2:
            time.sleep(0.6)                     # hang exactly one request
        return {"status": "OK", "n": state["n"]}

    port, shutdown = serve_worker(handler)
    try:
        c = WorkerClient("127.0.0.1", port, recv_timeout=0.15,
                         reconnect_attempts=3, backoff_s=0.01)
        assert c.request({"op": "x"})["status"] == "OK"
        assert not c.suspect
        t0 = time.monotonic()
        assert c.request({"op": "x"}) is None   # hung: bounded wait
        assert time.monotonic() - t0 < 0.5      # did not sit out the hang
        assert c.suspect and c.timeouts == 1
        assert c.reconnects >= 1                # stream rebuilt
        r = c.request({"op": "x"})
        assert r is not None and r["status"] == "OK"
        assert not c.suspect                    # clean round trip clears it
        c.close()
    finally:
        shutdown()


# ---------------------------------------------------------------------------
# process mode: real workers over one shared segment; writes are
# fleet-visible through replicated waves, and failover keeps serving them
# ---------------------------------------------------------------------------

def _worker_query(fe, cid, doc, tries=500):
    """Route one query to a SPECIFIC worker and poll its result there."""
    resp = fe._rpc(cid, {"op": "query", "doc": doc, "budget_ms": 1e6})
    assert resp["status"] == "OK"
    fe._rpc(cid, {"op": "flush"})
    for _ in range(tries):
        r = fe._rpc(cid, {"op": "result", "qid": resp["qid"]})
        if r is not None and r.get("result") is not None:
            return r["result"]
        time.sleep(0.02)
    raise AssertionError(f"worker {cid} never answered")


def test_process_mode_refuses_on_an_accelerator(monkeypatch):
    """One process holds an accelerator: spawning workers that each open
    the backend would fail or hang, so the constructor refuses and says
    why — before it copies or exports anything."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="mode='inproc'"):
        A1Frontend(None, 2, mode="process")


def test_inproc_fleet_serves_the_callers_store():
    """No second copy of the store: the fleet wraps the caller's GraphDB
    and its very device arrays."""
    import jax
    from repro.core.addressing import StoreConfig
    from repro.core.graphdb import GraphDB
    db = GraphDB(StoreConfig(n_shards=1, cap_v=64, cap_e=256, cap_delta=64,
                             cap_idx=64, cap_idx_delta=32))
    leaves = jax.tree.leaves(db.store)
    with A1Frontend(db, 2, caps=CAPS) as fe:
        assert fe.db is db
        assert all(a is b for a, b in zip(jax.tree.leaves(fe.db.store),
                                          leaves))


def test_process_mode_workers_map_one_segment():
    db = busy_db()
    a_gid, found = db.lookup_vertex("actor", 323)
    assert found
    want = full_rows(db, SEL)
    fe = A1Frontend(db, 2, mode="process", caps=CAPS, read_batch=1,
                    write_batch=1)
    try:
        for i in range(4):
            pub = fe.submit_query(q_chain(i % 3), budget_ms=1e6)
            row = None
            for _ in range(500):
                row = fe.query_result(pub)
                if row is not None:
                    break
                time.sleep(0.02)
            solo = db.query([q_chain(i % 3)], caps=CAPS)
            assert row is not None and row["status"] == "OK"
            assert row["count"] == int(solo.counts[0])

        # a write routed through the SLB commits on the primary, ships
        # through the durable replication log, and replays on every
        # replica BEFORE the client sees COMMITTED (the ack barrier)
        wrow = fe.write_result(fe.submit_write([CreateVertex(
            "film", 999, {"year": 2030, "genre": 0, "gross": 0.0})]))
        assert wrow["status"] == "COMMITTED"
        g999 = wrow["gids"][0]
        wrow = fe.write_result(fe.submit_write([CreateEdge(
            g999, a_gid, "film.actor")]))
        assert wrow["status"] == "COMMITTED"
        want_now = sorted(want + [g999])
        # read-your-write on EVERY alive coordinator, no grace period
        # (count form: unaffected by the fleet's results cap)
        films_of_323 = q_chain(323, direction="in")
        base = int(db.query([films_of_323], caps=CAPS).counts[0])
        for cid in fe._alive():
            res = _worker_query(fe, cid, films_of_323)
            assert res["count"] == base + 1, f"worker {cid} stale"
        st = fe.cluster_stats()
        assert st["membership"]["epoch"] == 1
        assert st["membership"]["primary"] == 0
        assert st["replication"]["shipped_seq"] >= 2
        assert st["replication"]["max_lag"] == 0      # acked => applied
        assert fe.stats["replicated_waves"] >= 2
        # the wave records are durable in the ObjectStore WAL table
        assert len(fe.rlog.os.scan("g.waves")) >= 2
        assert fe.rlog.os.get_meta("g.wave_frontier", 0) >= 2

        # paged selects over the wire; the frontend is pin-of-record and
        # pushes its pins to every worker (fleet_pins) via heartbeats
        page, tok = fe.select_paged(SEL)
        owner = fe._tokmeta[tok]["cid"]
        read_ts = fe._tokmeta[tok]["read_ts"]
        fe.pump()                                     # pins reach workers
        got = list(page)

        # S2: kill the owner mid-pagination.  The takeover serves the
        # remaining pages; afterwards the released pin must actually
        # unblock MVCC GC on the survivors (a dead worker's continuations
        # must never wedge the fleet's garbage collection)
        fe.kill_worker(owner)
        while tok is not None:
            page, tok = fe.next_page(tok)
            got.extend(page)
        assert sorted(int(x) for x in got) == want_now
        assert not fe.db.active_query_ts              # pin-of-record clear
        fe.pump()                                     # empty pins fan out
        survivor = fe._alive()[0]
        hb = fe._rpc(survivor, {"op": "heartbeat", "pins": fe._pins()})
        assert hb["gc_ts"] >= read_ts                 # pin no longer holds

        # failover: the killed owner may have been the primary — either
        # way the fleet still serves writes, exactly one primary exists,
        # and the new commit is immediately readable on the survivor
        st = fe.cluster_stats()
        assert st["membership"]["epoch"] >= 2         # eviction fenced it
        wrow = fe.write_result(fe.submit_write([CreateVertex(
            "film", 998, {"year": 2031, "genre": 0, "gross": 0.0})]))
        assert wrow["status"] == "COMMITTED"
        res = _worker_query(fe, survivor, q_chain(0))
        solo = db.query([q_chain(0)], caps=CAPS)
        assert res["count"] == int(solo.counts[0])    # reads stay correct
        # the commit advanced the survivor's clock PAST the dead owner's
        # old pin: a dead coordinator's continuations never wedge MVCC GC
        hb = fe._rpc(survivor, {"op": "heartbeat", "pins": fe._pins()})
        assert hb["gc_ts"] > read_ts
        if owner == 0:
            assert fe.stats["failovers"] == 1
            assert st["membership"]["primary"] == fe.membership.primary != 0
            assert fe.rlog.os.get_meta("g.epoch", 0) >= 2   # durable fence
    finally:
        fe.close()
