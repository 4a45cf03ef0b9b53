"""Core GraphDB behaviour: CRUD, MVCC snapshots, OCC, compaction, cascade."""
import numpy as np
import pytest

from repro.core.addressing import StoreConfig
from repro.core.graphdb import CapacityError, GraphDB
from repro.core.tasks import TaskQueue, compaction_task, vacuum_task


def small_db(**kw):
    cfg = StoreConfig(n_shards=4, cap_v=64, cap_e=512, cap_delta=128,
                      cap_idx=128, cap_idx_delta=64, d_f32=2, d_i32=2, **kw)
    db = GraphDB(cfg)
    db.vertex_type("actor", f_attrs=("rating",), i_attrs=("dob",))
    db.vertex_type("film", f_attrs=("gross",), i_attrs=("year",))
    db.edge_type("film.actor")
    return db


def test_create_and_get_vertex():
    db = small_db()
    gid = db.create_vertex("actor", 7, {"rating": 4.5, "dob": 1956})
    v = db.get_vertex("actor", 7)
    assert v["gid"] == gid and v["rating"] == 4.5 and v["dob"] == 1956


def test_duplicate_key_rejected():
    db = small_db()
    db.create_vertex("actor", 7)
    with pytest.raises(ValueError):
        db.create_vertex("actor", 7)


def test_same_key_different_type_ok():
    db = small_db()
    db.create_vertex("actor", 7)
    db.create_vertex("film", 7)
    assert db.get_vertex("actor", 7) is not None
    assert db.get_vertex("film", 7) is not None


def test_edges_both_halves():
    db = small_db()
    f = db.create_vertex("film", 1)
    a = db.create_vertex("actor", 2)
    db.create_edge(f, a, "film.actor")
    assert db.get_edges(f, direction="out") == [(a, 0)]
    assert db.get_edges(a, direction="in") == [(f, 0)]


def test_duplicate_edge_rejected():
    db = small_db()
    f = db.create_vertex("film", 1)
    a = db.create_vertex("actor", 2)
    db.create_edge(f, a, "film.actor")
    with pytest.raises(ValueError):
        db.create_edge(f, a, "film.actor")


def test_snapshot_isolation_on_update():
    db = small_db()
    a = db.create_vertex("actor", 1, {"rating": 1.0})
    ts0 = db.snapshot_ts()
    db.update_vertex(a, "actor", {"rating": 2.0})
    f_old, _ = db._read_data_host(a, ts0)
    f_new, _ = db._read_data_host(a, db.snapshot_ts())
    assert f_old[0] == 1.0 and f_new[0] == 2.0


def test_snapshot_isolation_on_delete():
    db = small_db()
    a = db.create_vertex("actor", 1)
    ts0 = db.snapshot_ts()
    db.delete_vertex(a)
    _, _, alive_old = db._read_header_host(a, ts0)
    _, _, alive_new = db._read_header_host(a, db.snapshot_ts())
    assert alive_old and not alive_new


def test_occ_write_write_abort():
    db = small_db()
    a = db.create_vertex("actor", 1)
    t1, t2 = db.create_transaction(), db.create_transaction()
    db.update_vertex(a, "actor", {"rating": 1.0}, txn=t1)
    db.update_vertex(a, "actor", {"rating": 2.0}, txn=t2)
    assert db.commit_many([t1, t2]) == ["COMMITTED", "ABORTED"]
    assert db.get_vertex("actor", 1)["rating"] == 1.0


def test_occ_stale_read_abort():
    db = small_db()
    a = db.create_vertex("actor", 1)
    t1 = db.create_transaction()
    db.update_vertex(a, "actor", {"rating": 5.0}, txn=t1)   # reads at old ts
    db.update_vertex(a, "actor", {"rating": 9.0})           # concurrent commit
    assert db.commit(t1) == "ABORTED"
    assert db.get_vertex("actor", 1)["rating"] == 9.0


def test_atomic_multi_op_txn():
    db = small_db()
    t = db.create_transaction()
    f = db.create_vertex("film", 1, txn=t)
    a = db.create_vertex("actor", 2, txn=t)
    t.create_e.append((f, a, 0))       # stage edge within same txn
    assert db.commit(t) == "COMMITTED"
    assert db.get_edges(f) == [(a, 0)]


def test_compaction_preserves_edges():
    db = small_db()
    f = db.create_vertex("film", 1)
    actors = [db.create_vertex("actor", 10 + i) for i in range(20)]
    t = db.create_transaction()
    for a in actors:
        db.create_edge(f, a, "film.actor", txn=t)
    db.commit(t)
    before = sorted(db.get_edges(f))
    db.run_compaction()
    assert sorted(db.get_edges(f)) == before
    assert int(db.dl_count.max()) == 0


def test_auto_compaction_on_log_pressure():
    db = small_db()
    f = db.create_vertex("film", 1)
    # cap_delta=128 per shard; f's out-log fills past it (all on f's shard)
    for i in range(200):
        a = db.create_vertex("actor", 100 + i)
        db.create_edge(f, a, "film.actor")
    assert len(db.get_edges(f)) == 200
    assert db.stats["compactions"] >= 1


def test_delete_vertex_cascades_no_dangling():
    db = small_db()
    f1 = db.create_vertex("film", 1)
    f2 = db.create_vertex("film", 2)
    a = db.create_vertex("actor", 3)
    db.create_edge(f1, a, "film.actor")
    db.create_edge(f2, a, "film.actor")
    db.delete_vertex(a)
    assert db.get_edges(f1) == [] and db.get_edges(f2) == []
    _, found = db.lookup_vertex("actor", 3)
    assert not found


def test_delete_then_reinsert_same_key():
    db = small_db()
    a = db.create_vertex("actor", 1, {"rating": 1.0})
    db.delete_vertex(a)
    b = db.create_vertex("actor", 1, {"rating": 2.0})
    assert b != a
    assert db.get_vertex("actor", 1)["rating"] == 2.0


def test_index_compaction_then_lookup():
    db = small_db()
    gids = [db.create_vertex("actor", i) for i in range(30)]
    db.run_index_compaction()
    for i, g in enumerate(gids):
        got, found = db.lookup_vertex("actor", i)
        assert found and got == g


def test_vacuum_reclaims_slots():
    db = small_db()
    gids = [db.create_vertex("actor", i) for i in range(10)]
    for g in gids[:5]:
        db.delete_vertex(g)
    db.run_compaction()
    db.run_index_compaction()
    n = db.vacuum()
    assert n == 5
    # reclaimed slots are reusable
    for i in range(5):
        db.create_vertex("actor", 100 + i)


def test_task_queue_delete_type_workflow():
    db = small_db()
    for i in range(10):
        db.create_vertex("actor", i)
    from repro.core.tasks import delete_type_task
    tq = TaskQueue(db)
    tq.enqueue(delete_type_task("actor", chunk=3))
    tq.drain()
    for i in range(10):
        _, found = db.lookup_vertex("actor", i)
        assert not found


def test_capacity_fastfail_vertex_store():
    cfg = StoreConfig(n_shards=2, cap_v=4, cap_e=64, cap_delta=32,
                      cap_idx=32, cap_idx_delta=16, d_f32=1, d_i32=1)
    db = GraphDB(cfg)
    db.vertex_type("t")
    for i in range(8):
        db.create_vertex("t", i)
    with pytest.raises(CapacityError):
        db.create_vertex("t", 99)


def test_locality_hint_allocates_same_shard():
    db = small_db()
    a = db.create_vertex("actor", 1)
    b = db.create_vertex("actor", 2, hint=a)
    assert a % db.cfg.n_shards == b % db.cfg.n_shards


def test_catalog_proxy_cache_ttl():
    from repro.core.catalog import Catalog
    t = [0.0]
    cat = Catalog(proxy_ttl=10.0, clock=lambda: t[0])
    cat.create_tenant("x")
    cat.create_graph("x", "g")
    vt = cat.create_vertex_type("x", "g", "v", max_f_cols=1, max_i_cols=1)
    p1 = cat.proxy("x", "g", "v", "v")
    t[0] = 5.0
    assert cat.proxy("x", "g", "v", "v") is p1          # within TTL
    t[0] = 15.0
    assert cat.proxy("x", "g", "v", "v") is p1          # version unchanged
    cat.create_edge_type("x", "g", "e")                 # bump version
    t[0] = 30.0
    assert cat.proxy("x", "g", "v", "v") is vt          # refreshed object


def test_loader_draws_distinct_zipf_picks():
    """The film-KG loader's cast sampler: distinct picks per film, as many
    as asked, skewed toward the popular end like the weights."""
    from repro.data.kg import _cdf, _successive
    w = 1.0 / np.power(np.arange(1, 51), 1.5)
    sizes = np.random.default_rng(0).integers(2, 8, size=4000)
    picks = _successive(np.random.default_rng(1), _cdf(w), sizes)
    assert [len(p) for p in picks] == sizes.tolist()
    assert all(len(set(p)) == len(p) for p in picks)
    hits = np.bincount(np.concatenate(picks), minlength=50)
    assert hits[0] > hits[10] > hits[49]
    with pytest.raises(ValueError):
        _successive(np.random.default_rng(0), _cdf(w[:3]), np.array([4]))
