"""A1QL query engine vs a networkx oracle + hypothesis property tests."""
import networkx as nx
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (CI installs it)")
from hypothesis import given, settings, strategies as st

from repro.core.addressing import StoreConfig
from repro.core.graphdb import GraphDB
from repro.core.query.executor import QueryCaps

CAPS = QueryCaps(frontier=512, expand=4096, results=32)


def film_db(seed=0, n_dir=4, n_film=15, n_act=20):
    cfg = StoreConfig(n_shards=4, cap_v=256, cap_e=4096, cap_delta=512,
                      cap_idx=512, cap_idx_delta=256, d_f32=2, d_i32=2)
    db = GraphDB(cfg)
    db.vertex_type("director")
    db.vertex_type("actor")
    db.vertex_type("film", f_attrs=("gross",), i_attrs=("year", "genre"))
    db.edge_type("film.director")
    db.edge_type("film.actor")
    rng = np.random.default_rng(seed)
    G = nx.MultiDiGraph()
    dirs = [db.create_vertex("director", i) for i in range(n_dir)]
    films, acts = [], []
    for i in range(n_film):
        year, genre = 1990 + int(rng.integers(30)), int(rng.integers(3))
        films.append(db.create_vertex("film", 100 + i,
                                      {"year": year, "genre": genre}))
        G.add_node(("film", 100 + i), year=year, genre=genre)
    acts = [db.create_vertex("actor", 300 + i) for i in range(n_act)]
    t = db.create_transaction()
    for i, f in enumerate(films):
        d = int(rng.integers(n_dir))
        db.create_edge(dirs[d], f, "film.director", txn=t)
        G.add_edge(("director", d), ("film", 100 + i), key="film.director")
        for a in rng.choice(n_act, size=int(rng.integers(1, 7)),
                            replace=False):
            db.create_edge(f, acts[a], "film.actor", txn=t)
            G.add_edge(("film", 100 + i), ("actor", 300 + int(a)),
                       key="film.actor")
    assert db.commit(t) == "COMMITTED"
    return db, G


def oracle_two_hop(G, start, e1, e2, genre=None):
    out = set()
    for _, f, k1 in G.out_edges(start, keys=True):
        if k1 != e1:
            continue
        if genre is not None and G.nodes[f].get("genre") != genre:
            continue
        for _, a, k2 in G.out_edges(f, keys=True):
            if k2 == e2:
                out.add(a)
    return out


def q1(did, genre=None, select="count"):
    tgt = {"type": "film",
           "_out_edge": {"type": "film.actor",
                         "_target": {"type": "actor", "select": select}}}
    if genre is not None:
        tgt["filter"] = {"attr": "genre", "op": "==", "value": genre}
    return {"type": "director", "id": did,
            "_out_edge": {"type": "film.director", "_target": tgt}}


def test_two_hop_counts_match_oracle():
    db, G = film_db()
    res = db.query([q1(d) for d in range(4)], caps=CAPS)
    assert not res.failed
    for d in range(4):
        assert res.counts[d] == len(
            oracle_two_hop(G, ("director", d), "film.director", "film.actor"))


def test_two_hop_with_filter_matches_oracle():
    db, G = film_db(seed=3)
    res = db.query([q1(d, genre=1) for d in range(4)], caps=CAPS)
    for d in range(4):
        assert res.counts[d] == len(
            oracle_two_hop(G, ("director", d), "film.director", "film.actor",
                           genre=1))


def test_reverse_traversal_matches_oracle():
    db, G = film_db(seed=5)
    q = {"type": "actor", "id": 305,
         "_in_edge": {"type": "film.actor",
                      "_target": {"type": "film", "select": ["key"]}}}
    res = db.query([q], caps=CAPS)
    got = sorted(int(x) for x in res.rows[("key", 0)][0] if x >= 0)
    want = sorted(f[1] for f, _, k in G.in_edges(("actor", 305), keys=True)
                  if k == "film.actor")
    assert got == want


def test_intersection_star_pattern():
    db, G = film_db(seed=7)
    # films by director 0 AND starring actor 300+i for each i: star join (Q3)
    for aid in range(5):
        q = {"intersect": [
            {"type": "director", "id": 0,
             "_out_edge": {"type": "film.director",
                           "_target": {"type": "film"}}},
            {"type": "actor", "id": 300 + aid,
             "_in_edge": {"type": "film.actor",
                          "_target": {"type": "film"}}}],
            "select": "count"}
        res = db.query([q], caps=CAPS)
        by_dir = {f for _, f, k in G.out_edges(("director", 0), keys=True)
                  if k == "film.director"}
        by_act = {f for f, _, k in G.in_edges(("actor", 300 + aid), keys=True)
                  if k == "film.actor"}
        assert res.counts[0] == len(by_dir & by_act)


@pytest.mark.parametrize("select", ["count", ["key"]])
def test_uniform_batch_scans_only_the_filled_delta_window(select):
    """A one-shape batch scans only the filled prefix of each shard's edge
    delta log, and answers exactly as a scan of the whole log."""
    import jax.numpy as jnp
    from repro.core import backend
    from repro.core.query import engine, planner
    from repro.core.query.executor import _to_result, compile_query
    db, G = film_db()
    assert planner.delta_window(db) < db.cfg.cap_delta
    docs = [q1(d, select=select) for d in range(4)]
    got = db.query(docs, caps=CAPS)
    lowered = engine._normalize_parsed(db, docs, None)
    fn = compile_query(db.cfg, lowered[0].plan, CAPS, len(docs),
                       backend.REF, xwin=planner.index_window(db))
    full = _to_result(lowered[0].plan, fn(
        db.store, jnp.asarray([lo.keys[0] for lo in lowered], jnp.int32),
        jnp.ones((len(docs),), bool), jnp.int32(db.snapshot_ts())))
    for f in ("counts", "rows_gid"):
        np.testing.assert_array_equal(getattr(got, f), getattr(full, f))
    if select == "count":
        want = [len(oracle_two_hop(G, ("director", d), "film.director",
                                   "film.actor")) for d in range(4)]
        assert got.counts.tolist() == want


def test_missing_start_vertex_yields_zero():
    db, _ = film_db()
    res = db.query([q1(999)], caps=CAPS)
    assert res.counts[0] == 0 and not res.failed


def test_three_hop_query():
    db, G = film_db(seed=11)
    # co-star query (paper Q4 shape): actor -> films -> actors
    q = {"type": "actor", "id": 301,
         "_in_edge": {"type": "film.actor",
                      "_target": {"type": "film",
                                  "_out_edge": {"type": "film.actor",
                                                "_target": {"type": "actor",
                                                            "select": "count"}}}}}
    res = db.query([q], caps=CAPS)
    films = {f for f, _, k in G.in_edges(("actor", 301), keys=True)
             if k == "film.actor"}
    co = set()
    for f in films:
        co |= {a for _, a, k in G.out_edges(f, keys=True) if k == "film.actor"}
    assert res.counts[0] == len(co)


def test_fast_fail_on_overflow():
    db, _ = film_db()
    tiny = QueryCaps(frontier=8, expand=4, results=4)
    res = db.query([q1(0)], caps=tiny)
    assert res.failed          # fast-fail, not wrong answers (§3.4)


def test_queries_see_snapshot_despite_updates():
    db, G = film_db()
    res0 = db.query([q1(0)], caps=CAPS)
    # mutate: delete an actor that was reachable
    a_gid, found = db.lookup_vertex("actor", 300)
    if found:
        db.delete_vertex(a_gid)
    res1 = db.query([q1(0)], caps=CAPS)
    # old result unchanged, new result consistent with mutation
    assert res1.counts[0] in (res0.counts[0], res0.counts[0] - 1)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_property_counts_match_oracle(seed):
    db, G = film_db(seed=seed, n_dir=3, n_film=10, n_act=12)
    res = db.query([q1(d) for d in range(3)], caps=CAPS)
    for d in range(3):
        assert res.counts[d] == len(
            oracle_two_hop(G, ("director", d), "film.director", "film.actor"))
