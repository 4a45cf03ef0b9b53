"""End-to-end serving driver: the paper's production workload (§5-6).

Builds a film knowledge graph at configurable scale through the
transactional write path, then serves the paper's query classes (Q1-Q4
analogues) through a 2-coordinator :class:`A1Frontend` fleet — SLB-style
least-loaded routing over ONE shared store, SLO-budget wave scheduling,
owner-stamped continuation tokens, live updates through the
write-admission queue — and finishes with the cluster front's signature
trick: a coordinator is killed mid-pagination and the surviving worker
takes the continuation over at the pinned snapshot, invisibly to the
client.

    PYTHONPATH=src python examples/serve_kg.py [--films 300] [--batches 30]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core.query.executor import QueryCaps
from repro.core.writes import UpdateVertex
from repro.data.kg import build_film_kg
from repro.launch import jax_cache
from repro.launch.cluster import A1Frontend


def q1(did):
    return {"type": "director", "id": int(did),
            "_out_edge": {"type": "film.director",
                          "_target": {"type": "film",
                                      "_out_edge": {"type": "film.actor",
                                                    "_target": {
                                                        "type": "actor",
                                                        "select": "count"}}}}}


def q3(did, aid):
    """Star pattern (paper Q3): films by director X AND starring actor Y —
    fused into the same wave batch as the chains since A1QL v2."""
    return {"intersect": [
        {"type": "director", "id": int(did),
         "_out_edge": {"type": "film.director", "_target": {"type": "film"}}},
        {"type": "actor", "id": int(aid),
         "_in_edge": {"type": "film.actor", "_target": {"type": "film"}}}],
        "select": "count"}


def q4(aid):
    """Co-star stress query (paper Q4: 3-hop, large fan-out)."""
    return {"type": "actor", "id": int(aid),
            "_in_edge": {"type": "film.actor",
                         "_target": {"type": "film",
                                     "_out_edge": {"type": "film.actor",
                                                   "_target": {
                                                       "type": "actor",
                                                       "select": "count"}}}}}


def drain(fe, pubs):
    """Poll every submitted id to its stored result (flush closes waves)."""
    fe.flush()
    rows = [fe.query_result(p) for p in pubs]
    assert all(r is not None for r in rows)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--films", type=int, default=300)
    ap.add_argument("--actors", type=int, default=400)
    ap.add_argument("--batches", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=16)
    args = ap.parse_args()
    jax_cache.enable()

    print(f"building KG: {args.films} films / {args.actors} actors ...")
    t0 = time.time()
    kg = build_film_kg(n_films=args.films, n_actors=args.actors)
    db = kg.db
    print(f"  built in {time.time()-t0:.1f}s; commits={db.stats['commits']}")

    # 2 coordinators over ONE shared store (FastRestartCache rehydration);
    # a generous SLO budget keeps first-wave jit compiles from truncating
    # the warmup traffic — steady-state waves run far under it
    fe = A1Frontend(db, 2, caps=QueryCaps(frontier=2048, expand=16384,
                                          results=32),
                    read_batch=args.batch_size, budget_ms=60_000.0)
    rng = np.random.default_rng(0)

    for b in range(args.batches):
        # mixed chain + star batch: one fused wave program per batch shape
        dirs = rng.choice(kg.director_keys, args.batch_size)
        half = args.batch_size // 2
        pubs = [fe.submit_query(q1(d), qclass="Q1+Q3")
                for d in dirs[:half]]
        pubs += [fe.submit_query(q3(d, a), qclass="Q1+Q3")
                 for d, a in zip(dirs[half:],
                                 rng.choice(kg.actor_keys[:50],
                                            args.batch_size - half))]
        drain(fe, pubs)
        if b % 3 == 0:          # interleave the paper's stress query
            acts = rng.choice(kg.actor_keys[:50], args.batch_size)
            drain(fe, [fe.submit_query(q4(a), qclass="Q4") for a in acts])
        if b % 5 == 0:          # live updates via the write-admission queue:
            # staged at the admission snapshot, committed when the owning
            # coordinator's mutation wave closes — and visible to BOTH
            # coordinators at once, because the fleet shares one store
            f = int(rng.choice(kg.film_keys))
            gid, found = fe.db.lookup_vertex("film", f)
            if found:
                fe.submit_write([UpdateVertex(
                    gid, "film", {"gross": float(rng.uniform(1, 500))})])
    fe.flush()                  # close any wave still waiting on its budget

    # continuation handoff: kill the owning coordinator after page 1 and
    # let the survivor adopt the token at the pinned snapshot
    star = int(kg.actor_keys[0])
    sel = {"type": "actor", "id": star,
           "_in_edge": {"type": "film.actor",
                        "_target": {"type": "film", "select": ["key"]}}}
    page, token = fe.select_paged(sel)
    pages, rows = 1, len(page)
    owner = fe._tokmeta[token]["cid"] if token is not None else None
    if owner is not None:
        fe.kill_worker(owner)
        print(f"killed coordinator {owner} mid-pagination ...")
    while token is not None:
        page, token = fe.next_page(token)
        pages += 1
        rows += len(page)
    print(f"paged select for mega-actor {star}: {pages} page(s), "
          f"{rows} row(s), takeovers={fe.stats['takeovers']}")

    st = fe.cluster_stats()
    print("\nfrontend:", st["frontend"])
    print("budget spend (ms buckets):", st["budget_spend_ms"])
    for cid, ws in st["workers"].items():
        print(f"coordinator {cid}: admitted={ws['admitted']} "
              f"served={ws['served']} waves={ws['read_waves']}")
    print("db stats:", fe.db.stats)
    fe.close()


if __name__ == "__main__":
    main()
