#!/usr/bin/env python3
"""Bring-up smoke test: the graph database's main path on TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the store sharded over four chips

One chip: build a ``GraphDB`` at the per-shard capacities of the paper's
film knowledge graph (``configs/a1_kg.py`` FULL, one shard, full column
widths), load a seeded film KG through the write path, and serve through
``A1Server`` one mixed batch — 2-hop counts (Q1), 3-hop counts (Q4), star
intersections (Q3), and point reads of the films and actors the writes
touch — plus one ``Nearest`` probe and one paged select, with writes
(``UpdateVertex``, ``CreateEdge``) interleaved and one background
compaction closed while serving.  Every served answer must equal the
reference backend's answer at the same snapshot, every count and every
acknowledged write must match a numpy oracle built from the loader's own
op records, and one fused program must contain the Pallas kernels
(``tpu_custom_call``).

``--chips 4`` builds the KG on four shards with the same per-shard
capacities, the store placed across the four chips and kept there through
write waves and compactions, and serves the mixed batch through
``A1Server(use_spmd=True)`` in both budget modes — per-query answers
against the reference backend, shared-budget counts against the per-query
ones at the same snapshot, both against the oracle; it runs no other
phase.

Earlier lines are for orientation only (wall times include compilation).
The last line is ``{"ok": true, "device": {...}}``.  Any failed phase
raises, so the script exits non-zero; with no TPU it exits non-zero before
printing a result.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# films : actors : directors in build_film_kg's default ratios (200:300:40)
FILMS_PER_UNIT, ACTORS_PER_UNIT, DIRECTORS_PER_UNIT = 10, 15, 2
N_GENRES = 8
# cut of cap_v / cap_e / cap_idx that the store plus the largest program
# needs to fit one v5e chip (1 = the full a1_kg per-shard capacities)
CAP_CUT = 1


def log(msg: str) -> None:
    """Orientation line on stdout, mirrored to stderr so that a run which
    is stopped from outside still shows how far it got."""
    print(msg, flush=True)
    print(msg, file=sys.stderr, flush=True)


class Clock:
    """Per-phase wall time and the backend-compile seconds inside it."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.n_compiles = 0
        self.phases = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.n_compiles += 1
            if secs >= 10:
                log(f"  compiled {fun_name} in {secs:.1f}s")

    def phase(self, name: str):
        clock = self

        class _Span:
            def __enter__(self):
                self.t0, self.c0 = time.perf_counter(), clock.compile_s
                self.n0 = clock.n_compiles
                log(f"[{name}] ...")

            def __exit__(self, *exc):
                dt = time.perf_counter() - self.t0
                dc = clock.compile_s - self.c0
                clock.phases[name] = round(dt, 3)
                if exc[0] is None:
                    log(f"[{name}] {dt:.1f}s wall, {dc:.1f}s of it "
                        f"compiling {clock.n_compiles - self.n0} programs")
        return _Span()


# ---------------------------------------------------------------------------
# the workload: the paper's query classes over the film KG
# ---------------------------------------------------------------------------

def q1(dkey):
    """Q1, 2-hop count: actors who worked with director X."""
    return {"type": "director", "id": int(dkey),
            "_out_edge": {"type": "film.director", "_target": {
                "type": "film", "_out_edge": {"type": "film.actor", "_target": {
                    "type": "actor", "select": "count"}}}}}


def q4(akey):
    """Q4, 3-hop count: co-stars of actor Y (Y included)."""
    return {"type": "actor", "id": int(akey),
            "_in_edge": {"type": "film.actor", "_target": {
                "type": "film", "_out_edge": {"type": "film.actor", "_target": {
                    "type": "actor", "select": "count"}}}}}


def q3(dkey, akey):
    """Q3, star intersect: films by director X starring actor Y."""
    return {"intersect": [
        {"type": "director", "id": int(dkey), "_out_edge": {
            "type": "film.director", "_target": {"type": "film"}}},
        {"type": "actor", "id": int(akey), "_in_edge": {
            "type": "film.actor", "_target": {"type": "film"}}}],
        "select": "count"}


def films_of(akey):
    return {"type": "actor", "id": int(akey), "_in_edge": {
        "type": "film.actor", "_target": {"type": "film", "select": ["key"]}}}


def gross_of(dkey, fkey):
    """One film's gross, read through its director's edge list."""
    return {"type": "director", "id": int(dkey), "_out_edge": {
        "type": "film.director", "_target": {
            "type": "film", "select": ["gross"],
            "filter": {"attr": "key", "op": "==", "value": int(fkey)}}}}


class Oracle:
    """The KG as the loader wrote it, rebuilt from its op records."""

    def __init__(self):
        self.gid = {}                    # (vtype, key) -> gid
        self.key = {}                    # gid -> key
        self.dir_films, self.film_actors, self.actor_films = {}, {}, {}
        self.film_dir = {}
        self.gross = {}                  # film gid -> float32 gross

    def record(self, ops, res):
        from repro.core.writes import CreateEdge, CreateVertex
        for op, gid in zip(ops, res.gids):
            if isinstance(op, CreateVertex):
                self.gid[(op.vtype, op.key)] = gid
                self.key[gid] = op.key
                if op.vtype == "film":
                    self.gross[gid] = float(op.attrs["gross"])
            elif isinstance(op, CreateEdge):
                self.add_edge(op.src, op.dst, op.etype)

    def add_edge(self, src, dst, etype):
        if etype == "film.director":
            self.dir_films.setdefault(src, set()).add(dst)
            self.film_dir[dst] = src
        elif etype == "film.actor":
            self.film_actors.setdefault(src, set()).add(dst)
            self.actor_films.setdefault(dst, set()).add(src)

    def q1(self, dkey):
        films = self.dir_films.get(self.gid[("director", dkey)], ())
        return len(set().union(*[self.film_actors.get(f, ()) for f in films]))

    def q4(self, akey):
        films = self.actor_films.get(self.gid[("actor", akey)], ())
        return len(set().union(*[self.film_actors.get(f, ()) for f in films]))

    def q3(self, dkey, akey):
        return len(self.dir_films.get(self.gid[("director", dkey)], set())
                   & self.actor_films.get(self.gid[("actor", akey)], set()))

    def fits(self, films, bound: int) -> bool:
        """Whether a traversal from these films stays inside the caps (no
        fast-fail): its frontier and raw span at most ``bound``."""
        span = sum(len(self.film_actors.get(f, ())) + 1 for f in films)
        return 0 < len(films) <= bound and span <= bound


def pick_keys(kg, oracle, bound, rng, n):
    """Directors and actors whose traversals fit the serving caps."""
    dirs = [int(k) for k in rng.permutation(kg.director_keys)
            if oracle.fits(oracle.dir_films.get(
                oracle.gid[("director", int(k))], ()), bound)][:n]
    acts = [int(k) for k in rng.permutation(kg.actor_keys)
            if oracle.fits(oracle.actor_films.get(
                oracle.gid[("actor", int(k))], ()), bound)][:n]
    if len(dirs) < n or len(acts) < n:
        raise RuntimeError("too few directors/actors inside the query caps")
    return dirs, acts


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def store_config(n_shards: int, n_films: int):
    """a1_kg FULL per-shard capacities (cut by CAP_CUT), vector index sized
    to the films plus room for updated versions."""
    from repro.configs.a1_kg import FULL
    return dataclasses.replace(
        FULL, n_shards=n_shards, cap_v=FULL.cap_v // CAP_CUT,
        cap_e=FULL.cap_e // CAP_CUT, cap_idx=FULL.cap_idx // CAP_CUT,
        cap_vec=n_films + 4096)


def load_kg(db, scale: int, seed: int):
    """Load the seeded film KG through ``GraphDB.write`` and record every
    op for the oracle."""
    from repro.data.kg import build_film_kg
    oracle = Oracle()
    write = db.write

    shown = [time.perf_counter()]

    def recording_write(ops, **kw):
        res = write(ops, **kw)
        oracle.record(ops, res)
        if time.perf_counter() - shown[0] > 30:
            shown[0] = time.perf_counter()
            log(f"  {len(oracle.key)} vertices written, "
                f"{shown[0] - t0:.0f}s")
        return res

    db.write = recording_write
    t0 = time.perf_counter()
    try:
        kg = build_film_kg(n_films=FILMS_PER_UNIT * scale,
                           n_actors=ACTORS_PER_UNIT * scale,
                           n_directors=DIRECTORS_PER_UNIT * scale,
                           n_genres=N_GENRES, seed=seed, db=db)
    finally:
        del db.write
    dt = time.perf_counter() - t0
    n_v = len(oracle.key)
    n_e = (sum(map(len, oracle.dir_films.values()))
           + sum(map(len, oracle.film_actors.values())) + kg.n_films)
    log(f"loaded {n_v} vertices, {n_e} edges in {dt:.1f}s: "
        f"{n_v / dt:.0f} vertices/s, {(n_v + n_e) / dt:.0f} ops/s "
        f"(write path, compaction and compiles included)")
    return kg, oracle


def same(a, b, what):
    """Served answer == reference answer, field by field."""
    import numpy as np
    for f in ("counts", "rows_gid", "failed_q"):
        x, y = getattr(a, f), getattr(b, f)
        if (x is None) != (y is None) or (
                x is not None and not np.array_equal(np.asarray(x),
                                                     np.asarray(y))):
            raise AssertionError(f"{what}: {f} differs from the reference")
    for k in (a.rows or {}):
        if not np.array_equal(np.asarray(a.rows[k]), np.asarray(b.rows[k])):
            raise AssertionError(f"{what}: rows[{k}] differ")


def serve_and_check(server, db, probe, what):
    """One served batch at a pinned snapshot, checked against the ref
    backend at that snapshot and against the oracle."""
    import numpy as np
    ts = db.snapshot_ts()
    res = server.execute(probe.batch, qclass=what, read_ts=ts)
    mesh = server.mesh if server.use_spmd else None
    ref = db.query(probe.batch, caps=server.caps, read_ts=ts, mesh=mesh,
                   budget=server.budget, backend="ref")
    same(res, ref, what)
    if res.failed_q is not None and np.any(res.failed_q):
        raise AssertionError(f"{what}: {int(np.sum(res.failed_q))} queries "
                             "fast-failed inside the caps")
    probe.check(res, what)
    return res


class Probe:
    """One mixed batch — Q1/Q4/Q3 counts, the gross of some films and the
    films of some actors — and what the oracle says it must answer.  The
    films and actors are those the write phase touches, so the same batch
    reads every acknowledged write back."""

    def __init__(self, oracle, dirs=(), acts=(), films=(), actors=(),
                 extra=()):
        self.oracle, self.films, self.actors = oracle, list(films), actors
        self.batch = ([q1(d) for d in dirs] + [q4(a) for a in acts]
                      + [q3(d, a) for d, a in zip(dirs, acts)])
        self.count_want = lambda: (
            [oracle.q1(d) for d in dirs] + [oracle.q4(a) for a in acts]
            + [oracle.q3(d, a) for d, a in zip(dirs, acts)])
        self.n = len(self.batch)
        self.batch += [gross_of(oracle.key[oracle.film_dir[f]],
                                oracle.key[f]) for f in self.films]
        self.batch += [films_of(oracle.key[a]) for a in actors]
        self.batch += list(extra)           # checked against ref only

    def check(self, res, what):
        import numpy as np
        o, n, nf = self.oracle, self.n, len(self.films)
        got = np.asarray(res.counts[:n] if n else [], np.int64)
        want = np.asarray(self.count_want(), np.int64)
        if not np.array_equal(got, want):
            i = int(np.flatnonzero(got != want)[0])
            raise AssertionError(f"{what}: query {i} counted {got[i]}, "
                                 f"oracle {want[i]}")
        if nf:
            (col,) = [v for k, v in res.rows.items() if k[0] == "f32"]
            if not np.array_equal(col[n:n + nf, 0], np.float32(
                    [o.gross[f] for f in self.films])):
                raise AssertionError(f"{what}: a film's gross differs "
                                     "from the last acknowledged write")
        for j, a in enumerate(self.actors):
            row = res.rows_gid[n + nf + j]
            if set(row[row >= 0].tolist()) != o.actor_films[a]:
                raise AssertionError(f"{what}: actor {a}'s films differ "
                                     "from the acknowledged edges")


def check_kernels_in_program(db, server, batch):
    """Lower the fused program of one batch and check that the Pallas
    kernels are in it (none gave way to the reference path)."""
    import jax
    import jax.numpy as jnp
    from repro.core import backend
    from repro.core.query import engine, planner
    lowered = engine._normalize_parsed(db, batch, None)
    eff = [lo.hints.apply(server.caps) for lo in lowered]
    caps_g, idxs = planner._fusion_groups(lowered, eff)[0]
    plans = tuple(lowered[i].plan for i in idxs)
    fn = planner.compile_batch(db.cfg, plans, caps_g, backend.resolve(None),
                               planner.delta_window(db),
                               planner.index_window(db))
    keys = jnp.asarray([k for i in idxs for k in lowered[i].keys], jnp.int32)
    q = len(idxs)
    text = fn.lower(db.store, keys, jnp.ones(keys.shape, bool),
                    jnp.zeros((q,), jnp.int32),
                    jnp.full((q,), -1, jnp.int32)).as_text()
    n = text.count("tpu_custom_call")
    if n == 0:
        raise AssertionError("fused program holds no Pallas kernel")
    log(f"fused program of {q} queries: {n} tpu_custom_call sites "
        f"(jax {jax.__version__})")


def plan_writes(oracle, dirs, acts, caps, rng, n=8):
    """Gross updates of films by the first directors, and new cast edges
    from those films to actors whose films fit one result window (so the
    edge reads back in one select row)."""
    import numpy as np
    films = sorted(set().union(*[oracle.dir_films[oracle.gid[
        ("director", d)]] for d in dirs[:n]]))[:n]
    new_gross = {f: float(np.float32(rng.uniform(1, 500))) for f in films}
    few = [oracle.gid[("actor", a)] for a in acts[n:]
           if len(oracle.actor_films.get(oracle.gid[("actor", a)], ()))
           < caps.results]
    new_edges = [(f, a) for f, a in zip(films, few)
                 if a not in oracle.film_actors.get(f, ())]
    return new_gross, new_edges


def write(server, oracle, new_gross, new_edges):
    """Submit the writes through the server, require every one to be
    acknowledged, then fold them into the oracle."""
    from repro.core.writes import CreateEdge, UpdateVertex
    # two waves: an edge create reads its endpoints, so in one wave with an
    # update of the same film it would lose OCC (first wins)
    waves = [[UpdateVertex(f, "film", {"gross": g})
              for f, g in new_gross.items()],
             [CreateEdge(f, a, "film.actor") for f, a in new_edges]]
    n_acks = 0
    for ops in filter(None, waves):
        wids = [server.submit_write([op]) for op in ops]
        server.flush_writes()
        for w in wids:
            r = server.write_result(w)
            if r is None or r["status"] != "COMMITTED":
                raise AssertionError(f"write not acknowledged: {r}")
        n_acks += len(wids)
    for f, a in new_edges:
        oracle.add_edge(f, a, "film.actor")
    oracle.gross.update(new_gross)
    log(f"{n_acks} writes acknowledged ({len(new_gross)} updates, "
        f"{len(new_edges)} edges)")


def one_chip(jax, clock, args):
    import numpy as np
    from repro.configs.a1_kg import _QCAPS
    from repro.core.graphdb import GraphDB
    from repro.core.query.executor import QueryCaps
    from repro.launch.serve import A1Server

    dev = jax.devices()[0]
    n_films = FILMS_PER_UNIT * args.scale
    cfg = store_config(1, n_films)
    with clock.phase("store"):
        db = GraphDB(cfg)
        jax.block_until_ready(db.store)
    gib = db.store.nbytes() / 2**30
    log(f"store: cap_v={cfg.cap_v} cap_e={cfg.cap_e} cap_idx={cfg.cap_idx} "
        f"cap_vec={cfg.cap_vec} d_f32={cfg.d_f32} d_i32={cfg.d_i32} "
        f"(cap cut 1/{CAP_CUT}): {gib:.3f} GiB on the device")
    with clock.phase("load"):
        kg, oracle = load_kg(db, args.scale, args.seed)
        db.vector_index("film")
    caps = QueryCaps(**_QCAPS)
    rng = np.random.default_rng(args.seed)
    dirs, acts = pick_keys(kg, oracle, caps.expand // 2, rng, 16)
    new_gross, new_edges = plan_writes(oracle, dirs, acts, caps, rng)
    probe = Probe(oracle, dirs, acts, new_gross, [a for _, a in new_edges])
    server = A1Server(db, caps=caps, budget="per-query", budget_ms=1e9,
                      write_batch=64)

    with clock.phase("serve: mixed batch + nearest + paged select"):
        serve_and_check(server, db, probe, "mixed")
        vec = [float(rng.uniform(1, 500))] + [0.0] * (cfg.d_f32 - 1)
        near = [{"nearest": {"type": "film", "vector": vec, "k": 8},
                 "select": ["key"]}]
        res = serve_and_check(server, db, Probe(oracle, extra=near),
                              "nearest")
        if int(np.sum(res.rows_gid[0] >= 0)) != 8:
            raise AssertionError("nearest returned fewer than k films")
        # an actor whose films fill more than one page but one result window
        star = next(int(a) for a in rng.permutation(kg.actor_keys)
                    if server.page < len(oracle.actor_films.get(
                        oracle.gid[("actor", int(a))], ())) <= caps.results)
        ts = db.snapshot_ts()
        page, token = server.select_paged(films_of(star), read_ts=ts)
        got = list(page)
        while token is not None:
            page, token = server.next_page(token)
            got += list(page)
        ref = db.query([films_of(star)], caps=caps, read_ts=ts,
                       backend="ref").rows_gid[0]
        want_f = sorted(oracle.actor_films[oracle.gid[("actor", star)]])
        if not (sorted(int(g) for g in got)
                == sorted(int(g) for g in ref if g >= 0) == want_f):
            raise AssertionError("paged select differs from ref/oracle")
        log(f"paged select: {len(got)} rows in pages of {server.page}")

    with clock.phase("serve: interleaved writes"):
        write(server, oracle, new_gross, new_edges)

    with clock.phase("serve: background compaction"):
        # the shadow is built while the store serves; the probe reads
        # every acknowledged write back during and after the handoff
        handle = db.begin_compaction(("edges", "index"))
        serve_and_check(server, db, probe, "during compaction")
        done = db.try_handoff(handle)
        if not all(done.values()):
            raise AssertionError(f"compaction handoff refused: {done}")
        serve_and_check(server, db, probe, "after compaction")
        check_kernels_in_program(db, server, probe.batch)
    return dev, 1


def four_chips(jax, clock, args):
    """The same KG on four shards, the store spread over four chips, served
    through SPMD programs in both budget modes."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.a1_kg import _QCAPS
    from repro.core.graphdb import GraphDB
    from repro.core.query.executor import QueryCaps
    from repro.dist import compat
    from repro.launch.serve import A1Server

    axes = ("data", "model")
    mesh = compat.make_mesh((4, 1), axes)
    cfg = store_config(4, FILMS_PER_UNIT * args.scale)

    def spread(what):
        bad = [f for f, a in vars(db.store).items()
               if len(a.sharding.device_set) != 4]
        if bad:
            raise AssertionError(f"{what}: leaves not on 4 devices: {bad}")
        log(f"{what}: all {len(vars(db.store))} store leaves span 4 devices")

    with clock.phase("store"):
        db = GraphDB(cfg, sharding=NamedSharding(mesh, P(axes)))
        jax.block_until_ready(db.store)
    log(f"store: 4 shards x (cap_v={cfg.cap_v} cap_e={cfg.cap_e} "
        f"cap_idx={cfg.cap_idx} cap_vec={cfg.cap_vec}), "
        f"{db.store.nbytes() / 4 / 2**30:.3f} GiB per chip")
    spread("after allocation")
    with clock.phase("load"):
        kg, oracle = load_kg(db, args.scale, args.seed)
    spread("after load")
    caps = QueryCaps(**_QCAPS)
    rng = np.random.default_rng(args.seed)
    # routed frontiers must fit the per-destination buckets as well
    dirs, acts = pick_keys(kg, oracle, caps.bucket, rng, 16)
    new_gross, new_edges = plan_writes(oracle, dirs, acts, caps, rng)
    probe = Probe(oracle, dirs, acts, new_gross, [a for _, a in new_edges])
    with clock.phase("serve: per-query budget, writes"):
        server = A1Server(db, caps=caps, use_spmd=True, mesh=mesh,
                          budget="per-query", budget_ms=1e9, write_batch=64)
        serve_and_check(server, db, probe, "spmd per-query")
        write(server, oracle, new_gross, new_edges)
        spread("after writes")
        # fold the writes in, so the next batch runs the programs above
        db.run_compaction()
        db.run_index_compaction()
        spread("after compaction")
        serve_and_check(server, db, probe, "spmd per-query after writes")
    with clock.phase("serve: shared budget"):
        # per-query answers were just checked against the ref backend; the
        # shared pool must give the same counts at the same snapshot (it
        # may differ only by fast-fail flags, and none is allowed)
        counts = Probe(oracle, dirs, acts)
        ts = db.snapshot_ts()
        per_query = server.execute(counts.batch, read_ts=ts)
        shared = A1Server(db, caps=caps, use_spmd=True, mesh=mesh,
                          budget="shared", budget_ms=1e9)
        res = shared.execute(counts.batch, read_ts=ts)
        same(res, per_query, "spmd shared")
        counts.check(res, "spmd shared")
    return jax.devices()[0], 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--scale", type=int, default=2_000,
                    help="KG size: scale x (10 films, 15 actors, 2 directors)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=1140.0,
                    help="seconds after which every thread's stack is "
                         "dumped to stderr and the run exits non-zero")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(args.deadline, exit=True)
    try:
        import jax
        from repro.launch import jax_cache
    except ImportError as e:
        print(f"chip_smoke: the repository is not importable ({e})",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices)",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    log(f"cache: {jax_cache.enable()}")
    from repro.core import backend
    be = backend.resolve(None)
    if be != backend.Backend("pallas", interpret=False):
        raise AssertionError(f"backend resolved to {be}, not compiled pallas")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"backend {be}")
    clock = Clock(jax)
    t0 = time.perf_counter()
    run = one_chip if args.chips == 1 else four_chips
    dev, count = run(jax, clock, args)
    for d in jax.devices()[:count]:
        st = d.memory_stats() or {}
        log(f"{d}: bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")
    log(f"total {time.perf_counter() - t0:.1f}s, compiling "
        f"{clock.compile_s:.1f}s; phases {json.dumps(clock.phases)}")
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
