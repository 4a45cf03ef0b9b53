"""Synthetic film/entertainment knowledge graph (the paper's §6 dataset).

The evaluation graph in the paper comes from a film knowledge base
(3.7 B vertices, 6.2 B edges, ~220-byte payloads, heavy degree skew — some
vertices exceed 10 M edges).  This generator reproduces its *shape* at a
configurable scale: directors/actors/films/genres with Zipf-skewed degrees,
loaded through the real transactional write path (``GraphDB.write`` batches
of mutation-op records), so benchmarks exercise the same code a production
load would.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.addressing import StoreConfig
from repro.core.graphdb import GraphDB
from repro.core.writes import CreateEdge, CreateVertex


def _cdf(weights: np.ndarray) -> np.ndarray:
    c = np.cumsum(weights)
    return c / c[-1]


def _successive(rng, cdf: np.ndarray, sizes: np.ndarray) -> list:
    """Per row, ``sizes[r]`` distinct indices drawn one after another with
    the weights behind ``cdf``, a repeat redrawn — the law of numpy's
    ``choice(replace=False, p=...)`` without its O(n) work per row."""
    if len(sizes) and sizes.max() > len(cdf):
        raise ValueError("more distinct draws than weighted items")
    m = 4 * int(sizes.max(initial=1))
    cand = cdf.searchsorted(rng.random((len(sizes), m)), side="right")
    out = []
    for row, k in zip(cand.tolist(), sizes.tolist()):
        picks = list(dict.fromkeys(row))[:k]
        while len(picks) < k:
            x = int(cdf.searchsorted(rng.random(), side="right"))
            if x not in picks:
                picks.append(x)
        out.append(picks)
    return out


@dataclasses.dataclass
class FilmKG:
    db: GraphDB
    n_directors: int
    n_actors: int
    n_films: int
    n_genres: int
    director_keys: np.ndarray
    actor_keys: np.ndarray
    film_keys: np.ndarray
    genre_keys: np.ndarray


def build_film_kg(*, n_films: int = 200, n_actors: int = 300,
                  n_directors: int = 40, n_genres: int = 8,
                  actors_per_film: tuple = (2, 8), seed: int = 0,
                  cfg: StoreConfig = None, db: GraphDB = None,
                  zipf_a: float = 1.5) -> FilmKG:
    rng = np.random.default_rng(seed)
    if db is None:
        if cfg is None:
            # size the store for the requested scale (+slack for updates)
            n_v = n_films + n_actors + n_directors + n_genres
            per_film = (actors_per_film[0] + actors_per_film[1]) // 2 + 2
            n_e = n_films * per_film * 2
            S = 8
            cfg = StoreConfig(
                n_shards=S,
                cap_v=max(256, 2 * n_v // S),
                cap_e=max(2048, 4 * n_e // S),
                cap_delta=max(512, n_e // S),
                cap_idx=max(512, 4 * n_v // S),
                cap_idx_delta=max(256, n_v // S),
                d_f32=2, d_i32=2)
        db = GraphDB(cfg)
    db.vertex_type("director", i_attrs=("dob",))
    db.vertex_type("actor", i_attrs=("dob",))
    db.vertex_type("film", f_attrs=("gross",), i_attrs=("year", "genre"))
    db.vertex_type("genre")
    db.edge_type("film.director")   # director -> film
    db.edge_type("film.actor")      # film -> actor
    db.edge_type("film.genre")      # film -> genre

    d_keys = np.arange(1_000, 1_000 + n_directors)
    a_keys = np.arange(10_000, 10_000 + n_actors)
    f_keys = np.arange(100_000, 100_000 + n_films)
    g_keys = np.arange(500, 500 + n_genres)

    def load(ops, chunk):
        """Commit op-record batches as implicit atomic writes, chunked to
        stay under the commit batch caps; returns created gids in order."""
        gids = []
        for off in range(0, len(ops), chunk):
            res = db.write(ops[off:off + chunk])
            assert not res.failed
            gids += res.gids
        return gids

    dirs = load([CreateVertex("director", int(k),
                              {"dob": int(rng.integers(1940, 1995))})
                 for k in d_keys], 200)
    acts = load([CreateVertex("actor", int(k),
                              {"dob": int(rng.integers(1940, 2000))})
                 for k in a_keys], 200)
    genres = load([CreateVertex("genre", int(k)) for k in g_keys], 200)

    # Zipf-skewed popularity: a few mega-actors, like the paper's skew
    pop = 1.0 / np.power(np.arange(1, n_actors + 1), zipf_a)
    dir_pop = 1.0 / np.power(np.arange(1, n_directors + 1), zipf_a)

    films = load([CreateVertex(
        "film", int(k),
        {"gross": float(rng.uniform(1, 500)),
         "year": int(rng.integers(1960, 2026)),
         "genre": int(rng.integers(n_genres))}) for k in f_keys], 200)

    # bulk-load fast path (check=False): uniqueness is the loader's contract
    n_f = len(films)
    f_dir = _cdf(dir_pop).searchsorted(rng.random(n_f), side="right")
    f_genre = rng.integers(n_genres, size=n_f)
    casts = _successive(rng, _cdf(pop), rng.integers(*actors_per_film,
                                                     size=n_f))
    e_ops = []
    for f, d, g, cast in zip(films, f_dir.tolist(), f_genre.tolist(),
                             casts):
        e_ops.append(CreateEdge(dirs[d], f, "film.director", check=False))
        e_ops.append(CreateEdge(f, genres[g], "film.genre", check=False))
        e_ops += [CreateEdge(f, acts[a], "film.actor", check=False)
                  for a in cast]
    load(e_ops, 400)
    db.run_compaction()
    db.run_index_compaction()
    return FilmKG(db=db, n_directors=n_directors, n_actors=n_actors,
                  n_films=n_films, n_genres=n_genres,
                  director_keys=d_keys, actor_keys=a_keys,
                  film_keys=f_keys, genre_keys=g_keys)
