"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only queries,throughput,...]
                                            [--smoke] [--json OUT.json]
                                            [--backend auto|ref|pallas]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit);
``--json`` additionally writes the rows as a JSON artifact (what CI
uploads per commit, accumulating the perf trajectory).  ``--smoke`` runs a
reduced knowledge graph and only the cheap suites — a per-PR signal, not a
paper-scale number.
"""
import argparse
import json
import os
import platform
import sys
import time

# work as `python -m benchmarks.run` (repo root) or `python benchmarks/run.py`
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced KG + cheap suites (CI per-PR signal)")
    ap.add_argument("--json", default="",
                    help="also write rows to this JSON file")
    ap.add_argument("--backend", default="", choices=["", "auto", "ref",
                                                      "pallas"],
                    help="read-path backend (default: $REPRO_BACKEND/auto)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.smoke and only is None:
        only = {"queries", "reads", "multiquery", "writes", "serve",
                "vector"}
    if args.backend:
        # before any repro import: every suite resolves the env default
        os.environ["REPRO_BACKEND"] = args.backend

    import jax

    from repro.launch import jax_cache
    jax_cache.enable()

    from benchmarks import (bench_multiquery, bench_queries, bench_reads,
                            bench_scaling, bench_serve, bench_throughput,
                            bench_vector, bench_writes)
    from benchmarks import common
    from repro.core import backend as backend_mod
    from repro.data.kg import build_film_kg

    be = backend_mod.resolve(args.backend or None)
    meta = {"backend": be.kind,
            "backend_interpret": be.interpret,
            "jax": jax.__version__,
            "jax_platform": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind}
    common.set_context(backend=be.kind)

    print("name,us_per_call,derived")
    t0 = time.time()
    kg = None
    if only is None or {"queries", "throughput", "reads", "multiquery"} & only:
        kg = (build_film_kg(n_films=40, n_actors=60, n_directors=8)
              if args.smoke else
              build_film_kg(n_films=150, n_actors=200, n_directors=30))
    if only is None or "queries" in only:
        bench_queries.run(kg)
    if only is None or "multiquery" in only:
        bench_multiquery.run(kg)
    if only is None or "throughput" in only:
        bench_throughput.run(kg)
    if only is None or "reads" in only:
        bench_reads.run(kg)
    if only is None or "writes" in only:
        bench_writes.run(smoke=args.smoke)
    if only is None or "serve" in only:
        bench_serve.run(smoke=args.smoke)
    if only is None or "vector" in only:
        bench_vector.run(smoke=args.smoke)
    if only is None or "scaling" in only:
        bench_scaling.run()
    wall = time.time() - t0
    print(f"# total {wall:.1f}s", file=sys.stderr)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": common.ROWS,
                       "smoke": args.smoke,
                       "wall_s": round(wall, 1),
                       "python": platform.python_version(),
                       "unix_time": int(time.time()),
                       **meta}, f, indent=1)
        print(f"# wrote {args.json} ({len(common.ROWS)} rows)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
