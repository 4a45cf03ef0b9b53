"""A1QL v2: the unified query entry point (§3.4).

One function — :func:`execute`, exported as ``GraphDB.query`` — replaces the
historical four-way split (``run_queries`` / ``run_queries_spmd`` /
``run_queries_batched`` / ``run_queries_batched_spmd``, all still available
as deprecated shims).  Every query parses to the typed logical-plan IR
(:mod:`repro.core.query.ir`), and routing is internal:

  * ``mesh=None`` runs the single-address-space executors; a mesh runs the
    shard_map'd SPMD programs — same results, property-tested;
  * **uniform** batches (every query lowers to the same physical plan, cap
    hints, and snapshot) run the per-plan-shape executor: one compiled
    program whose §3.4 working-set budget is shared by the batch — the
    historical ``run_queries`` semantics, and the parity oracle;
  * everything else — mixed plan shapes, star patterns next to chains,
    per-query MVCC snapshots, per-query cap hints — runs the fused
    multi-query waves (:mod:`repro.core.query.planner`) with *per-query*
    budgets, bit-identical to running each query alone.
    ``fused=True`` forces this path (per-query budgets + ``failed_q`` flags
    even for uniform batches — what serving's hedged retries want);
    ``fused=False`` forbids it (raises on non-uniform batches).

``read_ts`` is ``None`` (one fresh snapshot), a scalar, or per-query
timestamps; every distinct timestamp is pinned for the duration of the call
(the §2.2 GC barrier).  ``parsed`` short-circuits parsing: a list of IR
roots, ``ir.Lowered``, or historical ``(plan, key)`` tuples.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_mod
from repro.core.query import ir
from repro.core.query.a1ql import parse
from repro.core.query.executor import (QueryCaps, QueryResult, _to_result,
                                       compile_query)


def _normalize_parsed(db, queries, parsed) -> list[ir.Lowered]:
    if parsed is None:
        return [ir.lower(parse(db, q)) for q in queries]
    out = []
    for p in parsed:
        if isinstance(p, ir.Lowered):
            out.append(p)
        elif ir.is_root(p):
            out.append(ir.lower(p))
        elif isinstance(p, tuple) and len(p) == 2:
            out.append(ir.from_legacy(*p))       # historical (plan, key)
        else:
            raise TypeError(f"bad parsed entry {type(p).__name__}")
    if len(out) != len(queries):
        raise ValueError(f"{len(out)} parsed entries for "
                         f"{len(queries)} queries")
    return out


def _normalize_ts(db, Q: int,
                  read_ts: Union[None, int, Sequence[int]]) -> list[int]:
    if read_ts is None:
        return [db.snapshot_ts()] * Q
    if isinstance(read_ts, (int, np.integer)):
        return [int(read_ts)] * Q
    ts = [int(t) for t in read_ts]
    if len(ts) != Q:
        raise ValueError(f"read_ts has {len(ts)} entries for {Q} queries")
    return ts


def execute(db, queries: list[dict], *, caps: Optional[QueryCaps] = None,
            backend: Optional[str] = None,
            read_ts: Union[None, int, Sequence[int]] = None,
            mesh=None, storage_axes=("data", "model"),
            parsed: Optional[list] = None,
            fused: Optional[bool] = None,
            budget: Optional[str] = None,
            deadline: Optional[float] = None) -> QueryResult:
    """Execute a batch of A1QL queries at consistent snapshot timestamps.

    See the module docstring for routing; all queries in one call observe
    MVCC snapshots pinned for the whole call, and results (``counts`` /
    ``rows_gid`` / ``rows`` / ``truncated`` / fast-fail flags) scatter back
    into input order.

    ``budget`` selects the fused frontier discipline: ``"per-query"`` (the
    default) gives every query its own §3.4 working-set budget —
    bit-identical to solo runs; ``"shared"`` pools all live queries'
    frontiers into one shared-capacity pool (O(F*sqrt(Q)) peak memory, the
    serving-cap shape) whose overflow is owner-attributed via ``failed_q``
    — results can differ from per-query mode only via those flags.
    ``budget="shared"`` always runs the fused planner.

    Documents may carry a root-level ``"gid_cursor": <gid>`` — a runtime
    final predicate ``gid > cursor`` (deep-pagination refills); cursor
    batches always run fused, and the cursor never retraces a program.
    Cursors are local-executor only: SPMD select rows are ordered
    shard-major, so a max-gid cursor could silently skip rows — a cursor
    under ``mesh=`` raises (serve's refills fall back to the pow2 growing
    window there).

    ``deadline`` is an absolute ``time.monotonic()`` instant — the hard
    edge of the serving tier's SLO budget.  Fusion groups past the
    deadline are skipped, their queries flagged ``deadline_q`` (truncated,
    *not* failed).  A deadline forces the fused path: the uniform executor
    is a single all-or-nothing program with no per-group skip point.
    """
    from repro.core import faults as faults_mod
    from repro.core.query import planner
    if not queries:
        raise ValueError("execute() needs at least one query")
    # chaos site: a wave-execution crash ("raise") or straggler ("stall").
    # Raising here — before any snapshot is pinned — models a worker dying
    # mid-wave; the serving tier must retry or abort with attribution.
    faults_mod.check(db, "engine.wave")
    if budget not in (None, "per-query", "shared"):
        raise ValueError(f"budget must be 'per-query' or 'shared', "
                         f"got {budget!r}")
    caps = caps or QueryCaps()
    be = backend_mod.resolve(backend or getattr(db, "backend", None))
    lowered = _normalize_parsed(db, queries, parsed)
    Q = len(lowered)
    ts_list = _normalize_ts(db, Q, read_ts)
    eff_caps = [lo.hints.apply(caps) for lo in lowered]
    cursors = [lo.cursor for lo in lowered]
    any_cursor = any(c >= 0 for c in cursors)
    if any_cursor and mesh is not None:
        # SPMD select truncation is shard-major, not gid-ascending: paging
        # by max-gid cursor could permanently skip rows on later shards
        raise ValueError("gid_cursor is not supported under mesh= "
                         "(SPMD rows are shard-major; use the growing-"
                         "window continuation instead)")

    # Nearest-rooted plans only exist as fused probe-wave rows (the
    # per-plan-shape executors have no knn wave); a "per-query" oracle for
    # them is a fused batch of one
    any_nearest = any(p.nearest_k > 0 for lo in lowered
                      for p in lo.plan.chain_units())
    uniform = (all(lo.plan == lowered[0].plan for lo in lowered[1:])
               and all(c == eff_caps[0] for c in eff_caps[1:])
               and len(set(ts_list)) == 1
               and not any_cursor
               and not any_nearest)
    if fused is False and not uniform:
        raise ValueError("fused=False requires a uniform batch "
                         "(one plan shape, caps, snapshot, no cursors, "
                         "no nearest)")
    if fused is False and budget == "shared":
        raise ValueError("budget='shared' requires the fused planner")
    if fused is False and deadline is not None:
        raise ValueError("deadline= requires the fused planner (the "
                         "uniform executor has no per-group skip point)")
    run_fused = (bool(fused) or not uniform or budget == "shared"
                 or deadline is not None)

    pins = sorted(set(ts_list))
    for t in pins:                            # pin versions (GC barrier)
        db.active_query_ts.append(t)
    try:
        if run_fused:
            return planner.execute_fused(db, lowered, eff_caps, ts_list, be,
                                         mesh=mesh, storage_axes=storage_axes,
                                         budget=budget or "per-query",
                                         cursors=cursors, deadline=deadline)
        return _execute_uniform(db, lowered, eff_caps[0], ts_list[0], be,
                                mesh, storage_axes)
    finally:
        for t in pins:
            db.active_query_ts.remove(t)


def _execute_uniform(db, lowered: list[ir.Lowered], caps: QueryCaps,
                     read_ts: int, be, mesh, storage_axes) -> QueryResult:
    """One plan shape, shared working-set budget: the per-plan executors."""
    from repro.core.query.planner import delta_window, index_window
    plan = lowered[0].plan
    Q = len(lowered)
    xwin = index_window(db)
    if plan.is_intersect:
        # (branches, Q) key layout: branch bi of query qi probes keys[bi, qi]
        keys = jnp.asarray(np.array(
            [[lo.keys[bi] for lo in lowered]
             for bi in range(len(plan.branches))], np.int32))
    else:
        keys = jnp.asarray(np.array([lo.keys[0] for lo in lowered], np.int32))
    if mesh is not None:
        from repro.core.query.executor_spmd import compile_query_spmd
        fn = compile_query_spmd(db.cfg, plan, caps, Q, mesh, storage_axes,
                                backend=be, xwin=xwin)
    else:
        fn = compile_query(db.cfg, plan, caps, Q, be, xwin=xwin,
                           dwin=delta_window(db))
    out = fn(db.store, keys, jnp.ones((Q,), bool), jnp.int32(read_ts))
    return _to_result(plan, out)
