"""Query execution, single-address-space mode (§3.4).

This is the *logical* executor: it runs the physical plan against the global
store arrays on one device.  It defines the semantics; the distributed
executor (executor_spmd.py) must produce bit-identical results (property
tested), the same way A1's shipped operators must agree with coordinator-side
evaluation.

Execution mirrors the paper's operator set: index scan -> [edge enumeration ->
predicate evaluation -> dedup/repartition]* -> aggregate, all at one snapshot
timestamp, with fixed working-set capacities and a fast-fail flag instead of
spill (§3.4).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_mod
from repro.core import edges as edges_mod
from repro.core import index as index_mod
from repro.core.addressing import NULL, TS_INF, StoreConfig
from repro.core.query.a1ql import Hop, Plan, Pred
from repro.core.store import GraphStore, visible

I32MAX = jnp.int32(2**31 - 1)


@dataclasses.dataclass(frozen=True)
class QueryCaps:
    """Static working-set capacities (the paper's §3.4 memory budget; optional

    A1QL hints map to these)."""
    frontier: int = 1024       # live (qid, gid) pairs between hops
    expand: int = 4096         # CSR expansion slots per hop
    results: int = 64          # rows returned per query (continuation beyond)
    # spmd-only:
    bucket: int = 256          # per-destination-shard routing bucket
    # shared-frontier mode only (GraphDB.query(..., budget="shared")):
    # explicit shared-pool sizes; 0 = the planner's auto policy
    # (per-cap * ceil(sqrt(units)), pow2 — see planner.shared_budget)
    shared_frontier: int = 0
    shared_expand: int = 0
    shared_bucket: int = 0


@dataclasses.dataclass
class QueryResult:
    counts: Optional[np.ndarray] = None      # (Q,) for terminal 'count'
    rows_gid: Optional[np.ndarray] = None    # (Q, K) for terminal 'select'
    rows: Optional[dict] = None              # attr name -> (Q, K)
    truncated: Optional[np.ndarray] = None   # (Q,) rows overflowed K
    failed: bool = False                     # fast-fail (capacity overflow)
    failed_q: Optional[np.ndarray] = None    # (Q,) per-query fast-fail flags
                                             # (set by the multi-query planner;
                                             # plain run_queries flags the
                                             # whole batch)
    shared_ovf_q: Optional[np.ndarray] = None  # (Q,) subset of failed_q that
                                             # was caused by the *shared* pool
                                             # (budget="shared" truncation /
                                             # bucket drops) rather than the
                                             # query's own per-unit caps —
                                             # serving re-dispatches these
                                             # per-query instead of re-entering
                                             # the saturated pool
    deadline_q: Optional[np.ndarray] = None  # (Q,) SLO-budget truncation: the
                                             # query's wave group was skipped
                                             # because the execution deadline
                                             # passed (engine deadline=).  NOT
                                             # a capacity failure: failed_q
                                             # stays False and serving answers
                                             # truncated-with-flag instead of
                                             # hedging


# ---------------------------------------------------------------------------
# shared primitives
# ---------------------------------------------------------------------------

def eval_pred(pred: Pred, f_data, i_data, keys):
    """Vertex predicate evaluation (one of the paper's basic operators).

    ``f_data``/``i_data`` may carry any leading batch shape (the planner's
    fused waves evaluate predicates on ``(Q, F, d)`` row blocks)."""
    if pred.kind == "f32":
        x = f_data[..., pred.col]
        v = jnp.float32(pred.val)
    elif pred.kind == "i32":
        x = i_data[..., pred.col]
        v = jnp.int32(int(pred.val))
    else:
        x = keys
        v = jnp.int32(int(pred.val))
    if pred.op == "==":
        return x == v
    if pred.op == "!=":
        return x != v
    if pred.op == "<":
        return x < v
    if pred.op == "<=":
        return x <= v
    if pred.op == ">":
        return x > v
    return x >= v


def sort_pairs(qids, gids, valid):
    """Sort (qid, gid) pairs; invalid entries to the end.  Returns sorted

    (qids, gids, valid, first_of_run mask)."""
    k1 = jnp.where(valid, qids, I32MAX)
    k2 = jnp.where(valid, gids, I32MAX)
    k1, k2 = jax.lax.sort((k1, k2), num_keys=2)
    valid_s = k1 != I32MAX
    prev1 = jnp.concatenate([jnp.full((1,), -1, k1.dtype), k1[:-1]])
    prev2 = jnp.concatenate([jnp.full((1,), -1, k2.dtype), k2[:-1]])
    first = valid_s & ((k1 != prev1) | (k2 != prev2))
    return jnp.where(valid_s, k1, NULL), jnp.where(valid_s, k2, NULL), valid_s, first


def dedup_compact(qids, gids, valid, cap: int):
    """Dedup (qid, gid) pairs and compact to ``cap`` slots.

    The coordinator's "aggregated, duplicates removed" step.  Returns
    (qids', gids', valid', overflow).
    """
    q_s, g_s, v_s, first = sort_pairs(qids, gids, valid)
    n_unique = jnp.sum(first.astype(jnp.int32))
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1
    pos = jnp.where(first, pos, I32MAX)          # drop non-first
    out_q = jnp.full((cap,), NULL, jnp.int32).at[pos].set(q_s, mode="drop")
    out_g = jnp.full((cap,), NULL, jnp.int32).at[pos].set(g_s, mode="drop")
    return out_q, out_g, out_q >= 0, n_unique > cap


def check_vertices(store: GraphStore, cfg: StoreConfig, qids, gids, valid,
                   read_ts, target_vtype: int, pred: Optional[Pred]):
    """Liveness + type + predicate check of arrived vertices (worker-side

    'predicate evaluation against vertex data')."""
    ok = valid & (gids >= 0)
    rows = cfg.row_of_gid(jnp.where(ok, gids, 0))
    alive = ok & visible(store.v_create[rows], store.v_delete[rows], read_ts)
    if target_vtype >= 0:
        alive = alive & (store.vtype[rows] == jnp.int32(target_vtype))
    if pred is not None:
        use_cur = store.vdata_ts[rows] <= read_ts
        f = jnp.where(use_cur[:, None], store.vdata_f[rows],
                      store.vprev_f[rows])
        i = jnp.where(use_cur[:, None], store.vdata_i[rows],
                      store.vprev_i[rows])
        alive = alive & eval_pred(pred, f, i, store.vkey[rows])
    return alive


def build_select(store: GraphStore, cfg: StoreConfig, plan: Plan,
                 qids, gids, valid, read_ts, n_queries: int, k: int):
    """Scatter final (qid, gid) pairs into per-query rows + gather attrs."""
    q_s, g_s, v_s, first = sort_pairs(qids, gids, valid)
    # position within each query's run (dedup'd); NB: q_s pads invalid with
    # NULL(-1) which breaks sortedness, so search over an I32MAX-padded view.
    q_srch = jnp.where(v_s, q_s, I32MAX)
    c = jnp.cumsum(first.astype(jnp.int32))
    run_start = jnp.searchsorted(q_srch, q_srch, side="left").astype(jnp.int32)
    excl = c - first.astype(jnp.int32)           # exclusive cumsum
    pos_in_q = excl - excl[run_start]
    row = jnp.where(first & (q_s >= 0), q_s, I32MAX)
    col = jnp.where(first, pos_in_q, I32MAX)
    over = first & (pos_in_q >= k)
    col = jnp.where(over, I32MAX, col)

    rows_gid = jnp.full((n_queries, k), NULL, jnp.int32)
    rows_gid = rows_gid.at[row, col].set(g_s, mode="drop")
    truncated = jnp.zeros((n_queries,), bool).at[
        jnp.where(over, q_s, I32MAX)].set(True, mode="drop")

    safe = jnp.where(rows_gid >= 0, rows_gid, 0)
    r = cfg.row_of_gid(safe)
    use_cur = store.vdata_ts[r] <= read_ts
    out = {}
    for kind, colid in zip(plan.select_kind, plan.select_cols):
        if kind == "key":
            vals = jnp.where(rows_gid >= 0, store.vkey[r], NULL)
        elif kind == "f32":
            v = jnp.where(use_cur, store.vdata_f[r][..., colid],
                          store.vprev_f[r][..., colid])
            vals = v * (rows_gid >= 0)
        else:
            v = jnp.where(use_cur, store.vdata_i[r][..., colid],
                          store.vprev_i[r][..., colid])
            vals = v * (rows_gid >= 0)
        out[(kind, colid)] = vals
    return rows_gid, out, truncated


# ---------------------------------------------------------------------------
# chain execution (lookup -> hops -> terminal)
# ---------------------------------------------------------------------------

def _chain_frontier(store, cfg: StoreConfig, plan: Plan, caps: QueryCaps,
                    keys, valid, read_ts,
                    backend: backend_mod.Backend = backend_mod.REF,
                    xwin: Optional[int] = None, dwin: Optional[int] = None):
    """Run index lookup + all hops; returns final (qids, gids, valid, failed)."""
    Q = keys.shape[0]
    F = caps.frontier
    vt = jnp.full((Q,), plan.start_vtype, jnp.int32)
    gids, found = index_mod.lookup(store, cfg, vt, keys, valid, read_ts,
                                   backend=backend, xd_win=xwin)
    qids = jnp.arange(Q, dtype=jnp.int32)
    ok = valid & found
    pad = F - Q
    if pad < 0:
        raise ValueError("frontier capacity below query batch size")
    qids = jnp.concatenate([jnp.where(ok, qids, NULL),
                            jnp.full((pad,), NULL, jnp.int32)])
    gids = jnp.concatenate([jnp.where(ok, gids, NULL),
                            jnp.full((pad,), NULL, jnp.int32)])
    vmask = gids >= 0
    failed = jnp.zeros((), bool)

    for hop in plan.hops:
        oq, on, ov, ovf = edges_mod.expand(
            store, cfg, qids, gids, vmask, etype=jnp.int32(hop.etype),
            direction=hop.direction, read_ts=read_ts, cap_out=caps.expand,
            backend=backend, dwin=dwin)
        failed = failed | ovf
        qids, gids, vmask, ovf2 = dedup_compact(oq, on, ov, F)
        failed = failed | ovf2
        alive = check_vertices(store, cfg, qids, gids, vmask, read_ts,
                               hop.target_vtype, hop.pred)
        vmask = vmask & alive
        gids = jnp.where(vmask, gids, NULL)
        qids = jnp.where(vmask, qids, NULL)
    return qids, gids, vmask, failed


def _terminal(store, cfg, plan, caps, qids, gids, vmask, read_ts, Q: int):
    if plan.final_pred is not None:
        keep = check_vertices(store, cfg, qids, gids, vmask, read_ts,
                              -1, plan.final_pred)
        vmask = vmask & keep
        gids = jnp.where(vmask, gids, NULL)
        qids = jnp.where(vmask, qids, NULL)
    if plan.terminal == "count":
        q_s, g_s, v_s, first = sort_pairs(qids, gids, vmask)
        counts = jax.ops.segment_sum(
            first.astype(jnp.int32),
            jnp.where(first, q_s, Q).astype(jnp.int32),
            num_segments=Q + 1)[:Q]
        return {"counts": counts}
    rows_gid, attrs, trunc = build_select(store, cfg, plan, qids, gids, vmask,
                                          read_ts, Q, caps.results)
    return {"rows_gid": rows_gid, "attrs": attrs, "truncated": trunc}


def _run_intersect(store, cfg, plan: Plan, caps: QueryCaps, keys_b, valid,
                   read_ts, Q: int,
                   backend: backend_mod.Backend = backend_mod.REF,
                   xwin: Optional[int] = None, dwin: Optional[int] = None):
    """Star-pattern intersection (Q3): keep vertices reached by all branches."""
    B = len(plan.branches)
    all_q, all_g, all_v = [], [], []
    failed = jnp.zeros((), bool)
    for bi, branch in enumerate(plan.branches):
        q, g, v, f = _chain_frontier(store, cfg, branch, caps,
                                     keys_b[bi], valid, read_ts, backend,
                                     xwin, dwin)
        failed = failed | f
        all_q.append(q)
        all_g.append(g)
        all_v.append(v)
    qids = jnp.concatenate(all_q)
    gids = jnp.concatenate(all_g)
    vmask = jnp.concatenate(all_v)
    q_s, g_s, v_s, first = sort_pairs(qids, gids, vmask)
    run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    run_id = jnp.where(v_s, run_id, q_s.shape[0] - 1)
    run_len = jax.ops.segment_sum(v_s.astype(jnp.int32), run_id,
                                  num_segments=q_s.shape[0])
    keep = first & (run_len[run_id] == B)
    kq = jnp.where(keep, q_s, NULL)
    kg = jnp.where(keep, g_s, NULL)
    return _terminal(store, cfg, plan, caps, kq, kg, keep, read_ts, Q), failed


# compiled-executor cache (the paper parses per query; we compile per plan
# *shape* so repeated patterns — the common case in serving — are free).
# CACHE_STATS is observable so tests/benchmarks can assert no retracing.
_CACHE: dict = {}
CACHE_STATS = {"hits": 0, "misses": 0}


def compile_query(cfg: StoreConfig, plan: Plan, caps: QueryCaps,
                  n_queries: int,
                  backend: backend_mod.Backend = backend_mod.REF,
                  xwin: Optional[int] = None, dwin: Optional[int] = None):
    """Build the jitted program for one plan shape (shared-budget batch).

    ``xwin``/``dwin`` are the static primary-index / edge delta windows
    (see ``planner.index_window`` / ``planner.delta_window``) —
    semantics-preserving (skipped slots are provably empty), part of the
    cache key like the planner's."""
    key = (cfg, plan, caps, n_queries, backend, xwin, dwin, "local")
    if key in _CACHE:
        CACHE_STATS["hits"] += 1
        return _CACHE[key]
    CACHE_STATS["misses"] += 1

    if plan.is_intersect:
        @jax.jit
        def run(store, keys_b, valid, read_ts):
            out, failed = _run_intersect(store, cfg, plan, caps, keys_b,
                                         valid, read_ts, n_queries, backend,
                                         xwin, dwin)
            out["failed"] = failed
            return out
    else:
        @jax.jit
        def run(store, keys, valid, read_ts):
            q, g, v, failed = _chain_frontier(store, cfg, plan, caps, keys,
                                              valid, read_ts, backend, xwin,
                                              dwin)
            out = _terminal(store, cfg, plan, caps, q, g, v, read_ts,
                            n_queries)
            out["failed"] = failed
            return out

    _CACHE[key] = run
    return run


def run_queries(db, queries: list[dict], caps: Optional[QueryCaps] = None,
                backend: Optional[str] = None,
                read_ts: Optional[int] = None) -> QueryResult:
    """Deprecated shim: use ``GraphDB.query`` / ``engine.execute``.

    Uniform batches keep the historical shared-budget semantics; mixed
    batches route to the fused multi-query waves — exactly what
    ``execute`` does with ``fused=None``.
    """
    import warnings
    warnings.warn("run_queries is deprecated; use GraphDB.query(...) "
                  "(core.query.engine.execute)", DeprecationWarning,
                  stacklevel=2)
    from repro.core.query.engine import execute
    return execute(db, queries, caps=caps, backend=backend, read_ts=read_ts)


def _to_result(plan: Plan, out: dict) -> QueryResult:
    res = QueryResult(failed=bool(np.any(np.asarray(out["failed"]))))
    if plan.terminal == "count":
        res.counts = np.asarray(out["counts"])
    else:
        res.rows_gid = np.asarray(out["rows_gid"])
        res.truncated = np.asarray(out["truncated"])
        res.rows = {k: np.asarray(v) for k, v in out["attrs"].items()}
    return res


