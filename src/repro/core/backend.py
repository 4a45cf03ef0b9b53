"""Backend dispatch for the read hot path (edge enumeration + index probes).

A1's headline read throughput comes from a purpose-built RDMA read path
(§3.4); ours comes from the Pallas kernels under ``repro.kernels``.  This
module is the seam between the *semantics* layer (``core/edges.py``,
``core/index.py`` — pure jnp, the oracle) and the *hardware* layer (the
``edge_expand`` and ``sorted_lookup`` kernels): every hot read operator asks
the backend which implementation to run.

Contract
--------
A :class:`Backend` is a frozen (hashable) value threaded through the jitted
query programs as part of their cache key:

  * ``kind="ref"``     — the branchless jnp reference path.  Defines the
    semantics; always available.
  * ``kind="pallas"``  — the Pallas kernels.  Compiled on TPU; everywhere
    else they run in interpret mode (bit-identical by the kernel test
    suites, and by construction here: the kernel output is scattered into
    the reference layout, see ``edges.expand``).

Selection (first match wins):

  1. an explicit ``backend=`` argument to ``run_queries`` /
     ``compile_query`` / ``GraphDB(backend=...)``;
  2. the ``REPRO_BACKEND`` environment variable (``ref``/``pallas``/``auto``);
  3. ``auto``: ``pallas`` when the default jax backend is TPU (the hardware
     the kernels were written for), ``ref`` otherwise — CPU CI keeps running
    the cheap oracle, TPU runs at line rate, no code changes anywhere.

Adding the next kernel: give the op a jnp reference in the semantics layer,
add a ``Backend``-dispatched helper here, and key any program cache on the
backend.  See ``src/repro/core/README.md`` for the worked ``segment_spmm``
example.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax

_VALID = ("ref", "pallas", "auto")
ENV_VAR = "REPRO_BACKEND"


@dataclasses.dataclass(frozen=True)
class Backend:
    """Resolved backend choice.  Frozen: usable in jit/program cache keys."""

    kind: str                 # 'ref' | 'pallas'
    interpret: bool = False   # pallas kernels run in interpret mode (no TPU)

    @property
    def is_pallas(self) -> bool:
        return self.kind == "pallas"


REF = Backend("ref")


def resolve(spec: Optional[str] = None) -> Backend:
    """Resolve a backend name (or None) to a concrete :class:`Backend`.

    ``None`` falls back to ``$REPRO_BACKEND``, then ``auto``.
    """
    name = spec or os.environ.get(ENV_VAR, "") or "auto"
    if name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {name!r}")
    on_tpu = jax.default_backend() == "tpu"
    if name == "auto":
        name = "pallas" if on_tpu else "ref"
    if name == "ref":
        return REF
    return Backend("pallas", interpret=not on_tpu)


# ---------------------------------------------------------------------------
# dispatched primitives
# ---------------------------------------------------------------------------

def expand_tiles(starts, degs, pools, *, tile: int, cap_tiles: int,
                 backend: Backend):
    """Tile-padded ragged CSR span gather (the edge-enumeration primitive).

    Returns (outs, item_of_tile, tw_of_tile, n_tiles): ``outs[i]`` is
    ``pools[i]`` gathered to (cap_tiles*tile,) with -1 in invalid lanes;
    lane j of tile t is edge ``tw_of_tile[t]*tile + j`` of frontier item
    ``item_of_tile[t]`` (item == F marks a padding tile).
    """
    from repro.kernels.edge_expand import ref as _ref
    item, tw, n_tiles, _ = _ref.plan(degs, tile, cap_tiles)
    if backend.is_pallas:
        from repro.kernels.edge_expand.kernel import expand as _kernel
        outs = _kernel(starts, degs, tuple(pools), item, tw, tile=tile,
                       cap_tiles=cap_tiles, interpret=backend.interpret)
    else:
        outs, _, _ = _ref.expand(starts, degs, tuple(pools), tile, cap_tiles)
    return outs, item, tw, n_tiles


def searchsorted_blocked(keys, queries, lo, *, block: int, backend: Backend):
    """Left insertion position of each query within its own sorted block.

    ``keys`` is a flat block-major array whose slice ``[lo[q], lo[q]+block)``
    is sorted for every query q.  Returns block-relative positions, exactly
    ``jnp.searchsorted(keys[lo:lo+block], query, side='left')``.
    """
    import jax.numpy as jnp
    if backend.is_pallas:
        from repro.kernels.sorted_lookup.kernel import searchsorted_left_ranged
        return searchsorted_left_ranged(keys, queries, lo, lo + block,
                                        interpret=backend.interpret)
    # reference: per-query binary search inside [lo, lo + block) — no
    # per-query copy of the block (at a 16M-entry index, 64 probes would
    # otherwise materialize 4 GiB)
    n = keys.shape[0]
    lo = lo.astype(jnp.int32)

    def step(_, lh):
        a, b = lh
        mid = (a + b) // 2
        go = (a < b) & (keys[jnp.minimum(mid, n - 1)] < queries)
        return jnp.where(go, mid + 1, a), jnp.where(go | (a >= b), b, mid)

    pos, _ = jax.lax.fori_loop(0, int(block).bit_length(), step,
                               (lo, lo + block))
    return pos - lo


def searchsorted(keys, queries, *, backend: Backend):
    """Left insertion position of each query in one flat sorted array."""
    import jax.numpy as jnp
    if backend.is_pallas:
        from repro.kernels.sorted_lookup.kernel import searchsorted_left
        return searchsorted_left(keys, queries, interpret=backend.interpret)
    return jnp.searchsorted(keys, queries, side="left").astype(jnp.int32)


def searchsorted_ranged(keys, queries, lo, hi, *, backend: Backend):
    """Per-query windowed probe: ``count(keys[lo:hi] < q)`` for each query.

    ``keys`` need only be sorted within each query's ``[lo, hi)`` window
    (variable-width, unlike :func:`searchsorted_blocked`) — the shared
    frontier's per-segment runs, the shard-major primary index, etc.
    """
    if backend.is_pallas:
        from repro.kernels.sorted_lookup.kernel import searchsorted_left_ranged
        return searchsorted_left_ranged(keys, queries, lo, hi,
                                        interpret=backend.interpret)
    from repro.kernels.sorted_lookup.ref import searchsorted_left_ranged
    return searchsorted_left_ranged(keys, queries, lo, hi)


def sort_rows(x, *, backend: Backend):
    """Row-wise ascending sort of an (R, W) i32 matrix (the full-width sort
    behind every dedup/merge wave).  The pallas path runs the VMEM-resident
    bitonic network of ``kernels/dedup_compact``; both are bit-identical."""
    if backend.is_pallas:
        from repro.kernels.dedup_compact.kernel import sort_rows as _k
        return _k(x, interpret=backend.interpret)
    from repro.kernels.dedup_compact.ref import sort_rows as _r
    return _r(x)


def dedup_compact_rows(x, cap: int, *, backend: Backend):
    """(R, W) candidates (PAD = invalid) -> ((R, cap) sorted-unique regions,
    (R,) unique counts).  The §3.4 per-hop compaction; counts > cap is the
    fast-fail condition."""
    if backend.is_pallas:
        from repro.kernels.dedup_compact.kernel import dedup_compact_rows as _k
        return _k(x, cap, interpret=backend.interpret)
    from repro.kernels.dedup_compact.ref import dedup_compact_rows as _r
    return _r(x, cap)


def sort_pairs(k1, k2, *, backend: Backend):
    """Lexicographic ascending sort of flat (k1, k2) i32 pairs (the shared
    frontier's one compaction sort per hop)."""
    if backend.is_pallas:
        from repro.kernels.dedup_compact.kernel import sort_pairs as _k
        return _k(k1, k2, interpret=backend.interpret)
    from repro.kernels.dedup_compact.ref import sort_pairs as _r
    return _r(k1, k2)


def knn_topk(vecs, emb, gid, vtype, create, delete, q_vt, q_ts, k: int, *,
             backend: Backend):
    """Batched squared-L2 distance + per-query top-k over the vector index
    (the `Nearest` probe wave).  Entries are filtered by type and MVCC
    visibility per query; ties break by ascending gid, invalid slots come
    back as (+inf, I32MAX).  Both paths are bit-identical — the pallas
    kernel streams the index through VMEM tile by tile into a running
    two-key bitonic top-k merge, computing each distance with the ref's
    own f32 operations."""
    if backend.is_pallas:
        from repro.kernels.knn_topk.kernel import knn_topk as _k
        return _k(vecs, emb, gid, vtype, create, delete, q_vt, q_ts, k,
                  interpret=backend.interpret)
    from repro.kernels.knn_topk.ref import knn_topk as _r
    return _r(vecs, emb, gid, vtype, create, delete, q_vt, q_ts, k)
