"""Reference batched k-NN: squared-L2 distance + per-query top-k.

The oracle for the ``knn_topk`` pallas kernel.  Given a batch of query
vectors and the flat vector-index arrays (``core/vindex.py``), returns for
each query the ``k`` nearest *visible* entries of the requested vertex type.

Distance is the gid-monotone surrogate ``||e||^2 - 2 <v, e>`` (the query's
own ``||v||^2`` term is constant per row and dropped), so values can be
negative.  Both norms and inner products are f32 sums accumulated one
feature at a time, in feature order (:func:`sq_norms`,
:func:`inner_products`), never a matmul, and each product enters the sum as
four partial products that f32 holds exactly (:func:`_madd`).  A compiler
may fuse any multiply-add into an FMA or not, in either program, without
changing a bit, so the pallas kernel — which runs the same helpers on its
tiles — and this oracle agree exactly on any backend, whatever precision
or blocking a matmul would pick there.  Ties are broken by ascending gid via a two-key sort, which makes
the selection deterministic and backend-independent.  Invalid slots come
back as ``(+inf, I32MAX)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# plain int, NOT jnp.int32(...): this module is imported lazily from inside
# jitted programs, and a module-level device constant created mid-trace
# leaks a tracer
I32MAX = 2**31 - 1


def _split(x):
    """``x == hi + lo`` exactly, each with at most 12 significant bits."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & -4096
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi, x - hi


def _madd(acc, a, b):
    """``acc + a*b`` with ``a*b`` added as four exact partial products
    (12 x 12 significant bits fit f32's 24), so fusing any step into an
    FMA cannot change the result."""
    ah, al = _split(a)
    bh, bl = _split(b)
    for p in (ah * bh, ah * bl, al * bh, al * bl):
        acc = acc + p
    return acc


@jax.jit
def sq_norms(emb):
    """``||e||^2`` per row of (N, D) f32, accumulated in feature order."""
    ee = jnp.zeros(emb.shape[:1], jnp.float32)
    for d in range(emb.shape[1]):
        ee = _madd(ee, emb[:, d], emb[:, d])
    return ee


def inner_products(vecs, emb_t):
    """(R, D) x (D, N) -> (R, N) f32 ``<v, e>``, accumulated in feature
    order (one broadcast multiply-add per feature)."""
    ip = jnp.zeros((vecs.shape[0], emb_t.shape[1]), jnp.float32)
    for d in range(vecs.shape[1]):
        ip = _madd(ip, vecs[:, d:d + 1], emb_t[d:d + 1, :])
    return ip


@functools.partial(jax.jit, static_argnames="k")
def knn_topk(vecs, emb, gid, vtype, create, delete, q_vt, q_ts, k: int):
    """Top-k nearest visible entries per query row.

    vecs:   (R, D) f32 query vectors
    emb:    (N, D) f32 index embeddings
    gid:    (N,)   i32 entry vertex gid (NULL = empty slot)
    vtype:  (N,)   i32 entry vertex type
    create: (N,)   i32 MVCC create ts
    delete: (N,)   i32 MVCC delete ts (TS_INF = live)
    q_vt:   (R,)   i32 per-query type filter
    q_ts:   (R,)   i32 per-query snapshot ts
    k:      static int

    Returns ``(dist (R, k) f32, gids (R, k) i32)`` sorted ascending by
    ``(dist, gid)``; slots past the number of matches are ``(+inf, I32MAX)``.
    """
    R = vecs.shape[0]
    vecs = vecs.astype(jnp.float32)
    emb = emb.astype(jnp.float32)
    ee = sq_norms(emb)                       # (N,)
    ip = inner_products(vecs, emb.T)         # (R, N)
    ok = (
        (gid >= 0)[None, :]
        & (vtype[None, :] == q_vt[:, None])
        & (create[None, :] <= q_ts[:, None])
        & (q_ts[:, None] < delete[None, :])
    )
    # `+ 0.0` canonicalizes -0.0 so both backends sort identical bit patterns.
    d = jnp.where(ok, (ee[None, :] - 2.0 * ip) + 0.0, jnp.inf)
    g = jnp.where(ok, jnp.broadcast_to(gid[None, :], ok.shape), I32MAX)
    ds, gs = jax.lax.sort((d, g), dimension=1, num_keys=2)
    N = emb.shape[0]
    if N < k:  # fewer index slots than requested neighbours: pad out
        ds = jnp.pad(ds, ((0, 0), (0, k - N)), constant_values=jnp.inf)
        gs = jnp.pad(gs, ((0, 0), (0, k - N)), constant_values=2**31 - 1)
    return ds[:, :k], gs[:, :k]
