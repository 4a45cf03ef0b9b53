"""Batched distance+top-k Pallas TPU kernel (the `Nearest` probe wave).

Hardware adaptation: the reference path materializes the full (R, N)
distance matrix in HBM and runs an XLA two-key sort over its whole width.
Here the index streams through VMEM in tiles of BN entries on the inner
grid axis: each step computes the (br, BN) distance tile, masks MVCC +
type visibility in-register, and — only when some visible entry of the
tile can still enter the answer — merges the tile into a running per-query
top-KB buffer kept in VMEM scratch across the whole N axis, with the
rotate+select bitonic network of ``kernels/bitonic.py`` on a (dist, gid)
key pair.  The full-width distance matrix never exists, and VMEM holds one
tile whatever the index size.

Bit-parity with the ref oracle: both paths compute every distance with the
same f32 operations in the same order (``ref.sq_norms`` for ``||e||^2``,
then ``<v, e>`` accumulated one feature at a time, then
``(||e||^2 - 2<v, e>) + 0.0``), so tiling cannot change any value;
selection then orders identical (dist, gid) pairs lexicographically, which
has exactly one answer.  ``+ 0.0`` canonicalizes -0.0 on both paths.

Grid: (row_blocks, entry_tiles); the entry axis is sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic import LANES, SUB, sort_refs
from repro.kernels.knn_topk.ref import inner_products, sq_norms

I32MAX = 2**31 - 1
MERGE_W = 1024      # widest merge: lanes of the running buffer + one tile


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _knn_kernel(v_ref, e_ref, m_ref, qvt_ref, qts_ref, od_ref, og_ref,
                dbuf, gbuf, *, kp: int, kb: int):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        dbuf[...] = jnp.full(dbuf.shape, jnp.inf, jnp.float32)
        gbuf[...] = jnp.full(gbuf.shape, I32MAX, jnp.int32)

    meta = m_ref[...]                    # (8, BN): gid, vtype, create, delete, ee
    gid, vt, cr, dl = (meta[i:i + 1, :] for i in range(4))
    ee = jax.lax.bitcast_convert_type(meta[4:5, :], jnp.float32)
    ip = inner_products(v_ref[...], e_ref[...])           # (br, BN)
    qvt, qts = qvt_ref[...], qts_ref[...]                 # (br, 1)
    ok = (gid >= 0) & (vt == qvt) & (cr <= qts) & (qts < dl)
    d = jnp.where(ok, (ee - 2.0 * ip) + 0.0, jnp.inf)
    kth = dbuf[:, kp - 1:kp]                              # current k-th best
    hit = jnp.max(jnp.where(ok & (d <= kth), 1, 0)) > 0

    @pl.when(hit)
    def _merge():
        dbuf[:, kb:] = d
        gbuf[:, kb:] = jnp.where(ok, jnp.broadcast_to(gid, ok.shape), I32MAX)
        sort_refs([dbuf, gbuf], flat=False)

    @pl.when(n == pl.num_programs(1) - 1)
    def _done():
        od_ref[...] = dbuf[:, :kb]
        og_ref[...] = gbuf[:, :kb]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def knn_topk(vecs, emb, gid, vtype, create, delete, q_vt, q_ts, k: int, *,
             interpret: bool = False):
    """Pallas top-k nearest visible entries; see the ref oracle for the
    argument contract.  Returns ``(dist (R, k) f32, gids (R, k) i32)``."""
    R, D = vecs.shape
    N = emb.shape[0]
    kp = _pow2ceil(max(1, k))
    kb = max(LANES, kp)                  # running buffer: lane-aligned >= k
    W = max(2 * kb, min(MERGE_W, _pow2ceil(kb + max(1, N))))
    bn = W - kb                          # entries per grid step
    n2 = pl.cdiv(max(1, N), bn) * bn
    r2 = pl.cdiv(R, SUB) * SUB

    emb = emb.astype(jnp.float32)
    v2 = jnp.pad(vecs.astype(jnp.float32), ((0, r2 - R), (0, 0)))
    e2 = jnp.pad(emb.T, ((0, 0), (0, n2 - N)))
    ee = jax.lax.bitcast_convert_type(sq_norms(emb), jnp.int32)
    meta = jnp.stack([jnp.pad(gid, (0, n2 - N), constant_values=-1),
                      jnp.pad(vtype, (0, n2 - N), constant_values=-1),
                      jnp.pad(create, (0, n2 - N), constant_values=I32MAX),
                      jnp.pad(delete, (0, n2 - N), constant_values=0),
                      jnp.pad(ee, (0, n2 - N))]
                     + [jnp.zeros((n2,), jnp.int32)] * (SUB - 5))
    qvt2 = jnp.pad(q_vt, (0, r2 - R), constant_values=-2)[:, None]
    qts2 = jnp.pad(q_ts, (0, r2 - R), constant_values=0)[:, None]

    row = lambda r, n: (r, 0)
    col = lambda r, n: (0, n)
    od, og = pl.pallas_call(
        functools.partial(_knn_kernel, kp=kp, kb=kb),
        grid=(r2 // SUB, n2 // bn),
        in_specs=[pl.BlockSpec((SUB, D), row),        # queries
                  pl.BlockSpec((D, bn), col),         # embeddings, transposed
                  pl.BlockSpec((SUB, bn), col),       # per-entry metadata
                  pl.BlockSpec((SUB, 1), row),        # query vtype
                  pl.BlockSpec((SUB, 1), row)],       # query snapshot ts
        out_specs=[pl.BlockSpec((SUB, kb), row),
                   pl.BlockSpec((SUB, kb), row)],
        out_shape=[jax.ShapeDtypeStruct((r2, kb), jnp.float32),
                   jax.ShapeDtypeStruct((r2, kb), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((SUB, W), jnp.float32),
                        pltpu.VMEM((SUB, W), jnp.int32)],
        interpret=interpret,
    )(v2, e2, meta, qvt2, qts2)
    return od[:R, :k], og[:R, :k]
