"""Ragged CSR expansion Pallas TPU kernel (A1 edge enumeration, §3.4).

The paper's edge enumeration walks a vertex's edge list — an (address, size)
span in FaRM.  The TPU adaptation streams those spans tile-by-tile:

* a host/jnp *plan* (ref.plan) flattens the ragged spans into a dense grid of
  128-lane tiles: tile i serves frontier item ``item_of_tile[i]``, its
  ``tw``-th tile;
* each pool is viewed as (rows, 128) so every DMA moves whole (8, 128)
  VMEM tiles; a scalar-prefetched per-tile row drives the BlockSpec
  index_map, which streams the 8-row group holding the tile's first edge
  and the group after it (spans are not tile-aligned);
* the kernel rotates the 16-row window to the span offset (``pltpu.roll``),
  picks the two rows the tile straddles, and masks the tail.

One grid step emits one tile into row ``t % 8`` of an (8, 128) output block
that stays resident for eight consecutive steps, so every output store is a
whole aligned block.

Output is tile-padded ragged: lane j of tile i is edge ``tw*T + j`` of item
``item_of_tile[i]``, or -1.  Downstream (dedup/routing) consumes the mask.

Why not one DMA per edge?  Degree skew (the paper sees degrees > 10M) makes
per-edge gathers pathological; per-tile streaming keeps the DMA engine at
line rate for any degree distribution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUB = 8          # sublanes per (8, 128) i32 tile
# tiles per pallas_call: its two prefetched (CHUNK,) i32 tables must fit the
# 1 MiB scalar memory, so larger plans run as several calls
CHUNK = 1 << 16


def _pick_row(win, r):
    """Row ``r`` (traced) of a (16, L) window, as (1, L): exact masked sum
    (one nonzero term per lane), which needs no dynamic sublane slice."""
    rows = jax.lax.broadcasted_iota(jnp.int32, win.shape, 0)
    return jnp.sum(jnp.where(rows == r, win, 0), axis=0, keepdims=True)


def _expand_kernel(row_ref, meta_ref, *refs, n_pools: int):
    t = pl.program_id(0)
    in_refs, out_refs = refs[:2 * n_pools], refs[2 * n_pools:]
    r = row_ref[t] % SUB                 # tile's first row within its group
    off = meta_ref[t] // (2 * LANES)     # span offset within that row
    n_ok = meta_ref[t] % (2 * LANES)     # valid lanes of this tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0)
    from_lo = lane < LANES - off
    valid = lane < n_ok
    shift = (LANES - off) % LANES

    @pl.when(t % SUB == 0)
    def _init():
        for o in out_refs:
            o[...] = jnp.full((SUB, LANES), -1, jnp.int32)

    for p in range(n_pools):
        win = jnp.concatenate([in_refs[2 * p][...], in_refs[2 * p + 1][...]],
                              axis=0)                        # (16, 128)
        win = pltpu.roll(win, shift, 1)  # lane c of row q -> edge q*128+c+off
        row = jnp.where(from_lo, _pick_row(win, r), _pick_row(win, r + 1))
        row = jnp.where(valid, row, -1)
        o = out_refs[p]
        o[...] = jnp.where(sub == t % SUB, row, o[...])


@functools.partial(jax.jit, static_argnames=("tile", "cap_tiles", "interpret"))
def expand(starts, degs, pools, item_of_tile, tw_of_tile, *, tile: int = 128,
           cap_tiles: int, interpret: bool = False):
    """See ref.expand; plan arrays are produced by ref.plan (jnp, cheap)."""
    if tile != LANES:
        raise ValueError(f"edge_expand streams {LANES}-lane tiles, got {tile}")
    F = degs.shape[0]
    E = pools[0].shape[0]
    n_pools = len(pools)
    # per-tile scalars: absolute pool row, and (span offset, valid lanes)
    item_c = jnp.minimum(item_of_tile, F - 1)
    start = starts[item_c] + tw_of_tile * LANES
    n_ok = jnp.where(item_of_tile < F,
                     jnp.clip(degs[item_c] - tw_of_tile * LANES, 0, LANES), 0)
    start = jnp.where(n_ok > 0, start, 0)
    ct8 = pl.cdiv(cap_tiles, SUB) * SUB
    row = jnp.pad(start // LANES, (0, ct8 - cap_tiles))
    meta = jnp.pad((start % LANES) * (2 * LANES) + n_ok, (0, ct8 - cap_tiles))

    rows = pl.cdiv(E, LANES)
    n_grp = pl.cdiv(rows, SUB)
    pools2 = tuple(p.reshape(rows, LANES) if E == rows * LANES else
                   jnp.pad(p, (0, rows * LANES - E),
                           constant_values=-1).reshape(rows, LANES)
                   for p in pools)

    def spec(plus_one):
        def index_map(t, row_ref, meta_ref):
            g = row_ref[t] // SUB + plus_one
            return (jnp.minimum(g, n_grp - 1), 0)
        return pl.BlockSpec((SUB, LANES), index_map)

    call = pl.pallas_call(
        functools.partial(_expand_kernel, n_pools=n_pools),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(min(ct8, CHUNK),),
            in_specs=[spec(k) for _ in range(n_pools) for k in (0, 1)],
            out_specs=[pl.BlockSpec((SUB, LANES), lambda t, *_: (t // SUB, 0))
                       for _ in range(n_pools)]),
        out_shape=[jax.ShapeDtypeStruct((min(ct8, CHUNK), LANES), jnp.int32)
                   for _ in range(n_pools)],
        interpret=interpret,
    )
    args = [p for p in pools2 for _ in (0, 1)]
    parts = []
    for c0 in range(0, ct8, CHUNK):
        n = min(CHUNK, ct8 - c0)
        r_c = jnp.pad(row[c0:c0 + n], (0, min(ct8, CHUNK) - n))
        m_c = jnp.pad(meta[c0:c0 + n], (0, min(ct8, CHUNK) - n))
        parts.append([o[:n] for o in call(r_c, m_c, *args)])
    outs = [p[0] if len(parts) == 1 else jnp.concatenate(p)
            for p in zip(*parts)]
    return tuple(o[:cap_tiles].reshape(-1) for o in outs)
