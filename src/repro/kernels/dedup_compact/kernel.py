"""Dedup/compact Pallas TPU kernel (the per-hop frontier compaction, §3.4).

Hardware adaptation: the reference path compacts every hop with a full-width
``jax.lax.sort`` over the candidate matrix — an XLA sort that materializes
the whole (R, W) buffer in HBM per comparison pass.  Here each row block is
sorted *inside VMEM* with a bitonic network: W is padded to a power of two,
every compare-exchange stage is one vectorized select over the resident
block, and the dedup ("mark duplicates PAD, sort again, slice the cap") is
fused into the same kernel so the full-width sorted intermediate never
leaves VMEM.

Every sequence sits in VMEM as (W/128, 128) and is sorted in place by
``kernels/bitonic.py`` (rotate + select exchanges, tile by tile), so the
kernel compiles to a few loops whatever the width.

Three entry points mirroring the ref oracle (bit-identical by construction —
integer sorting has one answer):

  * :func:`sort_rows`            — row-wise ascending sort;
  * :func:`dedup_compact_rows`   — sorted-unique first-``cap`` compaction +
                                   per-row unique counts;
  * :func:`sort_pairs`           — lexicographic flat (seg, gid) pair sort
                                   (the shared-frontier compaction), a
                                   two-key compare-exchange on both arrays.

Grid: (rows,); one row's whole (padded) width lives in VMEM per program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic import LANES, SUB, for_tiles, sort_refs

I32MAX = 2**31 - 1
PAD = I32MAX
TILE = SUB * LANES          # positions per (8, 128) tile


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def vmem_limit(block_bytes: int) -> int:
    """Scoped-VMEM request for a kernel whose operand blocks and scratch
    total ``block_bytes``: double-buffered, within a v5e core's 128 MiB."""
    return int(min(100 << 20, max(16 << 20, 3 * block_bytes)))


def _sort_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]
    sort_refs([o_ref], flat=True)


def _dedup_kernel(x_ref, o_ref, n_ref, buf, *, kr: int):
    buf[...] = x_ref[...]
    sort_refs([buf], flat=True)
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 1)

    def mark(t, carry):
        # first-of-run flags: position i differs from i - 1 (the previous
        # tile's last element is carried in); non-first slots become PAD
        last, cnt = carry
        rows = pl.ds(pl.multiple_of(t * SUB, SUB), SUB)
        x = buf[rows, :]
        lr = pltpu.roll(x, 1, 1)
        prev = jnp.where(lane == 0, pltpu.roll(lr, 1, 0), lr)
        head = pltpu.roll(pltpu.roll(last, 1, 1), 1, 0)
        prev = jnp.where((lane == 0) & (sub == 0), head, prev)
        first = (x != PAD) & (x != prev)
        buf[rows, :] = jnp.where(first, x, PAD)
        return x, cnt + first.astype(jnp.int32)

    _, cnt = for_tiles(
        buf.shape[0] // SUB, mark,
        (jnp.full((SUB, LANES), -1, jnp.int32),
         jnp.zeros((SUB, LANES), jnp.int32)))
    n_ref[...] = jnp.broadcast_to(jnp.sum(cnt), n_ref.shape)
    sort_refs([buf], flat=True)             # uniques first, PAD behind
    o_ref[...] = buf[:kr, :]


def _pairs_kernel(s_ref, g_ref, os_ref, og_ref):
    os_ref[...] = s_ref[...]
    og_ref[...] = g_ref[...]
    sort_refs([os_ref, og_ref], flat=True)


def _as_tiles(x, W2: int):
    """(R, W) -> (R * W2/128, 128): each row a (W2/128, 128) block,
    padded with I32MAX (sorts behind every real value)."""
    R, W = x.shape
    return jnp.pad(x, ((0, 0), (0, W2 - W)),
                   constant_values=I32MAX).reshape(R * W2 // LANES, LANES)


@functools.partial(jax.jit, static_argnames="interpret")
def sort_rows(x, *, interpret: bool = False):
    """Row-wise ascending sort of (R, W) i32; == jax.lax.sort(x, dim=1).

    Values must be <= INT32_MAX (the pad fill), which every frontier gid
    and the PAD sentinel satisfy.
    """
    R, W = x.shape
    W2 = max(TILE, _pow2ceil(W))
    m = W2 // LANES
    spec = pl.BlockSpec((m, LANES), lambda r: (r, 0))
    out = pl.pallas_call(
        _sort_kernel,
        grid=(R,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R * m, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(16 * W2)),
        interpret=interpret,
    )(_as_tiles(x, W2))
    # pad values are I32MAX: they sort behind every real value, so the
    # leading W columns of each padded row are exactly the sorted row
    return out.reshape(R, W2)[:, :W]


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def dedup_compact_rows(x, cap: int, *, interpret: bool = False):
    """(R, W) candidates -> ((R, cap), (R,) unique counts); see ref oracle."""
    R, W = x.shape
    W2 = max(TILE, _pow2ceil(W))
    m = W2 // LANES
    # a row of width W holds <= W <= W2 uniques, so when cap exceeds the
    # padded width the kernel emits W2 columns and the tail is pure PAD; the
    # emitted width is tile-aligned and sliced back to cap here
    kcap = min(pl.cdiv(cap, TILE) * TILE, W2)
    kr = kcap // LANES
    out, n = pl.pallas_call(
        functools.partial(_dedup_kernel, kr=kr),
        grid=(R,),
        in_specs=[pl.BlockSpec((m, LANES), lambda r: (r, 0))],
        out_specs=[pl.BlockSpec((kr, LANES), lambda r: (r, 0)),
                   pl.BlockSpec((SUB, LANES), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((R * kr, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((R * SUB, LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((m, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(16 * W2)),
        interpret=interpret,
    )(_as_tiles(x, W2))
    out = out.reshape(R, kcap)[:, :min(cap, kcap)]
    if out.shape[1] < cap:
        out = jnp.pad(out, ((0, 0), (0, cap - out.shape[1])),
                      constant_values=I32MAX)
    return out, n.reshape(R, TILE)[:, 0]


@functools.partial(jax.jit, static_argnames="interpret")
def sort_pairs(k1, k2, *, interpret: bool = False):
    """Lexicographic ascending sort of flat (k1, k2) i32 pairs.

    == jax.lax.sort((k1, k2), num_keys=2).  Pads with (I32MAX, I32MAX),
    which sorts behind every real pair.  Both keys sit in VMEM whole.
    """
    (W,) = k1.shape
    W2 = max(TILE, _pow2ceil(W))
    s, g = _as_tiles(k1[None], W2), _as_tiles(k2[None], W2)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    os_, og = pl.pallas_call(
        _pairs_kernel,
        in_specs=[vmem, vmem],
        out_specs=[vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct(s.shape, jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(16 * W2)),
        interpret=interpret,
    )(s, g)
    return os_.reshape(-1)[:W], og.reshape(-1)[:W]
