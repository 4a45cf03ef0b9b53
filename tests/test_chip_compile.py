"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (what every other kernel test runs) cannot show what the
chip's compiler refuses: block tiling, unsupported primitives, scalar or
vector memory overruns.  These tests compile each kernel of the read path
for one device of a described ``v5e:2x2`` at the serving sizes of
``configs/a1_kg.py`` — the shapes the fused query programs pass at the
caps frontier 4096 / expand 16384 over one chip's share of the store —
and check that the kernel is in the compiled program.  Nothing runs; no
chip is needed.
"""
import os

import jax
import jax.numpy as jnp
import pytest

I32, F32 = jnp.int32, jnp.float32
E_POOL = 50_000_000          # a1_kg cap_e: one shard's half-edge pool
N_INDEX = 16_000_000         # a1_kg cap_idx: one shard's primary index
F, E = 4096, 16384           # a1_kg frontier / expand caps


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _edge_expand():
    from repro.kernels.edge_expand.kernel import expand
    cap_tiles = F + 1 + E // 128

    def fn(starts, degs, p0, p1, p2, p3, item, tw):
        return expand(starts, degs, (p0, p1, p2, p3), item, tw, tile=128,
                      cap_tiles=cap_tiles)
    return fn, [((F,), I32)] * 2 + [((E_POOL,), I32)] * 4 + [
        ((cap_tiles,), I32)] * 2


def _searchsorted_left():
    from repro.kernels.sorted_lookup.kernel import searchsorted_left
    return searchsorted_left, [((N_INDEX,), I32), ((F,), I32)]


def _searchsorted_left_ranged():
    from repro.kernels.sorted_lookup.kernel import searchsorted_left_ranged
    return searchsorted_left_ranged, [((N_INDEX,), I32)] + [((F,), I32)] * 3


def _sort_rows():
    from repro.kernels.dedup_compact.kernel import sort_rows
    return sort_rows, [((64, 2 * F), I32)]


def _dedup_compact_rows():
    from repro.kernels.dedup_compact.kernel import dedup_compact_rows
    return (lambda x: dedup_compact_rows(x, F)), [((85, 4 * E + F), I32)]


def _sort_pairs():
    from repro.kernels.dedup_compact.kernel import sort_pairs
    # the shared-budget program's widest compaction at 64 queries
    return sort_pairs, [((3_153_920,), I32)] * 2


def _knn_topk():
    from repro.kernels.knn_topk.kernel import knn_topk
    n, d = 1 << 20, 32
    return ((lambda *a: knn_topk(*a, 8)),
            [((8, d), F32), ((n, d), F32)] + [((n,), I32)] * 4
            + [((8,), I32)] * 2)


CASES = {f.__name__[1:]: f for f in (
    _edge_expand, _searchsorted_left, _searchsorted_left_ranged, _sort_rows,
    _dedup_compact_rows, _sort_pairs, _knn_topk)}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = CASES[kernel]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
