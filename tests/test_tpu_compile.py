"""Compiles of the search path for a described TPU v5e — no chip needed.

The TPU compiler ships with jaxlib and compiles for a chip that is only
described (``jax.experimental.topologies``).  These tests compile, at
the widths the bring-up smoke (``chip_smoke.py``) serves, what the chip
would run: the three Pallas intersect kernels, the fused fold over ~100M
resident postings, and the sharded fold on a 2x2 mesh.  They catch what
interpret mode cannot — primitives Mosaic does not lower, unaligned
slices, fast-memory overruns — and say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.  The persistent compilation cache is off
around these compiles, since an executable for a described chip can be
written but never read back here.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.device_engine import _build_sharded_fold, _fused_fold
from repro.kernels.intersect.kernel import (
    intersect_count_kernel,
    intersect_members_count_kernel,
    intersect_members_kernel,
)

N_POSTINGS = 100_000_000  # ~1M wiki-like documents, the smoke's default
LONG_WIDTH = 16_384  # a leaf-cluster segment of a frequent term at that size
LEVELS = 3  # 128-ary levels covering a posting list of up to 2M documents


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _tiles(n):
    """``n`` entries padded to whole (8, 128) int32 tiles, as resident."""
    return -(-n // 1024) * 1024


def _fence_lens(n):
    """Lengths of the resident fences of ``n`` postings at ``LEVELS``."""
    return [_tiles(-(-_tiles(n) // 128**j)) for j in range(1, LEVELS)]


@pytest.mark.parametrize(
    "kernel",
    [intersect_count_kernel, intersect_members_kernel, intersect_members_count_kernel],
    ids=["count", "members", "members_count"],
)
def test_intersect_kernel_lowers_for_v5e(one_chip, kernel):
    short = _spec((64, 512), one_chip)
    long = _spec((64, LONG_WIDTH), one_chip)
    compiled = kernel.lower(short, long, block_q=8, tile_s=128, tile_l=128).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel, not a fallback


def test_fused_fold_compiles_at_smoke_size(one_chip):
    group_width, stage_levels = 512, (LEVELS,) * 4  # arity-5 queries, 1M docs
    compiled = _fused_fold.lower(
        _spec((_tiles(N_POSTINGS),), one_chip),
        tuple(_spec((n,), one_chip) for n in _fence_lens(N_POSTINGS)),
        _spec((4, 1 << 16), one_chip),
        _spec((2, len(stage_levels) * group_width), one_chip),
        group_width=group_width,
        stage_levels=stage_levels,
        n_queries_pad=64,
        return_members=True,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * N_POSTINGS  # the postings are resident
    assert mem.temp_size_in_bytes < 64 << 20  # per-batch state stays small


def test_sharded_fold_compiles_on_2x2_with_one_all_reduce(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    group_width, stage_levels = 512, (LEVELS, LEVELS)
    fold = _build_sharded_fold(mesh, group_width, stage_levels, 64, False)
    rows = NamedSharding(mesh, P("data", None))
    plan = NamedSharding(mesh, P("data", None, None))
    width = _tiles(N_POSTINGS // 4)
    compiled = fold.lower(
        _spec((4, width), rows),
        tuple(_spec((4, n), rows) for n in _fence_lens(width)),
        _spec((4, 4, 1 << 14), plan),
        _spec((4, 2, len(stage_levels) * group_width), plan),
    ).compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"\ball-reduce(?:-start)?\(", hlo)) == 1, hlo[:2000]
