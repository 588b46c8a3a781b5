"""Compile rehearsal for the TPU: the served path's Pallas kernels and jitted
stages, compiled for a described (not attached) v5e chip at the 10M-point
leaf layouts.

Interpret mode cannot show a block the Mosaic compiler refuses, a kernel
that overflows scoped VMEM, or a program that does not fit the chip; this
compile can, at no chip time.  Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and several test workers
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import distributed_jax as dj
from repro.core import queries_jax as qj
from repro.core.pagestore import leaf_capacity
from repro.kernels import knn_topk, ops, window_filter

# leaf layouts of bulk-loaded 10M-point tables (NodeTable.device_layout):
# osm_like(10_000_000) and nycyt_like(10_000_000, d=5), both seed 0
LAYOUTS = {
    "osm_10m_d2": dict(d=2, n_leaves=29431, levels=(1, 204, 29431)),
    "nycyt_10m_d5": dict(d=5, n_leaves=58869, levels=(1, 93, 744, 58869)),
}
N_QUERIES = 128          # one full frontend microbatch, padded to pow2
PAIRS = qj.PAIR_CHUNK    # the largest pair bucket a window chunk scans
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The wrappers pick interpret mode from the attached backend (CPU
    here); the rehearsal compiles what a TPU backend would run."""
    monkeypatch.setattr(ops, "interpret_default", lambda: False)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel=True):
    """Compile ``fn`` (jitted already, or a plain wrapper) for the chip;
    the program must hold a Mosaic kernel and fit the chip's HBM."""
    lower = fn.lower if hasattr(fn, "lower") else jax.jit(fn).lower
    compiled = lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < HBM_BYTES
    return compiled


def _table(sharding, layout, compressed=False):
    """A ``DeviceTable`` of shapes only, at one of the 10M layouts."""
    d, n_l = layout["d"], layout["n_leaves"]
    s = leaf_capacity(d)

    def f(*shape, dtype=jnp.float32):
        return _sds(sharding, shape, dtype)

    levels = tuple(
        (f(n, d), f(n, d), f(n, dtype=jnp.int32), f(n, dtype=jnp.int32))
        for n in layout["levels"]
    )
    bf = jnp.bfloat16
    return qj.DeviceTable(
        leaf_pts=f(n_l, s, d),
        leaf_ids=f(n_l, s, dtype=jnp.int32),
        leaf_counts=f(n_l, dtype=jnp.int32),
        leaf_lo=f(n_l, d),
        leaf_hi=f(n_l, d),
        levels=levels,
        cold_lo=f(0, d),
        cold_hi=f(0, d),
        leaf_lo_c=f(n_l, d, dtype=bf) if compressed else None,
        leaf_hi_c=f(n_l, d, dtype=bf) if compressed else None,
        levels_c=(tuple((f(n, d, dtype=bf), f(n, d, dtype=bf))
                        for n in layout["levels"]) if compressed else None),
    )


# -- the four tiled kernels ----------------------------------------------
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("box_dtype", [jnp.float32, jnp.bfloat16])
def test_box_hits_tiled_compiles(one_chip, compiled_kernels, layout,
                                 box_dtype):
    lay = LAYOUTS[layout]
    d, n = lay["d"], lay["n_leaves"]
    _compile(
        ops.box_hits_tiled,
        _sds(one_chip, (n, d), box_dtype), _sds(one_chip, (n, d), box_dtype),
        _sds(one_chip, (N_QUERIES, d)), _sds(one_chip, (N_QUERIES, d)),
    )


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("box_dtype", [jnp.float32, jnp.bfloat16])
def test_leaf_mindist_tiled_compiles(one_chip, compiled_kernels, layout,
                                     box_dtype):
    lay = LAYOUTS[layout]
    d, n = lay["d"], lay["n_leaves"]
    _compile(
        ops.leaf_mindist_tiled,
        _sds(one_chip, (N_QUERIES, d)),
        _sds(one_chip, (n, d), box_dtype), _sds(one_chip, (n, d), box_dtype),
    )


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_pair_window_ids_compiles(one_chip, compiled_kernels, layout):
    lay = LAYOUTS[layout]
    d, n_l = lay["d"], lay["n_leaves"]
    s = leaf_capacity(d)
    i32 = jnp.int32
    _compile(
        ops.pair_window_ids,
        _sds(one_chip, (N_QUERIES, d)), _sds(one_chip, (N_QUERIES, d)),
        _sds(one_chip, (n_l, d)), _sds(one_chip, (n_l, d)),
        _sds(one_chip, (n_l, s, d)), _sds(one_chip, (n_l, s), i32),
        _sds(one_chip, (n_l,), i32),
        _sds(one_chip, (PAIRS,), i32), _sds(one_chip, (PAIRS,), i32),
        _sds(one_chip, (PAIRS,), i32),
    )


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("pairs", [8, PAIRS])
def test_pair_dist2_compiles(one_chip, compiled_kernels, layout, pairs):
    lay = LAYOUTS[layout]
    d, n_l = lay["d"], lay["n_leaves"]
    s = leaf_capacity(d)
    i32 = jnp.int32
    _compile(
        ops.pair_dist2,
        _sds(one_chip, (N_QUERIES, d)), _sds(one_chip, (n_l, s, d)),
        _sds(one_chip, (n_l,), i32),
        _sds(one_chip, (pairs,), i32), _sds(one_chip, (pairs,), i32),
    )


@pytest.mark.parametrize("n_l", [1, 3])
def test_pair_kernels_compile_on_a_tiny_table(one_chip, compiled_kernels,
                                              n_l):
    """An adaptive table early in its refinement holds fewer leaves than
    one ``PAIR_ROWS`` id tile."""
    s, d, i32 = leaf_capacity(2), 2, jnp.int32
    pairs = (_sds(one_chip, (8,), i32), _sds(one_chip, (8,), i32))
    pts = _sds(one_chip, (n_l, s, d))
    counts = _sds(one_chip, (n_l,), i32)
    q = _sds(one_chip, (N_QUERIES, d))
    box = _sds(one_chip, (n_l, d))
    _compile(ops.pair_window_ids, q, q, box, box, pts,
             _sds(one_chip, (n_l, s), i32), counts, *pairs,
             _sds(one_chip, (8,), i32))
    _compile(ops.pair_dist2, q, pts, counts, *pairs)


def test_vmem_tiles_at_the_10m_layout():
    """The tile the frontier and mindist kernels get at 10M leaves is the
    one this file compiles (its budget counts the lane padding)."""
    for lay in LAYOUTS.values():
        for b in (4, 2):
            nt, qt = window_filter.vmem_tiles(
                lay["n_leaves"], N_QUERIES, lay["d"], in_bytes=b
            )
            assert (nt, qt) == (1024, 128)
    assert knn_topk.PAIR_ROWS == window_filter.PAIR_ROWS == 8


# -- the served path's jitted stages, with the kernels compiled ----------
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("compressed", [False, True])
def test_frontier_count_compiles(one_chip, compiled_kernels, layout,
                                 compressed):
    lay = LAYOUTS[layout]
    dev = _table(one_chip, lay, compressed=compressed)
    q = _sds(one_chip, (N_QUERIES, lay["d"]))
    _compile(qj._frontier_count, dev, q, q, True)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_fused_pack_scan_compiles(one_chip, compiled_kernels, layout):
    lay = LAYOUTS[layout]
    dev = _table(one_chip, lay)
    q = _sds(one_chip, (N_QUERIES, lay["d"]))
    hits = _sds(one_chip, (N_QUERIES, lay["n_leaves"]), jnp.bool_)
    offset = _sds(one_chip, (), jnp.int32)
    _compile(qj._fused_pack_scan, dev, q, q, hits, offset, PAIRS, True)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("n_candidate_leaves", [8, 64])
def test_knn_core_fused_compiles(one_chip, compiled_kernels, layout,
                                 n_candidate_leaves):
    lay = LAYOUTS[layout]
    dev = _table(one_chip, lay)
    qs = _sds(one_chip, (N_QUERIES, lay["d"]))
    b0 = _sds(one_chip, (), jnp.int32)
    _compile(qj._knn_core_fused, dev, qs, b0, 16, n_candidate_leaves, True)



# -- the four-chip collective rounds (chip_smoke.py --four-chips) ---------
def test_shard_map_rounds_compile_on_four_chips(topo):
    """The k-NN and window-count ``shard_map`` rounds over a 4-shard
    stacked table of the 10M osm layout, one shard per chip."""
    lay = LAYOUTS["osm_10m_d2"]
    d, s = lay["d"], leaf_capacity(lay["d"])
    n_l = -(-lay["n_leaves"] // 4)    # leaves per shard, balanced split
    mesh = jax.sharding.Mesh(topo.devices[:4], ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    split = NamedSharding(mesh, PartitionSpec("data"))
    rep = NamedSharding(mesh, PartitionSpec())
    i32 = jnp.int32
    q = _sds(rep, (32, d))
    pts = _sds(split, (4, n_l, s, d))
    counts = _sds(split, (4, n_l), i32)
    knn = _compile(
        dj.knn_shard_map_round(mesh, "data", 16, n_l),
        q, pts, _sds(split, (4, n_l, s), i32), counts,
        _sds(split, (4, n_l, d)), _sds(split, (4, n_l, d)), kernel=False,
    )
    assert "all-gather" in knn.as_text()
    win = _compile(dj.window_count_shard_map_round(mesh, "data"),
                   q, q, pts, counts, kernel=False)
    assert "all-reduce" in win.as_text()
