"""Serving: LM generation engine + FMBI retrieval server."""
import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.datasets import osm_like
from repro.launch.train import reduced_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.serve.engine import LMServer, RetrievalServer


def test_lm_server_greedy_generation():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = ModelConfig(
        name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=100, dtype="float32", chunk_q=16,
    )
    with jax.set_mesh(mesh):
        params = M.init_params(cfg, jax.random.key(0))
        server = LMServer(cfg, params)
        prompts = np.random.default_rng(0).integers(0, 100, (2, 12))
        out = server.generate(prompts, max_new=5)
    assert out.shape == (2, 5)
    assert out.dtype.kind in "iu"
    assert (out >= 0).all() and (out < cfg.padded_vocab).all()


def test_retrieval_server_exact_and_kernel_paths_agree():
    pts = osm_like(4096, seed=1)
    srv = RetrievalServer(pts, levels=5)
    qs = np.random.default_rng(2).random((8, 2)).astype(np.float32)
    rows, d2, exact = srv.knn(qs, 8, n_candidate_leaves=12)
    _, d2k = srv.knn_kernel(qs, 8)
    for i, q in enumerate(qs):
        od = np.sort(np.sum((pts - q) ** 2, axis=1))[:8]
        if exact[i]:
            np.testing.assert_allclose(np.sort(d2[i]), od, rtol=1e-3,
                                       atol=1e-6)
        np.testing.assert_allclose(np.sort(d2k[i]), od, rtol=1e-3,
                                   atol=1e-6)


def test_adaptive_residency_hit_rate_improves_for_focused_stream():
    """AMBI's residency policy: a focused query stream converges onto a hot
    leaf set (high hit rate); a uniform stream keeps missing."""
    pts = osm_like(20_000, seed=3)
    rng = np.random.default_rng(4)

    focused = RetrievalServer(pts, levels=6, adaptive=True, hot_capacity=8)
    for _ in range(30):
        qs = (rng.random((16, 2)) * 0.05 + 0.6).astype(np.float32)
        focused.knn(qs, 4)

    uniform = RetrievalServer(pts, levels=6, adaptive=True, hot_capacity=8)
    for _ in range(30):
        qs = rng.random((16, 2)).astype(np.float32)
        uniform.knn(qs, 4)

    assert focused.stats.hit_rate > uniform.stats.hit_rate + 0.2


def test_retrieval_server_boots_from_nodetable_snapshot(tmp_path):
    """Bulk load on CPU, snapshot the flat table, and boot the serving path
    from the snapshot without rebuilding: exact answers, adaptive residency
    via nearest_leaf."""
    from repro.core import PageStore, bulk_load

    pts = osm_like(8_000, seed=7)
    idx = bulk_load(pts, 250, PageStore(250))
    snap = tmp_path / "index.npz"
    idx.save(snap)

    srv = RetrievalServer.from_snapshot(snap, adaptive=True, hot_capacity=16)
    assert not srv._routed
    qs = np.random.default_rng(5).random((16, 2)).astype(np.float32)
    rows, d2, exact = srv.knn(qs, 8, n_candidate_leaves=24)
    for i, q in enumerate(qs):
        if exact[i]:
            od = np.sort(np.sum((pts - q) ** 2, axis=1))[:8]
            np.testing.assert_allclose(np.sort(d2[i]), od, rtol=1e-3,
                                       atol=1e-6)
    assert srv.stats.queries == 16  # adaptive residency ran via nearest_leaf

    # bridged leaf grid matches the table: window counts stay exact
    from repro.core import jax_index as JI
    import jax.numpy as jnp

    los = qs[:4] - 0.05
    his = qs[:4] + 0.05
    counts = JI.window_count(srv.index, jnp.asarray(los), jnp.asarray(his))
    for i in range(4):
        ref = int(np.sum(np.all((pts >= los[i]) & (pts <= his[i]), axis=1)))
        assert int(counts[i]) == ref
