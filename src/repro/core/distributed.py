"""Parallel bulk loading and distributed query processing (paper Section 5).

Two layers:

1. ``parallel_bulk_load`` — the paper's central-server / m-local-servers
   architecture, simulated at page-I/O granularity for the Figure-11
   experiments.  The central server partitions a gamma*m page sample into m
   subspaces with a SplitTree, streams the remaining points to their owners,
   and every local server bulk loads its own FMBI.  The reported cost is the
   makespan (slowest server), per Beame et al. [4] as cited by the paper.

2. ``shard_build`` / ``shard_knn`` — the TPU-native mapping of the same
   architecture onto a device mesh with ``shard_map``: the "data" mesh axis
   plays the m local servers.  A global sample is all-gathered to compute
   the top-level splits (central Step 1), points travel to their owner shard
   with a fixed-capacity ``all_to_all`` (the network distribution step), and
   each shard builds its local ``JaxIndex`` independently.  Queries then
   touch only qualified shards; k-NN follows the paper's two-round
   SpatialHadoop protocol (local candidates, then a global top-k).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import jax_index
from .fmbi import Index, bulk_load
from .nodetable import NodeTable
from .pagestore import IOStats, PageStore, branch_capacity, leaf_capacity
from .splittree import build_group_median_tree

P = jax.sharding.PartitionSpec


# --------------------------------------------------------------------------
# 1. host-level m-server simulation (Figure 11)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ParallelBuild:
    indexes: list[Index]
    central_io: IOStats
    per_server_io: list[IOStats]
    row_maps: list[np.ndarray] = dataclasses.field(default_factory=list)

    @property
    def makespan_io(self) -> int:
        """Parallel cost = slowest local server (paper Section 5)."""
        return max(s.total for s in self.per_server_io) if self.per_server_io else 0

    @property
    def total_io(self) -> int:
        return self.central_io.total + sum(s.total for s in self.per_server_io)

    def merged_table(self) -> NodeTable:
        """Combine the per-server node tables into one global table.

        Local dataset rows are mapped back to global ids through
        ``row_maps`` and each server's page ids are shifted into a single
        flat page namespace, so the result is a shippable snapshot of the
        whole distributed index: a synthetic root over the m server roots
        that any client can query (or ``NodeTable.save``) without touching
        the per-server stores.
        """
        offsets, off = [], 0
        for idx in self.indexes:
            offsets.append(off)
            off += idx.store.allocated_pages
        return NodeTable.merged(
            [idx.table for idx in self.indexes],
            self.row_maps,
            offsets,
            root_page=off,
        )

    def merged_index(self, points: np.ndarray, buffer_pages: int) -> Index:
        """A queryable :class:`Index` over :meth:`merged_table` with a fresh
        (cold) page store — the client-side view of the cluster's index."""
        d = points.shape[1]
        table = self.merged_table()
        store = PageStore(buffer_pages)
        store.mark_allocated(int(table.page_id.max()) + 1)
        return Index(table, d, leaf_capacity(d), branch_capacity(d), store, points)


def parallel_bulk_load(
    points: np.ndarray,
    m: int,
    buffer_pages: int,
    rng: np.random.Generator | None = None,
) -> ParallelBuild:
    """Bulk load FMBI on m servers; each server gets buffer_pages/m pages."""
    rng = rng or np.random.default_rng(0)
    n, d = points.shape
    c_l = leaf_capacity(d)
    central = PageStore(buffer_pages)
    if m == 1:
        store = PageStore(buffer_pages)
        idx = bulk_load(points, buffer_pages, store, rng)
        return ParallelBuild([idx], IOStats(), [store.stats], [np.arange(n)])

    # central server: SplitTree with m-1 splits over a gamma*m page sample
    gamma = max(buffer_pages // m, 1)
    p_total = -(-n // c_l)
    sample_pages = min(gamma * m, p_total)
    need = min(sample_pages * c_l, n)
    perm = rng.permutation(n)
    samp = perm[:need]
    group_pages = max(need // (m * c_l), 1)
    trim = m * group_pages * c_l
    central.read_run(sample_pages)
    tree, _, samp_assign = build_group_median_tree(
        points[samp[:trim]], m, group_pages, c_l
    )
    # stream the rest: the central server reads the remaining pages once
    rest = np.concatenate([samp[trim:], perm[need:]])
    central.read_run(-(-len(rest) // c_l))
    rest_assign = tree.route(points[rest]) if len(rest) else np.zeros(0, np.int32)

    server_buffer = max(buffer_pages // m, branch_capacity(d) + 1)
    indexes, per_io, row_maps = [], [], []
    for s in range(m):
        rows = np.concatenate(
            [samp[:trim][samp_assign == s], rest[rest_assign == s]]
        )
        store = PageStore(server_buffer)
        idx = bulk_load(points[rows], server_buffer, store, rng)
        indexes.append(idx)
        per_io.append(store.stats)
        row_maps.append(rows)
    return ParallelBuild(indexes, central.stats, per_io, row_maps)


def parallel_window_cost(
    build: ParallelBuild, lo: np.ndarray, hi: np.ndarray
) -> tuple[int, int]:
    """(n results, makespan page reads) for one window across servers —
    only qualified servers (subspace intersects the window) are probed."""
    from .geometry import mbb_intersects
    from .queries import window_query

    total, costs = 0, []
    for idx in build.indexes:
        if len(idx.points) == 0 or not mbb_intersects(idx.root.mbb, lo, hi):
            continue
        idx.store.buffer.clear()  # cold per-query cost (comparable across m)
        res, io = window_query(idx, lo, hi)
        total += len(res)
        costs.append(io.total)
    return total, (max(costs) if costs else 0)


# --------------------------------------------------------------------------
# 2. shard_map distributed build + queries (TPU-native Section 5)
# --------------------------------------------------------------------------
def gather_topk_merge(d2, rows, axis: str, k_out: int):
    """Global round of the two-round k-NN protocol, inside ``shard_map``:
    all-gather every shard's per-query (distance, id) top-k and merge to
    the ``k_out`` global best.  Returns (d2, ids, source) where ``source``
    is each result's position on the gather axis (its shard).  Shared by
    the ``JaxIndex`` path (``shard_knn``) and the DeviceTable path
    (``distributed_jax.knn_batch_shard_map``)."""
    all_d2 = jax.lax.all_gather(d2, axis)      # (m, Q, kk)
    all_rows = jax.lax.all_gather(rows, axis)
    m, q, kk = all_d2.shape
    flat_d2 = jnp.moveaxis(all_d2, 0, 1).reshape(q, m * kk)
    flat_rw = jnp.moveaxis(all_rows, 0, 1).reshape(q, m * kk)
    negv, topi = jax.lax.top_k(-flat_d2, k_out)
    sel_rows = jnp.take_along_axis(flat_rw, topi, axis=1)
    sel_src = (topi // kk).astype(jnp.int32)
    return -negv, sel_rows, sel_src


def _median_splits(sample: jnp.ndarray, levels: int):
    """Replicated median splits over a gathered sample (central Step 1)."""
    n, d = sample.shape
    g = jnp.zeros(n, dtype=jnp.int32)
    sdim = jnp.zeros((levels, 1 << levels), dtype=jnp.int32)
    sval = jnp.full((levels, 1 << levels), jnp.inf, dtype=sample.dtype)
    pts = sample
    for level in range(levels):
        n_groups = 1 << level
        size = n // n_groups
        gmax = jax.ops.segment_max(pts, g, num_segments=n_groups)
        gmin = jax.ops.segment_min(pts, g, num_segments=n_groups)
        dim_g = jnp.argmax(gmax - gmin, axis=1).astype(jnp.int32)
        key = pts[jnp.arange(n), dim_g[g]]
        order = jnp.lexsort((key, g))
        pts, g = pts[order], g[order]
        half = size // 2
        med = key[order][jnp.arange(n_groups) * size + (half - 1)]
        sdim = sdim.at[level, :n_groups].set(dim_g)
        sval = sval.at[level, :n_groups].set(med)
        g = g * 2 + (jnp.arange(n) % size >= half).astype(jnp.int32)
    return sdim, sval


def _route_tables(points, sdim, sval):
    g = jnp.zeros(points.shape[0], dtype=jnp.int32)
    for level in range(sdim.shape[0]):
        dim = sdim[level, g]
        val = sval[level, g]
        coord = points[jnp.arange(points.shape[0]), dim]
        g = g * 2 + (coord > val).astype(jnp.int32)
    return g


def shard_build(points, mesh, levels_local: int, axis: str = "data",
                sample_per_shard: int = 256):
    """Distributed FMBI build under shard_map.

    ``points``: (n, d) global array, row-sharded over ``axis``.  Returns the
    local index arrays, each with a leading per-shard dimension sharded over
    ``axis``:  (points_sorted, row_ids, split_dim, split_val, leaf_lo,
    leaf_hi, n_mine, gsplit_dim, gsplit_val).  ``row_ids`` carry *global*
    dataset row ids through the all_to_all (-1 for padding), so local
    query answers need no slot translation and ``shard_build_tables`` can
    flatten each shard into a globally-addressed :class:`NodeTable`.
    """
    n_shards = mesh.shape[axis]
    levels_global = int(np.log2(n_shards))
    assert (1 << levels_global) == n_shards, "shard count must be a power of 2"
    n, d = points.shape
    per = n // n_shards
    cap = max(2 * per // n_shards, per // n_shards + sample_per_shard, 8)

    def body(pts_local):
        pts_local = pts_local.reshape(per, d)
        # global row ids of this shard's input slice (the input is
        # row-sharded contiguously over the mesh axis)
        ids_local = (
            jax.lax.axis_index(axis).astype(jnp.int32) * per
            + jnp.arange(per, dtype=jnp.int32)
        )
        # --- central step: sample -> global splits (replicated) ----------
        stride = max(per // sample_per_shard, 1)
        sample_local = pts_local[::stride][:sample_per_shard]
        sample = jax.lax.all_gather(sample_local, axis).reshape(-1, d)
        if levels_global > 0:
            gs_dim, gs_val = _median_splits(sample, levels_global)
            owner = _route_tables(pts_local, gs_dim, gs_val)
        else:
            gs_dim = jnp.zeros((1, 1), jnp.int32)
            gs_val = jnp.zeros((1, 1), pts_local.dtype)
            owner = ids_local * 0
        # --- fixed-capacity dispatch to owner shards ----------------------
        order = jnp.argsort(owner)
        pts_sorted = pts_local[order]
        ids_sorted = ids_local[order]
        owner_sorted = owner[order]
        first = jnp.searchsorted(owner_sorted, jnp.arange(n_shards))
        pos = jnp.arange(per) - first[owner_sorted]
        dropped = pos >= cap  # overflow beyond capacity -> spare slot
        send = jnp.full((n_shards, cap + 1, d),
                        jnp.finfo(pts_local.dtype).max,
                        dtype=pts_local.dtype)
        send_ids = jnp.full((n_shards, cap + 1), -1, dtype=jnp.int32)
        sendmask = jnp.zeros((n_shards, cap + 1), dtype=jnp.int32)
        safe_pos = jnp.where(dropped, cap, pos)
        send = send.at[owner_sorted, safe_pos].set(pts_sorted)
        send_ids = send_ids.at[owner_sorted, safe_pos].set(ids_sorted)
        sendmask = sendmask.at[owner_sorted, safe_pos].max(
            jnp.where(dropped, 0, 1))
        send, send_ids = send[:, :cap], send_ids[:, :cap]
        sendmask = sendmask[:, :cap]
        if n_shards > 1:
            recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                      concat_axis=0, tiled=True)
            recv_ids = jax.lax.all_to_all(send_ids, axis, split_axis=0,
                                          concat_axis=0, tiled=True)
            recvmask = jax.lax.all_to_all(sendmask, axis, split_axis=0,
                                          concat_axis=0, tiled=True)
        else:
            recv, recv_ids, recvmask = send, send_ids, sendmask
        pts_mine = recv.reshape(-1, d)
        valid = recvmask.reshape(-1).astype(bool)
        big = jnp.finfo(pts_mine.dtype).max
        pts_mine = jnp.where(valid[:, None], pts_mine, big)
        # carry the points' global identities through the shuffle: local
        # indexes answer with dataset row ids, not anonymous slots
        row_ids = jnp.where(valid, recv_ids.reshape(-1), -1)
        # --- local FMBI build ---------------------------------------------
        local = jax_index.build(pts_mine, levels_local, row_ids)
        n_mine = valid.sum().reshape(1)
        out = (
            local.points_sorted[None], local.row_ids[None],
            local.split_dim[None], local.split_val[None],
            local.leaf_lo[None], local.leaf_hi[None],
            n_mine[None], gs_dim[None], gs_val[None],
        )
        return out

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=P(axis, None),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                   P(axis), P(axis), P(axis)),
    )
    return fn(points)


def unpack_local_index(shard_out, shard: int, levels_local: int):
    """Materialize shard ``shard``'s JaxIndex from ``shard_build`` output."""
    ps, ri, sd, sv, lo, hi, nm, gd, gv = shard_out
    n_leaves = 1 << levels_local
    return jax_index.JaxIndex(
        points_sorted=ps[shard], row_ids=ri[shard], split_dim=sd[shard],
        split_val=sv[shard], leaf_lo=lo[shard], leaf_hi=hi[shard],
        levels=levels_local, leaf_size=ps[shard].shape[0] // n_leaves,
    )


def table_from_jax_index(jidx) -> NodeTable:
    """Flatten a ``JaxIndex`` leaf grid into a one-level :class:`NodeTable`.

    Empty (all-padding) leaves are dropped and leaf MBBs are recomputed
    tight over the valid points (the grid's segment boxes include the
    +inf padding sentinels).  ``perm`` takes the grid's ``row_ids``
    verbatim, so a ``shard_build`` shard — which carries global dataset
    ids through the all_to_all — flattens into a table that addresses the
    global dataset, ready for the sharded device engine.
    """
    from .fmbi import Node

    pts = np.asarray(jidx.points_sorted, dtype=np.float64)
    ids = np.asarray(jidx.row_ids)
    n_l, s = jidx.n_leaves, jidx.leaf_size
    d = pts.shape[1]
    grid = pts.reshape(n_l, s, d)
    ids2 = ids.reshape(n_l, s)
    valid = ids2 >= 0
    live = np.flatnonzero(valid.any(axis=1))
    if len(live) == 0:
        raise ValueError("grid holds no valid points")
    lo = np.where(valid[..., None], grid, np.inf).min(axis=1)
    hi = np.where(valid[..., None], grid, -np.inf).max(axis=1)
    leaves = [
        Node(
            mbb=np.stack([lo[l], hi[l]]),
            page_id=1 + j,
            point_idx=ids2[l][valid[l]].astype(np.int64),
        )
        for j, l in enumerate(live)
    ]
    if len(leaves) == 1:
        root = leaves[0]
        root.page_id = 0
    else:
        root = Node(
            mbb=np.stack([lo[live].min(axis=0), hi[live].max(axis=0)]),
            page_id=0,
            children=leaves,
        )
    return NodeTable.from_tree(root, d, n_points_hint=int(valid.sum()))


def shard_build_tables(shard_out, levels_local: int) -> list[NodeTable]:
    """Per-shard :class:`NodeTable`s from ``shard_build`` output — the
    bridge that lands the TPU build on the same representation as the
    host m-server simulation (``ParallelBuild`` / ``NodeTable.merged`` /
    the sharded device engine)."""
    n_shards = np.asarray(shard_out[0]).shape[0]
    return [
        table_from_jax_index(unpack_local_index(shard_out, s, levels_local))
        for s in range(n_shards)
    ]


def shard_knn(shard_out, queries, k: int, mesh, levels_local: int,
              axis: str = "data", n_candidate_leaves: int = 8):
    """Two-round distributed k-NN (paper Section 5 / SpatialHadoop):
    local candidates per shard, then a global top-k over gathered
    (distance, row) candidates."""
    n_shards = mesh.shape[axis]
    ps, ri, sd, sv, lo, hi, *_ = shard_out
    n_leaves = 1 << levels_local
    leaf_size = ps.shape[1] // n_leaves

    def body(ps_l, ri_l, sd_l, sv_l, lo_l, hi_l):
        local = jax_index.JaxIndex(
            points_sorted=ps_l.reshape(-1, ps_l.shape[-1]),
            row_ids=ri_l.reshape(-1),
            split_dim=sd_l.reshape(sd_l.shape[1:]),
            split_val=sv_l.reshape(sv_l.shape[1:]),
            leaf_lo=lo_l.reshape(lo_l.shape[1:]),
            leaf_hi=hi_l.reshape(hi_l.shape[1:]),
            levels=levels_local, leaf_size=leaf_size,
        )
        rows, d2, _ = jax_index.knn(local, queries, k,
                                    n_candidate_leaves=n_candidate_leaves)
        top_d2, sel_rows, sel_shard = gather_topk_merge(d2, rows, axis, k)
        return top_d2[None], sel_rows[None], sel_shard[None]

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)),
    )
    d2, rows, shards = fn(ps, ri, sd, sv, lo, hi)
    # all shards hold the same global answer; shard 0's copy suffices
    return d2[0], rows[0], shards[0]
