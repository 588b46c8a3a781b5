"""Compiled device-resident query engine over the flat ``NodeTable``.

The NumPy engine in ``queries.py`` is the paper-faithful authority — it
charges the LRU page I/O the paper costs indexes by — but its batched hot
paths still execute on the host.  This module compiles the same batched
window and k-NN queries for the accelerator: the ``NodeTable`` is exported
once into fixed-shape device arrays (:class:`DeviceTable`) and every query
batch then runs as a couple of jit-compiled dispatches with no per-query
Python on the geometry path.

Execution model
---------------
  * **Level-synchronous frontier traversal.**  The table's rows are
    re-blocked by BFS depth (``NodeTable.device_layout``); descending the
    tree is a static unrolled loop over level blocks in which the whole
    level's MBBs are tested against the whole query batch with one masked
    broadcast comparison, and survival propagates to the next level through
    a fixed-fanout parent-position gather.  There is no dynamic frontier —
    every row is tested, masked by its parent's bit — which keeps all
    shapes static while computing exactly the visited set of the NumPy
    engine (MBB nesting makes the hit set downward-closed).
  * **Window collection is work-proportional.**  The traversal's (Q, L)
    leaf hit mask is flattened into a list of (query, leaf) *pairs* — the
    batch's true candidate set — padded to a power-of-two bucket and
    scanned leaf-block by leaf-block.  Cost scales with the candidate
    leaves the batch actually touches (the property the NumPy engine has),
    not with Q x max-per-query, and the compiled variants are bounded by
    the pair-bucket sizes.  Qualifying ids are packed host-side with two
    vectorized NumPy selections (the only remaining host work).
  * **k-NN scans fixed candidate budgets with certificates.**  Each query
    takes its C closest leaves by box mindist (indices-only ``top_k`` —
    XLA CPU's top_k with live values is pathologically slow), scans them,
    and certifies exactness against the mindist of the closest unscanned
    leaf (computed by masking the scanned leaves to +inf and taking a row
    min).  The budget doubles until every certificate holds, so results
    are exact; budgets are powers of two, bounding compiled variants.
  * **Fused leaf kernels.**  The per-candidate containment test
    (``kernels/window_filter.window_mask_gathered``) and candidate
    distance scan (``kernels/knn_topk.gathered_dist2``) run as Pallas
    kernels on TPU (``use_kernel=None`` auto-selects; interpret mode
    exercises the same kernels on CPU CI) with an equivalent jnp path for
    plain XLA backends.

Parity contract
---------------
For float32-representable inputs, window results are exactly the NumPy
engine's id sets: containment is an exact comparison on identical values.
k-NN candidate sets are certified complete by the best-first bound (k-th
distance <= mindist of the closest unscanned leaf), so returned ids are
exact nearest neighbors *under float32 distance arithmetic*: the NumPy
engine ranks by float64, so two neighbors whose true squared distances
differ by less than one f32 ulp can order differently at the k-th
boundary (never observed under the suite's pinned seeds; exact ties are
unspecified in both engines — tie-heavy tests compare distances).
Result *order* within a window result set is unspecified; compare as
sets.  The device path charges no simulated I/O — ``IOStats`` remain the
NumPy engine's job.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from .jax_index import _pow2
from .nodetable import LEAF_TILE, SLOT_TILE, NodeTable, round_up

BIG = float(np.finfo(np.float32).max)

# one dispatch scans at most this many (query, leaf) pairs; bigger
# candidate sets stream in chunks so memory stays bounded and compiled
# variants stay the handful of power-of-two bucket sizes below the cap
PAIR_CHUNK = 16384

# host -> device upload accounting: the adaptive-serving tests prove a graft
# refreshes the device table by uploading only its delta (full_exports stays
# at the boot count; each refresh uploads exactly the new leaf blocks)
@dataclasses.dataclass
class UploadStats:
    """Host -> device upload counters.

    Instance-scoped: each ``DeviceQueryServer`` (and each explicitly
    threaded export) owns its own sink, so two servers in one process
    keep independent delta-only-upload proofs.  ``UPLOAD_STATS`` below is
    the module-level default sink for code that exports tables without a
    server (and for the upload totals of otherwise-unowned exports).
    Supports dict-style reads for the counter names.
    """

    full_exports: int = 0        # DeviceTable.from_table calls
    delta_refreshes: int = 0     # DeviceTable.apply_delta calls
    uploaded_leaf_blocks: int = 0  # leaf blocks shipped host -> device
    uploaded_points: int = 0       # live points inside those blocks

    def __getitem__(self, key: str) -> int:
        if key not in self.__dataclass_fields__:
            raise KeyError(key)
        return getattr(self, key)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def reset(self) -> dict:
        """Zero the counters; returns the pre-reset values."""
        old = self.as_dict()
        for k in self.__dataclass_fields__:
            setattr(self, k, 0)
        return old

    def record_export(self, n_blocks: int, n_points: int) -> None:
        self.full_exports += 1
        self.uploaded_leaf_blocks += int(n_blocks)
        self.uploaded_points += int(n_points)

    def record_delta(self, n_blocks: int, n_points: int) -> None:
        self.delta_refreshes += 1
        self.uploaded_leaf_blocks += int(n_blocks)
        self.uploaded_points += int(n_points)


UPLOAD_STATS = UploadStats()


def reset_upload_stats() -> dict:
    """Zero the module-default upload counters; returns pre-reset values."""
    return UPLOAD_STATS.reset()


def _use_kernel_default() -> bool:
    from ..kernels import ops as kops

    return kops._on_tpu()


def _fused_default() -> bool:
    """Resolve the ``fused`` flag: the ``REPRO_FUSED`` env var (1/0) wins —
    0 pins the first-generation host-packing path for A/B runs — else the
    fused on-device packing engine is the default."""
    env = os.environ.get("REPRO_FUSED")
    if env is not None and env != "":
        return env not in ("0", "false", "False")
    return True


def _levels_to_jax(levels) -> tuple:
    """Host level blocks -> the per-depth device tuples ``DeviceTable``
    carries (shared by the full export and the delta refresh)."""
    return tuple(
        (
            jnp.asarray(lv["lo"]),
            jnp.asarray(lv["hi"]),
            jnp.asarray(lv["parent"]),
            jnp.asarray(lv["slot"]),
        )
        for lv in levels
    )


def _levels_c_to_jax(levels) -> tuple:
    """Compressed (bf16 outward-rounded) bound columns per level block.

    Kept as a parallel tuple rather than widening the level tuples so the
    uncompressed pytree structure — and therefore every existing jit cache
    entry — is unchanged."""
    from .nodetable import compress_boxes_bf16

    out = []
    for lv in levels:
        if "lo_c" in lv:
            lo_c, hi_c = lv["lo_c"], lv["hi_c"]
        else:
            lo_c, hi_c = compress_boxes_bf16(lv["lo"], lv["hi"])
        out.append((jnp.asarray(lo_c), jnp.asarray(hi_c)))
    return tuple(out)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceTable:
    """Fixed-shape device export of a ``NodeTable``.

    ``levels`` is a tuple of per-depth blocks ``(lo, hi, parent, slot)``
    (see ``NodeTable.device_layout`` for the exact semantics).  The whole
    object is a pytree, so it is passed to jitted cores as a runtime
    argument and two tables with identical shapes share compilations.
    ``leaf_ids_host`` keeps the id blocks host-side for the NumPy packing
    stage of window collection.

    A *partial* export (``from_table(..., partial=True)`` over a table with
    unrefined AMBI rows) additionally carries the cold axis: unrefined-row
    MBBs in ``cold_lo``/``cold_hi`` whose hits :func:`frontier_leaf_hits`
    surfaces past the leaf columns, and the ``leaf_rows``/``cold_rows``
    host maps :meth:`apply_delta` uses to refresh the export incrementally
    after the host grafts new subtrees.
    """

    # the two tables hold whole TPU tiles (NodeTable.device_layout): S'
    # slots a block, a multiple of 128, and L' >= L point blocks, of 8
    leaf_pts: jnp.ndarray    # (d, L', S') leaf-blocked points, pad = dtype max
    leaf_ids: jnp.ndarray    # (L, S') int32 dataset rows, pad = -1
    leaf_counts: jnp.ndarray # (L,) int32 live slots per leaf block
    leaf_lo: jnp.ndarray     # (L, d)
    leaf_hi: jnp.ndarray     # (L, d)
    levels: tuple            # per depth: (lo (n,d), hi (n,d), parent, slot)
    cold_lo: jnp.ndarray = None  # (U, d) unrefined-row MBBs (partial export)
    cold_hi: jnp.ndarray = None  # (U, d)
    # compressed-MBB layout (from_table(compressed=True)): outward-rounded
    # bf16 copies of every bound column.  Traversal against them yields a
    # superset of the f32 hit set at half the bound bandwidth; the f32
    # columns above stay authoritative for the certified re-check.
    leaf_lo_c: jnp.ndarray = None  # (L, d) bf16
    leaf_hi_c: jnp.ndarray = None  # (L, d) bf16
    levels_c: tuple = None         # per depth: (lo_c, hi_c) bf16
    n_points: int = None
    fill: int = None         # widest leaf's live slots (see leaf_size)
    leaf_ids_host: np.ndarray = None
    leaf_rows: np.ndarray = None  # (L,) table row behind each leaf slot
    cold_rows: np.ndarray = None  # (U,) table row behind each cold slot
    upload_stats: "UploadStats" = None  # sink for this table's uploads

    def tree_flatten(self):
        # n_points and the host maps are host-only scaffolding: excluded
        # from the pytree (aux is part of the jit cache key, and no jitted
        # core reads any of them), so shard tables with identical shapes
        # but different live fills share compilations; traced
        # reconstructions carry None, which lazy accessors rebuild
        return (
            (self.leaf_pts, self.leaf_ids, self.leaf_counts, self.leaf_lo,
             self.leaf_hi, self.levels, self.cold_lo, self.cold_hi,
             self.leaf_lo_c, self.leaf_hi_c, self.levels_c),
            (),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def compressed(self) -> bool:
        return self.leaf_lo_c is not None

    @property
    def n_leaves(self) -> int:
        return self.leaf_ids.shape[0]

    @property
    def n_cold(self) -> int:
        return 0 if self.cold_lo is None else self.cold_lo.shape[0]

    @property
    def slots(self) -> int:
        """Slots per stored leaf block: ``leaf_size`` in whole lane rows."""
        return self.leaf_ids.shape[1]

    @property
    def leaf_size(self) -> int:
        """The widest leaf's fill (at least 1): the points a leaf block
        holds at most.  Host scaffolding like ``n_points``, recovered from
        the fill counts after a pytree round-trip."""
        if self.fill is None:
            self.fill = max(int(np.asarray(self.leaf_counts).max(initial=0)),
                            1)
        return self.fill

    @property
    def dim(self) -> int:
        return self.leaf_pts.shape[0]

    @property
    def host_ids(self) -> np.ndarray:
        """Host-side leaf id blocks; rebuilt (and cached) if this instance
        came out of a pytree round-trip that dropped the scaffolding."""
        if self.leaf_ids_host is None:
            self.leaf_ids_host = np.asarray(self.leaf_ids)
        return self.leaf_ids_host

    def live_points(self) -> int:
        """Live point count (sum of leaf fills); like :attr:`host_ids`,
        lazily recovered when a pytree round-trip dropped the scaffolding."""
        if self.n_points is None:
            self.n_points = int(np.asarray(self.leaf_counts).sum())
        return self.n_points

    @classmethod
    def from_table(
        cls,
        table: NodeTable,
        points: np.ndarray,
        dtype=np.float32,
        *,
        partial: bool = False,
        compressed: bool = False,
        stats: "UploadStats" = None,
    ) -> "DeviceTable":
        """Export ``table`` over ``points`` (a full upload).

        ``n_points`` is the table's *live* point count (the sum of its leaf
        fills), not ``len(points)`` — a shard table addresses the global
        dataset but owns only its slice, and result lengths truncate to
        what the table can actually return.  For a whole-dataset fully
        refined table the two are equal; a partial export counts only the
        refined points.

        ``compressed=True`` additionally ships the outward-rounded bf16
        bound columns (see ``NodeTable.device_layout``) the fused engine
        traverses against, halving bound-column bandwidth; results stay
        id-identical because every compressed box contains its f32 box and
        the collection stage re-checks against the exact f32 columns.
        """
        lay = table.device_layout(
            np.asarray(points), dtype=dtype, partial=partial,
            compressed=compressed,
        )
        levels = _levels_to_jax(lay["levels"])
        sink = stats if stats is not None else UPLOAD_STATS
        sink.record_export(
            len(lay["leaf_counts"]), int(lay["leaf_counts"].sum())
        )
        return cls(
            leaf_pts=jnp.asarray(lay["leaf_pts"]),
            leaf_ids=jnp.asarray(lay["leaf_ids"]),
            leaf_counts=jnp.asarray(lay["leaf_counts"]),
            leaf_lo=jnp.asarray(lay["leaf_lo"]),
            leaf_hi=jnp.asarray(lay["leaf_hi"]),
            levels=levels,
            cold_lo=jnp.asarray(lay["cold_lo"]),
            cold_hi=jnp.asarray(lay["cold_hi"]),
            leaf_lo_c=(jnp.asarray(lay["leaf_lo_c"]) if compressed else None),
            leaf_hi_c=(jnp.asarray(lay["leaf_hi_c"]) if compressed else None),
            levels_c=(_levels_c_to_jax(lay["levels"]) if compressed else None),
            n_points=int(lay["leaf_counts"].sum()),
            fill=lay["leaf_size"],
            leaf_ids_host=lay["leaf_ids"],
            leaf_rows=lay["leaf_rows"],
            cold_rows=lay["cold_rows"],
            upload_stats=sink,
        )

    @classmethod
    def from_index(cls, index, dtype=np.float32, *, compressed: bool = False,
                   stats: "UploadStats" = None) -> "DeviceTable":
        """From a built ``core.fmbi.Index`` (table + dataset)."""
        return cls.from_table(index.table, index.points, dtype=dtype,
                              compressed=compressed, stats=stats)

    def apply_delta(self, table: NodeTable, points: np.ndarray) -> "DeviceTable":
        """Incremental refresh after host-side grafts: returns a *new*
        ``DeviceTable`` (double-buffered — the caller keeps serving this
        one until it swaps) in which only the freshly grafted leaf blocks
        are uploaded from the host.

        Grafting never mutates an existing refined leaf — it refines an
        unrefined row in place and appends new rows — so every leaf slot
        this export already holds stays valid verbatim: the big point/id
        payload is extended device-side (old blocks are reused, padded to a
        wider slot count on device if a new leaf is fuller than any before)
        and only the new leaves' blocks cross the host/device boundary.
        The O(n_nodes) traversal metadata (level blocks, leaf/cold MBBs,
        fill counts) is recomputed host-side and re-uploaded — it is tiny
        next to the point payload and renumbering cold slots keeps the
        frontier encoding dense.
        """
        if self.leaf_rows is None:
            raise ValueError(
                "delta refresh needs the host scaffolding (leaf_rows); "
                "this table came out of a pytree round-trip — re-export "
                "with DeviceTable.from_table"
            )
        dtype = np.dtype(self.leaf_pts.dtype)
        big = np.finfo(dtype).max
        old_rows = self.leaf_rows
        known = np.zeros(table.n_nodes, dtype=bool)
        known[old_rows] = True
        rows_now = table.leaf_rows()
        new_rows = rows_now[~known[rows_now]]
        leaf_rows = np.concatenate([old_rows, new_rows])
        counts_new = table.leaf_count[new_rows]
        fill = max(self.leaf_size,
                   int(counts_new.max()) if len(counts_new) else 1)
        s_old = self.slots
        S = max(s_old, round_up(fill, SLOT_TILE))
        l_old = self.n_leaves
        lp, li = self.leaf_pts, self.leaf_ids
        if S > s_old:  # widen existing blocks device-side (no host upload)
            lp = jnp.concatenate(
                [lp, jnp.full((*lp.shape[:2], S - s_old), big,
                              dtype=lp.dtype)],
                axis=2,
            )
            li = jnp.concatenate(
                [li, jnp.full((l_old, S - s_old), -1, dtype=li.dtype)], axis=1
            )
        if len(new_rows):
            # the new blocks replace the point table's padding blocks and
            # bring their own, out to whole sublane tiles again
            nb_pts, nb_ids = table.pack_leaf_blocks(
                new_rows, np.asarray(points), S, dtype,
                n_blocks=round_up(len(leaf_rows), LEAF_TILE) - l_old,
            )
            lp = jnp.concatenate([lp[:, :l_old], jnp.asarray(nb_pts)], axis=1)
            li = jnp.concatenate([li, jnp.asarray(nb_ids)], axis=0)
        cold = np.flatnonzero(table.unrefined)
        level_blocks = table.level_blocks(
            table.slot_map(leaf_rows, cold), dtype
        )
        levels = _levels_to_jax(level_blocks)
        counts = table.leaf_count[leaf_rows].astype(np.int32)
        # compressed exports stay compressed across the delta: the bound
        # columns are O(n_nodes) metadata recomputed host-side anyway, so
        # re-rounding them costs nothing next to the point payload
        new_lo = table.mbb_lo[leaf_rows].astype(dtype)
        new_hi = table.mbb_hi[leaf_rows].astype(dtype)
        if self.compressed:
            from .nodetable import compress_boxes_bf16

            lo_c, hi_c = compress_boxes_bf16(new_lo, new_hi)
            leaf_lo_c = jnp.asarray(lo_c)
            leaf_hi_c = jnp.asarray(hi_c)
            levels_c = _levels_c_to_jax(level_blocks)
        else:
            leaf_lo_c = leaf_hi_c = levels_c = None
        ids_host = self.host_ids
        if len(new_rows):  # S can only widen when there are new leaves
            ids_host = np.concatenate(
                [
                    np.pad(ids_host, ((0, 0), (0, S - s_old)),
                           constant_values=-1),
                    nb_ids,
                ]
                if S > s_old
                else [ids_host, nb_ids]
            )
        sink = self.upload_stats if self.upload_stats is not None else UPLOAD_STATS
        sink.record_delta(len(new_rows), int(counts_new.sum()))
        return DeviceTable(
            leaf_pts=lp,
            leaf_ids=li,
            leaf_counts=jnp.asarray(counts),
            leaf_lo=jnp.asarray(new_lo),
            leaf_hi=jnp.asarray(new_hi),
            levels=levels,
            cold_lo=jnp.asarray(table.mbb_lo[cold].astype(dtype)),
            cold_hi=jnp.asarray(table.mbb_hi[cold].astype(dtype)),
            leaf_lo_c=leaf_lo_c,
            leaf_hi_c=leaf_hi_c,
            levels_c=levels_c,
            n_points=int(counts.sum()),
            fill=fill,
            leaf_ids_host=ids_host,
            leaf_rows=leaf_rows,
            cold_rows=cold,
            upload_stats=sink,
        )

    def remap_rows(self, remap: np.ndarray) -> None:
        """Rebase the host scaffolding after ``NodeTable.compact`` (row
        renumbering changes no leaf content, so the device arrays stay)."""
        if self.leaf_rows is not None:
            self.leaf_rows = remap[self.leaf_rows]
        if self.cold_rows is not None:
            self.cold_rows = remap[self.cold_rows]


# --------------------------------------------------------------------------
# level-synchronous frontier traversal
# --------------------------------------------------------------------------
@jax.jit
def frontier_leaf_hits(
    dev: DeviceTable, los: jnp.ndarray, his: jnp.ndarray
) -> jnp.ndarray:
    """(Q, L + U) mask of leaves — and, for a partial export, cold
    (unrefined) rows — whose MBB intersects each query window.

    One masked broadcast box test per level block; survival propagates
    down through the parent-position gather.  Columns ``[0, L)`` are leaf
    slots, columns ``[L, L + U)`` are the cold slots of a partial AMBI
    export (the serving layer's "this query needs the host" mask; U = 0
    for a fully refined table, so the shape reduces to the classic (Q, L)).
    Branch rows scatter into the sentinel row ``L + U`` of the
    accumulator, which is dropped.
    """
    q = los.shape[0]
    n_slots = dev.n_leaves + dev.n_cold
    d = dev.dim
    leaf_hit = jnp.zeros((n_slots + 1, q), dtype=bool)
    prev = None
    for lo_l, hi_l, parent, slot in dev.levels:
        # static unroll over dimensions: (n_level, Q) planes, no
        # (n_level, Q, d) temporaries
        hit = None
        for j in range(d):
            h = (lo_l[:, j][:, None] <= his[:, j][None, :]) & (
                hi_l[:, j][:, None] >= los[:, j][None, :]
            )
            hit = h if hit is None else hit & h
        if prev is not None:
            hit = hit & prev[parent]
        leaf_hit = leaf_hit.at[slot].max(hit)
        prev = hit
    return leaf_hit[:n_slots].T


# --------------------------------------------------------------------------
# fused engine: tiled frontier + on-device pair packing (second generation)
# --------------------------------------------------------------------------
def _level_bounds(dev: DeviceTable, i: int):
    """Bound columns the fused frontier tests level ``i`` against: the
    outward-rounded bf16 copies when the export is compressed (half the
    bandwidth, hit set a superset of f32 — never a false negative), else
    the exact f32 columns."""
    if dev.levels_c is not None:
        return dev.levels_c[i]
    lo, hi, _, _ = dev.levels[i]
    return lo, hi


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _frontier_count(
    dev: DeviceTable, los: jnp.ndarray, his: jnp.ndarray, use_kernel: bool
):
    """Fused frontier pass: the (Q, L + U) hit mask *plus* the number of
    (query, leaf) candidate pairs, in one dispatch.

    The mask stays on device (the pair-packing stage consumes it there);
    only the scalar pair count crosses to the host, where it picks the
    power-of-two pair bucket.  With ``use_kernel`` each level block's box
    test runs as the VMEM-tiled Pallas kernel (``box_hits_tiled``); the
    jnp path unrolls per-dimension (n_level, Q) planes exactly like
    :func:`frontier_leaf_hits`.  A compressed export is traversed against
    its bf16 bounds — the resulting superset costs only extra candidate
    pairs, which the exact-f32 collection stage rejects."""
    with jax.named_scope("frontier"):
        q = los.shape[0]
        n_slots = dev.n_leaves + dev.n_cold
        d = dev.dim
        leaf_hit = jnp.zeros((n_slots + 1, q), dtype=bool)
        prev = None
        for i, (_, _, parent, slot) in enumerate(dev.levels):
            lo_l, hi_l = _level_bounds(dev, i)
            if use_kernel:
                from ..kernels import ops as kops

                hit = kops.box_hits_tiled(lo_l, hi_l, los, his) > 0
            else:
                hit = None
                for j in range(d):
                    lo_j = lo_l[:, j].astype(jnp.float32)[:, None]
                    hi_j = hi_l[:, j].astype(jnp.float32)[:, None]
                    h = (lo_j <= his[:, j][None, :]) & (
                        hi_j >= los[:, j][None, :]
                    )
                    hit = h if hit is None else hit & h
            if prev is not None:
                hit = hit & prev[parent]
            leaf_hit = leaf_hit.at[slot].max(hit)
            prev = hit
        hits = leaf_hit[:n_slots].T
        n_pairs = jnp.sum(hits[:, : dev.n_leaves].astype(jnp.int32))
    return hits, n_pairs


def _compact_idx(mask_flat, first: int, count: int, offset):
    """Stream compaction via cumsum + binary search: the positions of set
    bits ``offset + first .. offset + first + count`` of a flat 0/1 mask
    (1-based ranks), plus the mask's total.

    XLA lowers ``jnp.nonzero``/scatter compaction poorly on CPU (a 131k
    mask costs ~6 ms); a monotone cumsum probed by ``searchsorted`` is
    ~10x cheaper there and vectorizes fine on TPU.  ``offset`` is a traced
    scalar so chunked callers share one compiled variant per chunk width.
    Ranks past the total return clamped positions — mask with the returned
    total."""
    s = jnp.cumsum(mask_flat.astype(jnp.int32))
    ranks = jnp.arange(first, first + count, dtype=jnp.int32) + offset
    pos = jnp.searchsorted(s, ranks)
    pos = jnp.minimum(pos, mask_flat.shape[0] - 1).astype(jnp.int32)
    return pos, ranks, s[-1]


@functools.partial(jax.jit, static_argnames=("pc", "use_kernel"))
def _fused_pack_scan(
    dev: DeviceTable,
    los: jnp.ndarray,
    his: jnp.ndarray,
    hits: jnp.ndarray,
    offset,
    pc: int,
    use_kernel: bool,
):
    """One dispatch from hit mask to qualifying ids: pack the chunk's
    (query, leaf) pairs on device, scan them, and count per query.

    Replaces the first-generation host round-trip (mask transfer,
    ``np.nonzero``, bucket fill, re-upload) with on-device compaction —
    the mask never leaves the device.  Row-major flattening keeps pairs
    query-grouped, so chunk outputs concatenate into query-grouped ids.
    The box test *and* containment run against the exact f32 columns —
    this is the certified re-check that keeps a compressed traversal
    id-identical.  Returns the (pc, S) ids-or-minus-one matrix, per-query
    qualifying counts, and the chunk's id total."""
    with jax.named_scope("pair_compaction"):
        flat = hits[:, : dev.n_leaves].reshape(-1)
        pos, ranks, n_pairs = _compact_idx(flat, 1, pc, offset)
        pair_valid = (ranks <= n_pairs).astype(jnp.int32)
        q_idx = pos // dev.n_leaves
        leaf_idx = pos % dev.n_leaves
    with jax.named_scope("scan"):
        if use_kernel:
            from ..kernels import ops as kops

            ids_or, pair_counts = kops.pair_window_ids(
                los, his, dev.leaf_lo, dev.leaf_hi, dev.leaf_pts,
                dev.leaf_ids, dev.leaf_counts, q_idx, leaf_idx, pair_valid,
            )
        else:
            from ..kernels import ref as kref

            ids_or, pair_counts = kref.pair_window_ids_ref(
                los, his, dev.leaf_lo, dev.leaf_hi, dev.leaf_pts,
                dev.leaf_ids, dev.leaf_counts, q_idx, leaf_idx, pair_valid,
            )
    with jax.named_scope("per_query_sum"):
        per_query = jax.ops.segment_sum(
            pair_counts, q_idx, num_segments=los.shape[0]
        )
        return ids_or, per_query, jnp.sum(pair_counts)


@functools.partial(jax.jit, static_argnames=("r",))
def _fused_id_pack(ids_or: jnp.ndarray, r: int):
    """On-device qualifying-id compaction: the non-negative entries of the
    (P, S) id matrix packed into an ``r``-slot bucket, in pair order.

    Used when compiled kernels are available (TPU), where shipping the
    packed ids beats shipping the (P, S) matrix; the CPU path extracts on
    the host instead (transfer is cheap there, device compaction is not)."""
    with jax.named_scope("id_pack"):
        flat = ids_or.reshape(-1)
        pos, ranks, total = _compact_idx(flat >= 0, 1, r, jnp.int32(0))
        return jnp.where(ranks <= total, flat[pos], -1)


def _window_batch_fused(
    dev: DeviceTable,
    los: np.ndarray,
    his: np.ndarray,
    use_kernel: bool,
    return_cold: bool,
    device_id_pack: bool | None = None,
):
    """Fused window batch: device-resident from frontier to scanned ids.

    Two dispatches in the common (single-chunk) case — frontier + pair
    count, then pack + scan + count — with one scalar sync between them to
    pick the pair bucket.  ``device_id_pack`` (default: only where
    compiled kernels run) additionally compacts the qualifying ids on
    device so the transfer is work-proportional; on CPU the (P, S) matrix
    transfer + NumPy extraction is faster than any XLA compaction.

    Traced as one ``query.window`` span with the batch's real queries
    ``q``, intersecting pairs ``pairs`` against the pair bucket slots
    ``pair_slots`` over ``chunks``, and qualifying ``ids`` against the id
    pack's slots ``id_slots``; each blocking device-to-host read is a
    ``query.sync`` span (``what`` names it), the final split ``query.split``."""
    if device_id_pack is None:
        from ..kernels import ops as kops

        device_id_pack = kops.compiled_supported()
    with tracing.span("query.window") as sp:
        los = np.atleast_2d(np.asarray(los, dtype=np.float32))
        his = np.atleast_2d(np.asarray(his, dtype=np.float32))
        (los, his), q0 = _pad_batch([los, his], [BIG, -BIG])
        losj, hisj = jnp.asarray(los), jnp.asarray(his)
        hits, n_pairs = _frontier_count(dev, losj, hisj, use_kernel)
        with tracing.span("query.sync", what="pairs"):
            p0 = int(n_pairs)
        cold = None
        if return_cold:
            with tracing.span("query.sync", what="cold"):
                cold = np.asarray(hits[:q0, dev.n_leaves :])
        parts = []
        per_query = np.zeros(los.shape[0], dtype=np.int64)
        pair_slots = id_slots = chunks = 0
        for a in range(0, p0, PAIR_CHUNK):
            pc = _pow2(min(p0 - a, PAIR_CHUNK))
            pair_slots += pc
            chunks += 1
            ids_or, pq, total = _fused_pack_scan(
                dev, losj, hisj, hits, np.int32(a), pc, use_kernel
            )
            with tracing.span("query.sync", what="per_query"):
                per_query += np.asarray(pq, dtype=np.int64)
            if device_id_pack:
                with tracing.span("query.sync", what="ids_total"):
                    t = int(total)
                if t:
                    r = _pow2(t)
                    id_slots += r
                    packed = _fused_id_pack(ids_or, r)
                    with tracing.span("query.sync", what="ids"):
                        packed = np.asarray(packed)[:t]
                    parts.append(packed.astype(np.int64))
            else:
                with tracing.span("query.sync", what="id_matrix"):
                    arr = np.asarray(ids_or)
                parts.append(arr[arr >= 0].astype(np.int64))
        with tracing.span("query.split"):
            all_ids = (
                np.concatenate(parts) if parts
                else np.zeros(0, dtype=np.int64)
            )
            res = np.split(all_ids, np.cumsum(per_query[:q0])[:-1])
        sp.set(q=q0, pairs=p0, pair_slots=pair_slots, chunks=chunks,
               ids=int(per_query.sum()), id_slots=id_slots)
    return (res, cold) if return_cold else res


# --------------------------------------------------------------------------
# window: pair-list candidate collection
# --------------------------------------------------------------------------
def _gather_leaves(leaf_pts: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Point-major ``idx.shape + (S, d)`` blocks of the leaves ``idx``
    names: the gather reads the dimension-major table, and the axis moves
    on the small gathered block, never on the table."""
    return jnp.moveaxis(leaf_pts[:, idx], 0, -1)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _pair_collect(
    dev: DeviceTable,
    los: jnp.ndarray,
    his: jnp.ndarray,
    q_idx: jnp.ndarray,      # (P,) query of each candidate pair
    leaf_idx: jnp.ndarray,   # (P,) leaf slot of each candidate pair
    pair_valid: jnp.ndarray, # (P,) padding mask
    use_kernel: bool,
):
    """Scan one bucket of (query, leaf) candidate pairs: gather each
    pair's leaf block and test containment against its query's box."""
    s = dev.slots
    lo_p = los[q_idx]                         # (P, d)
    hi_p = his[q_idx]
    pts = _gather_leaves(dev.leaf_pts, leaf_idx)  # (P, S, d)
    # slot validity from the per-leaf fill counts: no (P, S) id gather
    valid = (
        jnp.arange(s, dtype=jnp.int32)[None, :]
        < dev.leaf_counts[leaf_idx][:, None]
    ) & pair_valid[:, None]
    if use_kernel:
        from ..kernels import ops as kops

        inside = (
            kops.window_mask_gathered(lo_p, hi_p, pts,
                                      valid.astype(jnp.int32)) > 0
        )
    else:
        inside = (
            jnp.all((pts >= lo_p[:, None, :]) & (pts <= hi_p[:, None, :]),
                    axis=2)
            & valid
        )
    return inside


def _pad_batch(arrs, fills):
    """Pad the query axis to a power-of-two bucket (bounds compiled
    variants across ragged batch sizes)."""
    q0 = arrs[0].shape[0]
    qp = _pow2(max(q0, 1))
    if qp == q0:
        return arrs, q0
    out = []
    for a, fill in zip(arrs, fills):
        pad = np.full((qp - q0,) + a.shape[1:], fill, dtype=a.dtype)
        out.append(np.concatenate([a, pad]))
    return out, q0


def window_query_batch_jax(
    dev: DeviceTable,
    los: np.ndarray,
    his: np.ndarray,
    *,
    use_kernel: bool | None = None,
    fused: bool | None = None,
    return_cold: bool = False,
) -> list[np.ndarray]:
    """Compiled batched window query: per-query arrays of dataset row ids.

    Ids are identical (as sets) to ``queries.window_query_batch`` for
    float32-representable inputs, and completeness is structural — every
    intersecting leaf becomes a candidate pair, so there is no budget to
    escalate.  Work scales with the candidate pairs the batch actually
    touches; the pair list streams in power-of-two buckets capped at
    ``PAIR_CHUNK`` so compiled variants stay bounded.

    ``fused`` (default on; ``REPRO_FUSED=0`` pins the first-generation
    path) keeps pair packing and id compaction on device — the frontier
    mask and candidate matrices never cross the host boundary, only bucket
    sizes (scalars) and the packed result ids do — and is the only path
    that exploits a compressed (bf16-MBB) export.

    On a *partial* export the returned ids cover only the refined leaves.
    ``return_cold=True`` additionally returns the (Q, U) cold-hit mask the
    frontier surfaced — per query, which unrefined rows it reached.  A
    query whose cold row is all-False is complete as returned; one that
    touches unindexed space must be answered (and its subspaces refined)
    host-side.  U = 0 for a refined table, so the mask is vacuously empty.
    """
    if use_kernel is None:
        use_kernel = _use_kernel_default()
    if fused is None:
        fused = _fused_default()
    if fused:
        return _window_batch_fused(dev, los, his, use_kernel, return_cold)
    los = np.atleast_2d(np.asarray(los, dtype=np.float32))
    his = np.atleast_2d(np.asarray(his, dtype=np.float32))
    # padding boxes are inverted: they can never intersect a leaf
    (los, his), q0 = _pad_batch([los, his], [BIG, -BIG])
    losj, hisj = jnp.asarray(los), jnp.asarray(his)
    hits = np.asarray(frontier_leaf_hits(dev, losj, hisj))[:q0]
    inter, cold = hits[:, : dev.n_leaves], hits[:, dev.n_leaves :]
    q_idx, leaf_idx = np.nonzero(inter)  # row-major: query-grouped
    p0 = len(q_idx)
    if p0 == 0:
        empty = [np.zeros(0, dtype=np.int64) for _ in range(q0)]
        return (empty, cold) if return_cold else empty
    parts, pair_counts = [], []
    for a in range(0, p0, PAIR_CHUNK):
        b = min(a + PAIR_CHUNK, p0)
        p = _pow2(b - a)
        qi = np.zeros(p, dtype=np.int32)
        li = np.zeros(p, dtype=np.int32)
        qi[: b - a] = q_idx[a:b]
        li[: b - a] = leaf_idx[a:b]
        pv = np.arange(p) < (b - a)
        inside = np.asarray(
            _pair_collect(
                dev, losj, hisj, jnp.asarray(qi), jnp.asarray(li),
                jnp.asarray(pv), use_kernel,
            )
        )
        ids = dev.host_ids[li]                # (P, S) host gather
        parts.append(ids[inside].astype(np.int64))
        pair_counts.append(inside.sum(axis=1)[: b - a])
    all_ids = np.concatenate(parts)
    per_pair = np.concatenate(pair_counts)
    per_query = np.bincount(q_idx, weights=per_pair, minlength=q0)
    res = np.split(all_ids, np.cumsum(per_query.astype(np.int64))[:-1])
    return (res, cold) if return_cold else res


# --------------------------------------------------------------------------
# k-NN: candidate-leaf scan + top-k merge
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("k", "n_candidate_leaves", "use_kernel")
)
def _knn_core(
    dev: DeviceTable,
    qs: jnp.ndarray,
    k: int,
    n_candidate_leaves: int,
    use_kernel: bool,
):
    """Scan each query's C closest leaves (by box mindist) and merge top-k.

    Returns (ids, d2, exact): ``exact`` certifies the best-first bound —
    the k-th distance does not exceed the mindist of the closest leaf left
    unscanned, so no unscanned leaf can hold a closer neighbor."""
    q = qs.shape[0]
    d, n_l, s = dev.dim, dev.n_leaves, dev.slots
    c = min(n_candidate_leaves, n_l)
    # box mindists accumulated per dimension: (Q, L) planes only
    mind = jnp.zeros((q, n_l), dtype=dev.leaf_lo.dtype)
    for j in range(d):
        g = jnp.maximum(
            dev.leaf_lo[:, j][None, :] - qs[:, j][:, None], 0.0
        ) + jnp.maximum(qs[:, j][:, None] - dev.leaf_hi[:, j][None, :], 0.0)
        mind = mind + g * g
    # indices-only top_k: keeping the values output live trips XLA CPU's
    # slow generic sort path (~10x); the unscanned bound is recovered below
    _, cand = jax.lax.top_k(-mind, c)
    flat_pts = _gather_leaves(dev.leaf_pts, cand).reshape(q, c * s, d)
    if use_kernel:
        from ..kernels import ops as kops

        # slot validity from the per-leaf fill counts: no (Q, C*S) id
        # gather — result ids are recovered after selection below
        flat_valid = (
            jnp.arange(s, dtype=jnp.int32)[None, None, :]
            < dev.leaf_counts[cand][:, :, None]
        ).reshape(q, c * s)
        d2 = kops.gathered_dist2(qs, flat_pts, flat_valid.astype(jnp.int32))
    else:
        # no mask needed: padding slots carry dtype-max coordinates, so
        # their squared distances overflow to +inf and never select
        d2 = jnp.sum((flat_pts - qs[:, None, :]) ** 2, axis=2)
    kk = min(k, c * s)
    # two-level merge: top-k within each leaf block, then across the C
    # block winners — same result set, much smaller sort fronts
    kl = min(kk, s)
    negl, til = jax.lax.top_k(-d2.reshape(q, c, s), kl)   # (Q, C, kl)
    negd, tim = jax.lax.top_k(negl.reshape(q, c * kl), kk)
    ti = (
        jnp.take_along_axis(til.reshape(q, c * kl), tim, axis=1)
        + (tim // kl) * s
    )
    leaf_sel = jnp.take_along_axis(cand, ti // s, axis=1)
    ids = dev.leaf_ids[leaf_sel, ti % s]
    d2k = -negd
    if c >= n_l:
        exact = jnp.ones(q, dtype=bool)
    elif kk < k:
        # fewer candidate slots than k: only a full scan certifies
        exact = jnp.zeros(q, dtype=bool)
    else:
        masked = mind.at[jnp.arange(q)[:, None], cand].set(jnp.inf)
        unscanned = jnp.min(masked, axis=1)
        # a kth drawn from a padding slot is BIG/inf: certificate fails
        exact = d2k[:, -1] <= unscanned
    return ids, d2k, exact


# --------------------------------------------------------------------------
# fused k-NN: compressed-bound candidate selection + on-device escalation
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("k", "n_candidate_leaves", "use_kernel")
)
def _knn_core_fused(
    dev: DeviceTable,
    qs: jnp.ndarray,
    b0,
    k: int,
    n_candidate_leaves: int,
    use_kernel: bool,
):
    """Fused-generation k-NN round.

    Differences to :func:`_knn_core`: candidate leaves are ranked by the
    *compressed* (bf16) box mindists when the export carries them — an
    outward-rounded box only shrinks the mindist, so the bound is a
    superset-safe underestimate and the exactness certificate derived
    from it stays conservative (kth <= compressed mindist <= f32 mindist
    — certifying against the underestimate is strictly harder, never
    wrong); the candidate scan streams through the fused pair kernel
    (``pair_dist2``) instead of an XLA-materialized (Q, C*S, d) gather;
    and outputs are padded to the c-independent width ``min(k, L*S)`` so
    escalation rounds scatter into one fixed result buffer."""
    q = qs.shape[0]
    d, n_l, s = dev.dim, dev.n_leaves, dev.slots
    c = min(n_candidate_leaves, n_l)
    if dev.leaf_lo_c is not None:
        blo, bhi = dev.leaf_lo_c, dev.leaf_hi_c
    else:
        blo, bhi = dev.leaf_lo, dev.leaf_hi
    mind = jnp.zeros((q, n_l), dtype=jnp.float32)
    for j in range(d):
        bl = blo[:, j].astype(jnp.float32)
        bh = bhi[:, j].astype(jnp.float32)
        g = jnp.maximum(bl[None, :] - qs[:, j][:, None], 0.0) + jnp.maximum(
            qs[:, j][:, None] - bh[None, :], 0.0
        )
        mind = mind + g * g
    _, cand = jax.lax.top_k(-mind, c)
    if use_kernel:
        from ..kernels import ops as kops

        q_rep = jnp.repeat(
            jnp.arange(q, dtype=jnp.int32)[:, None], c, axis=1
        ).reshape(-1)
        d2 = kops.pair_dist2(
            qs, dev.leaf_pts, dev.leaf_counts, q_rep, cand.reshape(-1)
        ).reshape(q, c, s)
    else:
        flat_pts = _gather_leaves(dev.leaf_pts, cand).reshape(q, c * s, d)
        d2 = jnp.sum((flat_pts - qs[:, None, :]) ** 2, axis=2).reshape(
            q, c, s
        )
    kk = min(k, c * s)
    kl = min(kk, s)
    negl, til = jax.lax.top_k(-d2, kl)                    # (Q, C, kl)
    negd, tim = jax.lax.top_k(negl.reshape(q, c * kl), kk)
    ti = (
        jnp.take_along_axis(til.reshape(q, c * kl), tim, axis=1)
        + (tim // kl) * s
    )
    leaf_sel = jnp.take_along_axis(cand, ti // s, axis=1)
    ids = dev.leaf_ids[leaf_sel, ti % s]
    d2k = -negd
    if c >= n_l:
        exact = jnp.ones(q, dtype=bool)
    elif kk < k:
        exact = jnp.zeros(q, dtype=bool)
    else:
        masked = mind.at[jnp.arange(q)[:, None], cand].set(jnp.inf)
        unscanned = jnp.min(masked, axis=1)
        exact = d2k[:, -1] <= unscanned
    kf = min(k, n_l * s)
    if kf > kk:  # c-independent output width for the escalation buffers
        ids = jnp.concatenate(
            [ids, jnp.full((q, kf - kk), -1, dtype=ids.dtype)], axis=1
        )
        d2k = jnp.concatenate(
            [d2k, jnp.full((q, kf - kk), BIG, dtype=d2k.dtype)], axis=1
        )
    # failed-certificate count over the real (non-padding) rows, computed
    # in the same dispatch: the only value the host syncs per round
    nfail = jnp.sum(
        (~exact) & (jnp.arange(q, dtype=jnp.int32) < b0)
    )
    return ids, d2k, exact, nfail


@functools.partial(jax.jit, static_argnames=("p",))
def _knn_pending(qs: jnp.ndarray, exact: jnp.ndarray, b0, p: int):
    """On-device escalation selection: pack the failed queries' indices
    into a ``p``-slot bucket and gather their coordinates — the host only
    learns *how many* certificates failed, never re-ships query rows.

    ``b0`` masks the batch's pow2 padding rows (their certificates are
    meaningless and must not consume bucket slots)."""
    fail = (~exact) & (jnp.arange(exact.shape[0]) < b0)
    (idx,) = jnp.nonzero(fail, size=p, fill_value=0)
    idx = idx.astype(jnp.int32)
    valid = jnp.arange(p, dtype=jnp.int32) < jnp.sum(fail.astype(jnp.int32))
    return idx, valid, qs[idx]


@jax.jit
def _knn_merge_round(ids_buf, d2_buf, exact_buf, b0, idx, valid, ids_n,
                     d2_n, exact_n):
    """Scatter an escalation round's results over the fixed buffers.

    Padding slots (``valid`` False) are routed to an out-of-range index
    and dropped — ``fill_value=0`` slots must not race a genuine update
    of query 0 (duplicate-index scatter order is undefined).  Returns the
    merged buffers plus the remaining failed-certificate count, so each
    escalation round costs the host exactly one scalar sync."""
    n = ids_buf.shape[0]
    idx_w = jnp.where(valid, idx, n)
    ids_buf = ids_buf.at[idx_w].set(ids_n, mode="drop")
    d2_buf = d2_buf.at[idx_w].set(d2_n, mode="drop")
    exact_buf = exact_buf.at[idx_w].set(exact_n, mode="drop")
    nfail = jnp.sum(
        (~exact_buf) & (jnp.arange(n, dtype=jnp.int32) < b0)
    )
    return ids_buf, d2_buf, exact_buf, nfail


def _knn_batch_fused(
    dev: DeviceTable,
    qs: np.ndarray,
    k: int,
    use_kernel: bool,
    n_candidate_leaves: int | None,
    return_dists: bool,
    max_rounds: int | None = None,
    return_exact: bool = False,
):
    """Fused k-NN batch: budget escalation without host selection.

    Each round reruns only the queries whose certificate failed — packed,
    gathered, and scattered back on device; the host syncs one scalar per
    round (the failure count, which sizes the next power-of-two bucket)
    and transfers results once, after every certificate holds.

    ``max_rounds`` caps the escalation rounds beyond the first dispatch
    (the serving brownout tier); capped queries return their best-effort
    answer with a ``False`` entry in the ``return_exact`` mask."""
    q0 = qs.shape[0]
    s = dev.leaf_size
    cap = _pow2(dev.n_leaves)
    if n_candidate_leaves is None:
        c = min(_pow2(max(8, -(-2 * k) // s)), cap)
    else:
        c = min(_pow2(max(n_candidate_leaves, 1)), cap)
    (batch,), b0 = _pad_batch([qs], [0.0])
    qsj = jnp.asarray(batch)
    b0j = np.int32(b0)
    ids_buf, d2_buf, exact_buf, nfail = _knn_core_fused(
        dev, qsj, b0j, k, c, use_kernel
    )
    full_scan = c >= dev.n_leaves
    n_fail = int(nfail) if not full_scan else 0
    rounds = 0
    while n_fail and (max_rounds is None or rounds < max_rounds):
        c = min(c * 2, cap)
        idx, valid, qsel = _knn_pending(qsj, exact_buf, b0j, _pow2(n_fail))
        ids_n, d2_n, exact_n, _ = _knn_core_fused(
            dev, qsel, np.int32(0), k, c, use_kernel
        )
        ids_buf, d2_buf, exact_buf, nfail = _knn_merge_round(
            ids_buf, d2_buf, exact_buf, b0j, idx, valid, ids_n, d2_n,
            exact_n
        )
        full_scan = c >= dev.n_leaves
        n_fail = int(nfail) if not full_scan else 0
        rounds += 1
    m = min(k, dev.live_points())
    ids, d2k = jax.device_get((ids_buf[:b0, :m], d2_buf[:b0, :m]))
    results = [ids[j].astype(np.int64) for j in range(q0)]
    out = (results,)
    if return_dists:
        out = out + ([d2k[j] for j in range(q0)],)
    if return_exact:
        if full_scan:  # whole leaf table scanned: vacuously exact
            exact = np.ones(q0, dtype=bool)
        else:
            exact = np.asarray(jax.device_get(exact_buf[:b0]))[:q0].copy()
        out = out + (exact,)
    return out if len(out) > 1 else out[0]


def knn_query_batch_jax(
    dev: DeviceTable,
    qs: np.ndarray,
    k: int,
    *,
    use_kernel: bool | None = None,
    fused: bool | None = None,
    n_candidate_leaves: int | None = None,
    return_dists: bool = False,
    max_rounds: int | None = None,
    return_exact: bool = False,
) -> list[np.ndarray]:
    """Compiled batched k-NN: per-query ascending-distance row-id arrays.

    The candidate budget starts at a small power of two and doubles until
    every query's exactness certificate holds (or the whole leaf table is
    scanned), so results match ``queries.knn_query_batch`` — returned ids
    are exact k nearest (length ``min(k, n)``); among exactly tied
    distances the chosen ids may differ.  Escalation reruns only the
    queries whose certificate failed (repacked into a smaller power-of-two
    bucket), so one hard query does not double the whole batch's work.

    With ``return_dists`` the per-query float32 squared distances come
    back too, as ``(ids_list, d2_list)`` — the distributed two-round
    merge consumes them (the same f32 values every shard computes for the
    same (point, query) pair, so a cross-shard merge reproduces the
    single-table ranking).

    On a *partial* export the results are exact over the refined subset
    only (an all-cold export returns empty results): whether the cold
    subspaces could hold closer neighbors is the serving layer's check
    (mindist of each cold box against the k-th returned distance).

    ``max_rounds`` caps the escalation rounds beyond the first dispatch
    — the serving brownout tier's budget cap.  A capped query returns
    its best-effort answer (the exact k-NN over the candidate leaves
    scanned so far, a superset-ranked approximation); ``return_exact``
    appends a per-query bool mask naming which answers the certificate
    actually covers, so callers can label capped answers honestly
    instead of silently serving approximations."""
    if use_kernel is None:
        use_kernel = _use_kernel_default()
    if fused is None:
        fused = _fused_default()
    if max_rounds is not None and max_rounds < 0:
        raise ValueError(f"max_rounds must be >= 0, got {max_rounds}")
    qs = np.atleast_2d(np.asarray(qs, dtype=np.float32))
    q0 = qs.shape[0]
    if dev.n_leaves == 0:  # partial export before the first graft: the
        # device holds nothing scannable — every query is the host's
        out = ([np.zeros(0, dtype=np.int64) for _ in range(q0)],)
        if return_dists:
            out = out + ([np.zeros(0, dtype=np.float32) for _ in range(q0)],)
        if return_exact:
            out = out + (np.ones(q0, dtype=bool),)
        return out if len(out) > 1 else out[0]
    if fused:
        return _knn_batch_fused(
            dev, qs, k, use_kernel, n_candidate_leaves, return_dists,
            max_rounds, return_exact,
        )
    s = dev.leaf_size
    cap = _pow2(dev.n_leaves)
    if n_candidate_leaves is None:
        c = min(_pow2(max(8, -(-2 * k) // s)), cap)
    else:
        c = min(_pow2(max(n_candidate_leaves, 1)), cap)
    results: list = [None] * q0
    dists: list = [None] * q0
    exact_mask = np.ones(q0, dtype=bool)
    pending = np.arange(q0)
    rounds = 0
    while len(pending):
        (batch,), b0 = _pad_batch([qs[pending]], [0.0])
        ids, d2k, exact = jax.device_get(
            _knn_core(dev, jnp.asarray(batch), k, c, use_kernel)
        )
        done = exact[:b0] if c < dev.n_leaves else np.ones(b0, dtype=bool)
        flush = done
        if max_rounds is not None and rounds >= max_rounds:
            # budget cap (brownout): emit best-effort answers for the
            # still-failing queries and mark them inexact
            flush = np.ones(b0, dtype=bool)
        # padding fill (BIG/inf distances) sorts last, so the result is
        # always the first min(k, n) entries — no distance threshold needed
        # (live_points recovers the count after a pytree round-trip)
        m = min(k, dev.live_points())
        for j in np.flatnonzero(flush):
            results[pending[j]] = ids[j, :m].astype(np.int64)
            dists[pending[j]] = d2k[j, :m]
            exact_mask[pending[j]] = bool(done[j])
        pending = pending[~flush]
        c = min(c * 2, cap)
        rounds += 1
    out = (results,)
    if return_dists:
        out = out + (dists,)
    if return_exact:
        out = out + (exact_mask,)
    return out if len(out) > 1 else out[0]
