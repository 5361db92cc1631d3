"""HPrepost: the paper's MapReduce miner as sharded JAX (the contribution).

The Hadoop pipeline maps onto a ``(data, model)`` device mesh:

  Job 1 (word count)      -> per-shard histogram kernel + ``psum`` over `data`
  Job 2 map (F-list sort) -> per-shard ``rank_encode_jnp`` (no communication)
  Job 2 reduce (PPC-tree) -> per-shard sort-based ``build_ppc_jnp``: every
                             data shard owns the PPC-tree/N-lists of its block,
                             exactly one Hadoop reducer's state
  F2 scan                 -> per-shard co-occurrence matmul + ``psum``
  k>2 mining waves        -> batched N-list intersections; *candidate* axis
                             sharded over `model` (the PFP/MRPrepost "group
                             partitioning"), per-candidate supports ``psum``-ed
                             over `data` (supports are additive across DB
                             blocks); the parent-state gather between waves is
                             the MapReduce shuffle, expressed as a sharded
                             ``take`` that XLA lowers to collectives.

Mining state per (data-shard, candidate): the merged N-list counts aligned
with the candidate's base-item code slots — static ``(D, C, W)`` buffers, so
every wave is one jitted, fully sharded call. All jitted functions are built
once per miner (static shapes bucketed to powers of two) so repeated mines
hit the jit cache.

The host drives the level loop (as the Hadoop job driver does); device code
never materializes the global database or any global tree.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import encoding as enc
from repro.fault import failures
from repro.mining.telemetry import trace
from repro.core.ppc import build_ppc_jnp
from repro.core.prepost import PrepostResult
from repro.kernels.cooccur.ops import cooccurrence_matrix
from repro.kernels.histogram.ops import item_histogram
from repro.kernels.nlist_intersect.ops import nlist_intersect

INF32 = np.iinfo(np.int32).max

# The mining programs call Pallas kernels inside ``shard_map``. A
# ``pallas_call`` output carries no varying-axes type, and the Pallas
# interpreter's discharge cannot track one, so these shard_maps run without
# the varying-axes check: every collective below sums or maxes genuinely
# per-shard values.
shard_map = functools.partial(jax.shard_map, check_vma=False)

# Version tag of the PreparedDB host payload (``to_host``/``from_host``).
# Bump on any layout change so stale on-disk snapshots are rejected, not
# misread.
PREPARED_SCHEMA = 1


@dataclasses.dataclass(frozen=True)
class HPrepostConfig:
    max_k: int | None = None
    nlist_width: int | None = None  # static W; None = auto (next pow2 of max)
    candidate_unit: int = 256  # candidate buffers: pow2 multiples of this
    la_block: int = 512  # intersect kernel: A-codes per tile
    ly_block: int = 512  # intersect kernel: Y-codes per tile
    batch_block: int = 8  # intersect kernel: candidates per grid program
    partition_candidates: bool = True  # mode B (PFP groups over `model`)
    locality_dispatch: bool = True  # children placed on their parent's shard:
    # the inter-wave shuffle becomes a shard-local gather (zero collectives),
    # at the cost of per-shard padding under skew (§Perf FIM iteration)
    pipeline_waves: bool = True  # dispatch wave l+1 before blocking on wave
    # l's supports: host candidate generation overlaps device execution; the
    # one-wave speculation is sound because support is anti-monotone
    backend: str = "auto"  # a repro.mining.tune registry name (auto | pallas
    # | jnp | pallas-tpu | pallas-gpu | pallas-interpret)
    max_f1: int = 4096  # guard on |F-list| (F2 matrix is K^2)
    max_itemsets: int = 2_000_000
    early_stop: bool = True  # early-stopping intersections (arXiv:1901.07773):
    # host-side Apriori-closure pruning of doomed candidates before they ship,
    # plus in-kernel bound masking on Pallas backends when supports are final
    # (single data shard, non-segmented). False = the exact legacy path,
    # bit-for-bit.
    tune: bool = False  # resolve block knobs through the persisted KernelTuner
    # instead of the static la/ly/batch_block fields

    # knobs that pick *how* waves execute but never change what ``prepare``
    # builds — stripped (normalized to defaults) from prep cache and
    # snapshot keys so a retune or backend switch reuses warm preps
    EXECUTION_ONLY = ("la_block", "ly_block", "batch_block", "backend",
                      "early_stop", "tune")

    def prep_key(self) -> "HPrepostConfig":
        """This config with execution-only knobs normalized away — the
        identity ``PreparedDB`` caches and snapshots key on."""
        defaults = {f: getattr(HPrepostConfig, f) for f in self.EXECUTION_ONLY}
        return dataclasses.replace(self, **defaults)


@dataclasses.dataclass
class PreparedDB:
    """Threshold-floor prepared database: every stage that depends only on
    the *loosest* threshold of a sweep (Job 1 histogram/F-list, Job 2
    PPC-tree build, N-list pack, F2 scan), device-resident.

    ``mine_prepared`` serves any ``min_count >= min_count_floor`` from it:
    the floor F-list is a superset of every tighter F-list, and N-list
    intersections count exact database supports regardless of which extra
    items sit in the tree, so tighter thresholds only *filter* — they never
    need a rebuild.
    """

    fl: enc.FList  # built at min_count_floor (superset of tighter F-lists)
    n_items: int
    n_rows: int  # unpadded R0 the thresholds resolve against
    min_count_floor: int  # loosest threshold this prep can serve
    width: int  # static N-list width W (0 when F1-only)
    packed: Any  # (D, K, W, 3) device N-lists, or None when F1-only
    singleton_state: Any  # packed[..., 2] — wave-2 bootstrap, or None
    C: np.ndarray  # (K, K) upper-triangular F2 co-occurrence counts
    prep_bytes: int  # per-shard footprint: sharded rows + F-list + packed
    rows_flist_bytes: int  # the threshold-independent part of prep_bytes
    stage_times: dict[str, float]  # job1_flist / job2_ppc_pack / f2_scan
    f1_only: bool = False  # True when built with need_waves=False
    n_shards: int = 1  # data-shard count (D) this prep was laid out for
    # False when the F-list order was imposed externally (``prepare(...,
    # flist=...)`` — the streaming path's shared global item order) instead
    # of derived support-descending from this database. Such preps are
    # segment building blocks for ``mine_prepared_segments``; the prefix
    # arithmetic ``mine_prepared`` leans on does not hold for them.
    support_ordered: bool = True

    def to_host(self) -> dict:
        """Gather the prep to a host payload (plain numpy + scalars) for
        cross-process persistence. ``packed`` keeps its ``(D, K, W, 3)``
        per-shard layout — each leading slice is one reducer's PPC-tree
        state, so the payload restores onto any mesh with the same data-
        shard count (``from_host`` enforces that)."""
        out = {
            "schema": PREPARED_SCHEMA,
            "n_items": int(self.n_items),
            "n_rows": int(self.n_rows),
            "min_count_floor": int(self.min_count_floor),
            "width": int(self.width),
            "f1_only": bool(self.f1_only),
            "support_ordered": bool(self.support_ordered),
            "n_shards": int(self.n_shards),
            "prep_bytes": int(self.prep_bytes),
            "rows_flist_bytes": int(self.rows_flist_bytes),
            "fl_min_count": int(self.fl.min_count),
            "fl_items": np.asarray(self.fl.items),
            "fl_supports": np.asarray(self.fl.supports),
            "C": np.asarray(self.C),
        }
        if self.packed is not None:
            out["packed"] = np.asarray(jax.device_get(self.packed))
        return out

    @classmethod
    def from_host(cls, payload: dict, miner: "HPrepostMiner") -> "PreparedDB":
        """Re-shard a ``to_host`` payload onto ``miner``'s mesh.

        Raises ``ValueError`` when the payload cannot serve on this mesh
        (schema skew, data-shard count mismatch, or shape/dtype corruption
        that slipped past the store's digests) — callers treat that as a
        snapshot miss and re-prepare. Prep stage times come back zeroed:
        a warm start pays no prep, and results must say so."""
        try:
            if int(payload["schema"]) != PREPARED_SCHEMA:
                raise ValueError(f"PreparedDB snapshot schema {payload['schema']!r} "
                                 f"!= {PREPARED_SCHEMA}")
            n_shards = int(payload["n_shards"])
            if n_shards != miner.D:
                raise ValueError(
                    f"snapshot was prepared for {n_shards} data shard(s) but the "
                    f"mesh has D={miner.D}; per-shard PPC state does not re-shard "
                    f"— re-prepare on this mesh"
                )
            fl = enc.FList(
                items=np.asarray(payload["fl_items"], np.int32),
                supports=np.asarray(payload["fl_supports"], np.int64),
                n_items=int(payload["n_items"]),
                min_count=int(payload["fl_min_count"]),
            )
            width = int(payload["width"])
            f1_only = bool(payload["f1_only"])
            C = np.asarray(payload["C"], np.int64)
            if C.shape != (fl.k, fl.k):
                raise ValueError(f"snapshot C has shape {C.shape}, expected {(fl.k, fl.k)}")
            packed = singleton = None
            if not f1_only and fl.k > 0:
                ph = np.asarray(payload["packed"], np.int32)
                want = (n_shards, fl.k, width, 3)
                if ph.shape != want:
                    raise ValueError(f"snapshot packed has shape {ph.shape}, expected {want}")
                packed = miner._shard(ph, P(miner._da, None, None, None))
                singleton = packed[:, :, :, 2]
        except (KeyError, TypeError, OverflowError) as e:
            raise ValueError(f"malformed PreparedDB snapshot payload: {e!r}") from e
        return cls(
            fl=fl,
            n_items=int(payload["n_items"]),
            n_rows=int(payload["n_rows"]),
            min_count_floor=int(payload["min_count_floor"]),
            width=width,
            packed=packed,
            singleton_state=singleton,
            C=C,
            prep_bytes=int(payload["prep_bytes"]),
            rows_flist_bytes=int(payload["rows_flist_bytes"]),
            stage_times={"job1_flist": 0.0, "job2_ppc_pack": 0.0, "f2_scan": 0.0},
            f1_only=f1_only,
            n_shards=n_shards,
            # pre-PR5 snapshots carry no key: they were all support-ordered
            support_ordered=bool(payload.get("support_ordered", True)),
        )

    def bytes_at(self, min_count: int, n_shards: int) -> int:
        """Per-shard prep footprint attributable to one threshold: rows +
        F-list + the N-list prefix of ranks frequent at ``min_count`` (the
        floor F-list is support-descending, so that prefix is exactly what
        an independent mine at this threshold would pack). Keeps the
        paper's memory-vs-min_sup figures threshold-dependent instead of
        flat at the sweep's loosest value."""
        packed_part = 0
        if self.packed is not None:
            packed_part = int(self.k_active(min_count) * self.width * 3 * 4 // max(n_shards, 1))
        return self.rows_flist_bytes + packed_part

    def k_active(self, min_count: int) -> int:
        """|F1| at ``min_count`` — a prefix length of the floor F-list."""
        return int(np.count_nonzero(np.asarray(self.fl.supports) >= min_count))


@dataclasses.dataclass
class SegmentHandle:
    """One segment's device state, ready for cross-segment wave execution.

    ``packed``/``singleton`` are the segment's N-list buffers with one extra
    all-invalid *sentinel* rank row appended (``extend_with_sentinel``);
    ``g2l`` maps every global stream rank to the segment's local rank, with
    ranks absent from the segment mapped to the sentinel. The kernel's
    padding semantics (``pre=INF, post=-1, cnt=0`` never subsumes and
    contributes zero) make a sentinel gather an exact empty N-list, so a
    candidate touching an item the segment never saw reports support 0
    there — precisely its contribution to the global (additive) support.
    """

    packed: Any  # (D, K_s + 1, W_s, 3) device N-lists incl. sentinel row
    singleton: Any  # packed[..., 2] — the segment's level-2 bootstrap
    g2l: np.ndarray  # (K_global,) int32: stream rank -> local rank | K_s


class LocalSegmentExecutor:
    """Runs planned waves over in-process segment handles — the execution
    half of ``mine_prepared_segments``, split from the planning loop so a
    coordinator can swap in a remote executor (workers over RPC) without
    touching the planner.

    Contract (shared with ``repro.mining.distributed``'s remote executor):

      - ``n_segments``: how many transaction partitions answer waves; 0
        short-circuits the wave loop (F1-only result).
      - ``begin()``: reset per-query state to the level-2 singleton
        bootstrap.
      - ``dispatch(level, parent_arr, base_idx, q_idx, use_local,
        stop_count=0)``: launch one planned wave over every segment;
        returns an opaque token. Must not block on device results
        (pipelining). ``stop_count`` is the in-kernel early-stop
        threshold — segmented supports are partial until the cross-
        segment reduce, so the planner always passes 0 here (masking
        against the global threshold would be unsound); host-side
        pruning carries the early-stop win instead.
      - ``collect(token)``: block, and return the per-candidate supports
        summed over this executor's segments as an int64 host vector —
        the paper's reduce step for this partition set. With ``weights``
        the reduce is instead the float64 weighted sum ``Σ w_s · sup_s``
        (time-decayed supports: the per-segment integer supports stay
        exact on device; damping happens only in this host reduce).
      - ``weights``: optional per-segment float weights, or None for the
        exact integer reduce — the planner reads this attribute to decide
        integer vs float threshold semantics.
      - ``state_bytes``: footprint of the in-flight merged-N-list states
        after the latest dispatch/collect (peak accounting).
    """

    def __init__(self, miner: "HPrepostMiner", handles: "list[SegmentHandle]",
                 weights=None):
        self.miner = miner
        self.handles = list(handles)
        if weights is not None:
            weights = np.asarray(weights, np.float64)
            if len(weights) != len(self.handles):
                raise ValueError(
                    f"{len(weights)} segment weights for {len(self.handles)} handles"
                )
        self.weights = weights
        self._prev: list | None = None
        self.state_bytes = 0

    @property
    def n_segments(self) -> int:
        return len(self.handles)

    def begin(self) -> None:
        self._prev = [h.singleton for h in self.handles]
        self.state_bytes = 0

    def dispatch(self, level, parent_arr, base_idx, q_idx, use_local,
                 stop_count=0):
        m = self.miner
        failures.fire("mine.wave")
        wave_fn = m._wave_local if use_local else m._wave
        new_states, parts = [], []
        for h, prev in zip(self.handles, self._prev):
            # level-2 parents are singleton ranks (per-segment rows);
            # later levels gather by global slot, shared by layout
            p_arr = h.g2l[parent_arr] if level == 2 else parent_arr
            plan = m._kernel_plan(len(parent_arr), h.packed.shape[2])
            new_s, sup_s = wave_fn(
                h.packed,
                prev,
                m._shard(p_arr, m._cand_spec),
                m._shard(h.g2l[base_idx], m._cand_spec),
                m._shard(h.g2l[q_idx], m._cand_spec),
                np.int32(stop_count),
                la_block=plan.la_block,
                ly_block=plan.ly_block,
                batch_block=plan.batch_block,
                backend=plan.backend,
                early_stop=plan.early_stop,
            )
            new_states.append(new_s)
            parts.append(sup_s)
        m.stage_counters["waves"] += 1
        m.stage_counters["seg_waves"] = (
            m.stage_counters.get("seg_waves", 0) + len(self.handles)
        )
        self._prev = new_states
        self.state_bytes = sum(
            int(s.size * 4 // max(m.D * m._Mb, 1)) for s in new_states
        )
        return parts

    def collect(self, parts) -> np.ndarray:
        arrs = jax.device_get(parts)
        stacked = np.stack(arrs, axis=0)
        if self.weights is not None:
            return np.tensordot(self.weights, stacked.astype(np.float64), axes=1)
        return np.sum(stacked, axis=0, dtype=np.int64)


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class HPrepostMiner:
    """Distributed N-list miner bound to a mesh.

    ``data_axis`` may name multiple mesh axes (e.g. ``("pod", "data")``) —
    DB blocks shard over all of them; ``model_axis`` shards the candidate
    space (mode B). ``model_axis=None`` degrades to pure mode A.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        data_axis: str | tuple[str, ...] = "data",
        model_axis: str | None = "model",
        config: HPrepostConfig = HPrepostConfig(),
    ):
        self.mesh = mesh
        self.data_axis = (data_axis,) if isinstance(data_axis, str) else tuple(data_axis)
        self.model_axis = model_axis
        self.cfg = config
        self.D = int(np.prod([mesh.shape[a] for a in self.data_axis]))
        self.M = int(mesh.shape[model_axis]) if model_axis else 1
        self._cand_spec = (
            P(self.model_axis)
            if (self.cfg.partition_candidates and self.model_axis)
            else P()
        )
        self.last_stage_times: dict[str, float] = {}
        # how many times each device stage ran over this miner's lifetime —
        # the engine's shared-prep planning is asserted against these;
        # ``stop_waves`` counts waves that ran the Pallas early-stop kernel
        # with a nonzero in-kernel threshold
        self.stage_counters: dict[str, int] = {
            "job1": 0, "job2": 0, "pack": 0, "f2": 0, "waves": 0,
            "stop_waves": 0,
        }
        # KernelPlan resolution: the owning frontend/engine attaches a
        # ``KernelTuner`` here; with ``cfg.tune`` off (or no tuner) plans
        # come straight from the config knobs. Memoized per wave shape.
        self.tuner = None
        self._plan_cache: dict[tuple[int, int], Any] = {}
        self._build_jits()

    def _kernel_plan(self, n_cands: int, width: int):
        """Resolve the execution plan (concrete backend + block knobs) for a
        wave of ``n_cands`` candidates over ``width``-slot N-lists."""
        from repro.mining import tune

        key = (tune._bucket(n_cands, 8, 512), tune._bucket(width, 8, 1024))
        plan = self._plan_cache.get(key)
        if plan is None:
            cfg = self.cfg
            if cfg.tune and self.tuner is not None:
                plan = self.tuner.plan_for(
                    backend=cfg.backend, B=n_cands, W=width,
                    early_stop=cfg.early_stop,
                    defaults=(cfg.la_block, cfg.ly_block, cfg.batch_block),
                )
            else:
                plan = tune.static_plan(
                    cfg.backend, cfg.la_block, cfg.ly_block, cfg.batch_block,
                    cfg.early_stop,
                )
            self._plan_cache[key] = plan
        return plan

    @property
    def _da(self):
        return self.data_axis if len(self.data_axis) > 1 else self.data_axis[0]

    def _shard(self, arr: np.ndarray, spec: P) -> jax.Array:
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    # ------------------------------------------------------------------ jits
    def _build_jits(self):
        cfg = self.cfg
        mesh = self.mesh
        da = self._da
        cand_spec = self._cand_spec

        @functools.partial(jax.jit, static_argnames=("n_items",))
        def job1(rows, *, n_items):
            def body(block):
                h = item_histogram(block, n_bins=n_items, backend=cfg.backend)
                return jax.lax.psum(h, da)

            return shard_map(body, mesh=mesh, in_specs=P(da, None), out_specs=P())(rows)

        @functools.partial(jax.jit, static_argnames=("max_nodes", "k", "n_items"))
        def job2(rows, lut, *, max_nodes, k, n_items):
            def body(block, lut):
                ranked = enc.rank_encode_jnp(block, lut, n_items)
                w = jnp.ones(block.shape[0], jnp.int32)
                item, count, pre, post, valid = build_ppc_jnp(ranked, w, max_nodes, n_items=k)
                lens = jax.ops.segment_sum(
                    jnp.where(valid, 1, 0), jnp.where(valid, item, k), num_segments=k + 1
                )[:k]
                lens = jax.lax.pmax(lens, da)
                return ranked[None], item[None], count[None], pre[None], post[None], lens

            return shard_map(
                functools.partial(body, lut=lut),
                mesh=mesh,
                in_specs=P(da, None),
                out_specs=(P(da, None), P(da), P(da), P(da), P(da), P()),
            )(rows)

        @functools.partial(jax.jit, static_argnames=("k", "width"))
        def pack(item, count, pre, post, *, k, width):
            def body(item, count, pre, post):
                item, count, pre, post = item[0], count[0], pre[0], post[0]
                n = item.shape[0]
                # (item, pre) order as two stable one-key sorts: a combined
                # item*n+pre key would overflow int32, and the TPU compiles a
                # one-key sort much faster than a multi-key one
                order = jnp.argsort(jnp.minimum(pre, n), stable=True)
                order = order[jnp.argsort(item[order], stable=True)]
                sitem = item[order]
                boundaries = jnp.searchsorted(sitem, jnp.arange(k + 1))
                slot = jnp.arange(n) - boundaries[jnp.clip(sitem, 0, k)]
                valid = (sitem >= 0) & (slot < width)
                flat = jnp.where(valid, jnp.clip(sitem, 0, k - 1) * width + slot, k * width)
                packed = jnp.full((k * width + 1, 3), jnp.array([INF32, -1, 0]), jnp.int32)
                vals = jnp.stack(
                    [pre[order].astype(jnp.int32), post[order].astype(jnp.int32),
                     count[order].astype(jnp.int32)], axis=1)
                vals = jnp.where(valid[:, None], vals, jnp.array([INF32, -1, 0], jnp.int32))
                packed = packed.at[flat].set(vals, mode="drop")
                return packed[: k * width].reshape(1, k, width, 3)

            return shard_map(
                body, mesh=mesh, in_specs=(P(da),) * 4,
                out_specs=P(da, None, None, None),
            )(item, count, pre, post)

        @functools.partial(jax.jit, static_argnames=("k",))
        def jobf2(rows, *, k):
            def body(block):
                C = cooccurrence_matrix(block[0], n_items=k, backend=cfg.backend)
                return jax.lax.psum(C, da)

            return shard_map(body, mesh=mesh, in_specs=P(da, None), out_specs=P())(rows)

        # the resolved KernelPlan rides in as static kwargs: block knobs and
        # backend pick a lowering, not a value — retraces happen per plan,
        # exactly like the per-shape-bucket retraces the buffers already pay.
        # ``stop`` is the dynamic in-kernel early-stop threshold (0 = off; see
        # mine_prepared for when a nonzero threshold is sound).
        plan_static = ("la_block", "ly_block", "batch_block", "backend",
                       "early_stop")

        @functools.partial(jax.jit, static_argnames=plan_static)
        def wave(packed, prev_state, parent_idx, base_idx, q_idx, stop, *,
                 la_block, ly_block, batch_block, backend, early_stop):
            # MapReduce shuffle: route parent rows to their candidates
            # (paper-faithful MRPrepost-style partitioning — the take crosses
            # shards and XLA emits the shuffle collectives)
            state = jnp.take(prev_state, parent_idx, axis=1)
            state = jax.lax.with_sharding_constraint(
                state, NamedSharding(mesh, P(da, *cand_spec, None))
            )

            def body(packed, state, base_idx, q_idx, stop):
                packed, state = packed[0], state[0]  # (K, W, 3), (C_l, W)
                a = packed[q_idx]
                y = packed[base_idx]
                # fused kernel: per-shard partial supports fall out of the
                # intersection itself — only the scalar psum leaves the shard
                new, part = nlist_intersect(
                    a[:, :, 0], a[:, :, 1], y[:, :, 0], y[:, :, 1], state,
                    a_cnt=a[:, :, 2], backend=backend, la_block=la_block,
                    ly_block=ly_block, batch_block=batch_block,
                    early_stop=early_stop, min_count=stop,
                )
                sup = jax.lax.psum(part, da)
                return new[None], sup

            return shard_map(
                body, mesh=mesh,
                in_specs=(P(da, None, None, None), P(da, *cand_spec, None),
                          cand_spec, cand_spec, P()),
                out_specs=(P(da, *cand_spec, None), cand_spec),
            )(packed, state, base_idx, q_idx, stop)

        @functools.partial(jax.jit, static_argnames=plan_static)
        def wave_local(packed, prev_state, parent_local, base_idx, q_idx, stop,
                       *, la_block, ly_block, batch_block, backend, early_stop):
            # locality-aware dispatch (beyond-paper, §Perf FIM): children sit
            # on their parent's shard, so the parent gather is shard-local —
            # the shuffle disappears; only the support psum remains.
            def body(packed, prev, pidx, bidx, qidx, stop):
                packed, prev = packed[0], prev[0]  # (K, W, 3), (Cprev_l, W)
                state = prev[pidx]  # local rows only
                a = packed[qidx]
                y = packed[bidx]
                new, part = nlist_intersect(
                    a[:, :, 0], a[:, :, 1], y[:, :, 0], y[:, :, 1], state,
                    a_cnt=a[:, :, 2], backend=backend, la_block=la_block,
                    ly_block=ly_block, batch_block=batch_block,
                    early_stop=early_stop, min_count=stop,
                )
                sup = jax.lax.psum(part, da)
                return new[None], sup

            return shard_map(
                body, mesh=mesh,
                in_specs=(
                    P(da, None, None, None),
                    P(da, *cand_spec, None),
                    cand_spec,
                    cand_spec,
                    cand_spec,
                    P(),
                ),
                out_specs=(P(da, *cand_spec, None), cand_spec),
            )(packed, prev_state, parent_local, base_idx, q_idx, stop)

        self._job1, self._job2, self._pack, self._jobf2 = job1, job2, pack, jobf2
        self._wave, self._wave_local = wave, wave_local

    # ---------------------------------------------------------------- driver
    @property
    def _Mb(self) -> int:
        return max(self.M, 1) if (self.cfg.partition_candidates and self.model_axis) else 1

    def prepare(
        self, rows: np.ndarray, n_items: int, min_count_floor: int, *,
        need_waves: bool = True, flist: enc.FList | None = None,
    ) -> PreparedDB:
        """Run every threshold-floor stage once: Job 1 (histogram/F-list),
        Job 2 (PPC-tree), N-list pack, F2 scan. The result serves any
        ``mine_prepared`` at ``min_count >= min_count_floor``.

        ``need_waves=False`` stops after the F-list (for ``max_k == 1``
        traffic, where the tree/N-lists are never consulted).

        ``flist`` imposes an external item order instead of deriving it
        support-descending from this database — the streaming path's global
        stream order, which every segment must share so cross-segment
        N-list ancestor relations agree (PrePost correctness needs one
        consistent total order, not specifically the support order). Job 1
        is skipped then (the caller already counted the batch), and the
        result is marked ``support_ordered=False``: it can only be mined
        through ``mine_prepared_segments``."""
        cfg = self.cfg
        stages: dict[str, float] = {}
        t0 = time.perf_counter()
        with trace.span("prep.job1"):
            R0, L = rows.shape
            Rp = (R0 + self.D - 1) // self.D * self.D
            # the Pallas intersect kernel accumulates counts in fp32 (exact
            # only below 2^24); every count it can produce is bounded by the
            # shard's transaction count, so refuse shards that could silently
            # wrap. The jnp path is integer-exact — only the Pallas dispatch
            # is guarded.
            from repro.kernels.nlist_intersect.ops import FP32_EXACT_MAX
            from repro.mining.tune import is_pallas, resolve_backend

            if is_pallas(resolve_backend(cfg.backend)) and Rp // self.D >= FP32_EXACT_MAX:
                raise ValueError(
                    f"per-shard row count {Rp // self.D} reaches the fp32 exact-"
                    f"integer bound 2^24; shard the database over more devices "
                    f"(D={self.D}) so N-list counts stay exactly representable"
                )
            rows_p = np.full((Rp, L), enc.PAD, np.int32)
            rows_p[:R0] = rows
            rows_sharded = self._shard(rows_p, P(self._da, None))

            if flist is None:
                supports = np.asarray(jax.device_get(self._job1(rows_sharded, n_items=n_items)))
                self.stage_counters["job1"] += 1
                fl = enc.build_flist(supports, min_count_floor)
            else:
                if flist.n_items != n_items:
                    raise ValueError(
                        f"imposed flist covers {flist.n_items} items, database has {n_items}"
                    )
                fl = flist
        stages["job1_flist"] = time.perf_counter() - t0
        K = fl.k
        if K > cfg.max_f1:
            raise ValueError(f"|F1|={K} exceeds max_f1={cfg.max_f1}; raise min_count or max_f1")

        rows_flist_bytes = int(rows_p.nbytes // max(self.D, 1))
        rows_flist_bytes += int(fl.items.nbytes + fl.supports.nbytes)
        prep_bytes = rows_flist_bytes
        stages["job2_ppc_pack"] = 0.0
        stages["f2_scan"] = 0.0
        packed = singleton = None
        C = np.zeros((K, K), np.int64)
        W = 0
        if K > 0 and need_waves:
            # each stage span ends when its device work is done: Job 2 at
            # the device_get of its N-list lengths, pack at an explicit
            # sync, F2 at its device_get
            t0 = time.perf_counter()
            with trace.span("prep.job2"):
                max_nodes = (Rp // self.D) * L
                ranked, item, count, pre, post, lens = self._job2(
                    rows_sharded, jnp.asarray(fl.rank_lut()), max_nodes=max_nodes, k=K,
                    n_items=n_items,
                )
                self.stage_counters["job2"] += 1
                w_needed = int(np.asarray(jax.device_get(lens)).max(initial=1))
            W = cfg.nlist_width or _pow2(max(w_needed, 8))
            with trace.span("prep.pack"):
                packed = jax.block_until_ready(self._pack(item, count, pre, post, k=K, width=W))
                self.stage_counters["pack"] += 1
            stages["job2_ppc_pack"] = time.perf_counter() - t0

            t0 = time.perf_counter()
            with trace.span("prep.f2"):
                if K > 1:
                    C = np.asarray(jax.device_get(self._jobf2(ranked, k=K)))
                    self.stage_counters["f2"] += 1
                C = np.triu(C, 1)
            stages["f2_scan"] = time.perf_counter() - t0
            prep_bytes += int(packed.size * 4 // max(self.D, 1))
            # level-2 bootstrap: parents are singletons, prev_state = node
            # counts (replicated over `model`: the bootstrap take is
            # collective-free)
            singleton = packed[:, :, :, 2]

        return PreparedDB(
            fl=fl, n_items=n_items, n_rows=R0, min_count_floor=int(min_count_floor),
            width=W, packed=packed, singleton_state=singleton, C=C,
            prep_bytes=prep_bytes, rows_flist_bytes=rows_flist_bytes,
            stage_times=stages, f1_only=not need_waves, n_shards=self.D,
            support_ordered=flist is None,
        )

    def _pack_wave(self, ranks, parents, qarr, level: int, slots_per_shard: int):
        """Host slot assignment for one wave: candidate i -> device slot.

        Pure array ops — candidate counts reach 10^5+ per wave, and this
        runs on the serial host rail the pipelined waves overlap with.
        ``ranks`` is (C, k) ascending rank rows; ``parents`` the previous-
        wave slots; ``qarr`` the extension ranks.

        -> (parent_arr, base_idx, q_idx, slot_of, Cpad, wave_fn)."""
        cfg = self.cfg
        unit = cfg.candidate_unit
        Mb = self._Mb
        Cn = len(ranks)
        base = ranks[:, 1].astype(np.int32)
        if level == 2 or not cfg.locality_dispatch:
            Cs = unit * _pow2((Cn + unit * Mb - 1) // (unit * Mb))
            Cpad = Cs * Mb
            slot_of = np.arange(Cn, dtype=np.int64)  # candidate i -> slot i
            parent_arr = np.zeros(Cpad, np.int32)
            base_idx = np.zeros(Cpad, np.int32)
            q_idx = np.zeros(Cpad, np.int32)
            parent_arr[:Cn] = parents
            base_idx[:Cn] = base
            q_idx[:Cn] = qarr
            return parent_arr, base_idx, q_idx, slot_of, Cpad, self._wave

        # locality-aware: bucket children onto their parent's shard; the
        # stable argsort over bucket ids yields each candidate's rank within
        # its bucket without any per-candidate loop
        bucket = np.minimum(parents.astype(np.int64) // slots_per_shard, Mb - 1)
        counts = np.bincount(bucket, minlength=Mb)
        worst = int(counts.max())
        Cs = unit * _pow2((worst + unit - 1) // unit)
        Cpad = Cs * Mb
        order = np.argsort(bucket, kind="stable")
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        pos = np.empty(Cn, np.int64)
        pos[order] = np.arange(Cn) - starts[bucket[order]]
        slot_of = bucket * Cs + pos
        parent_arr = np.zeros(Cpad, np.int32)
        base_idx = np.zeros(Cpad, np.int32)
        q_idx = np.zeros(Cpad, np.int32)
        parent_arr[slot_of] = parents % slots_per_shard  # local row
        base_idx[slot_of] = base
        q_idx[slot_of] = qarr
        return parent_arr, base_idx, q_idx, slot_of, Cpad, self._wave_local

    @staticmethod
    def _extensions(ranks, slots, pair_packed, prefix_packed, k_items):
        """Candidate generation: extend each rank row with every rank
        ``q2 < ranks[0]`` whose pairs with all members are frequent.

        Vectorized over the whole wave: the per-candidate allowed set is the
        bitwise AND of the gathered bit-packed ``pair_ok`` rows of its
        members, masked by the packed strict-lower-triangle prefix row of
        its smallest rank — no per-candidate Python loop.

        -> (ranks', parents', q') with ranks' of width ``ranks.shape[1]+1``."""
        k = ranks.shape[1]
        if not len(ranks):
            return (np.empty((0, k + 1), np.int32), np.empty(0, np.int64),
                    np.empty(0, np.int32))
        allowed = np.bitwise_and.reduce(pair_packed[ranks], axis=1)  # (C, Kb)
        allowed &= prefix_packed[ranks[:, 0]]
        mask = np.unpackbits(allowed, axis=1, count=k_items).view(bool)
        cs, q2s = np.nonzero(mask)
        new_ranks = np.concatenate(
            [q2s[:, None].astype(np.int32), ranks[cs]], axis=1
        )
        return new_ranks, slots[cs], q2s.astype(np.int32)

    @staticmethod
    def _apriori_kept(d_ranks: np.ndarray, surv_ranks: np.ndarray):
        """Anti-monotone host bound, boolean form: a width-``l+1`` candidate
        can reach ``min_count`` only if *every* drop-one subset of width
        ``l`` survived the settled wave — the enumeration guarantees every
        frequent width-``l`` itemset is in ``surv_ranks``, so a missing
        subset proves the candidate doomed. Position 0 (the extension item)
        is the parent the caller already checked; pair subsets are implied
        by ``pair_ok`` — so this only bites from width 4 up, and returns
        None below that.

        Membership is vectorized by viewing C-contiguous int32 rank rows as
        fixed-width byte strings: at equal total width, numpy's trailing-
        NUL-stripping compare is still an exact row equality."""
        l1 = d_ranks.shape[1]
        if l1 < 4 or not len(d_ranks) or not len(surv_ranks):
            return None
        w = l1 - 1
        sv = np.ascontiguousarray(surv_ranks).view(f"S{4 * w}").ravel()
        kept = np.ones(len(d_ranks), bool)
        for pos in range(1, l1):
            sub = np.ascontiguousarray(
                np.concatenate([d_ranks[:, :pos], d_ranks[:, pos + 1:]], axis=1)
            )
            kept &= np.isin(sub.view(f"S{4 * w}").ravel(), sv)
            if not kept.any():
                break
        return kept

    def mine_prepared(
        self,
        prepared: PreparedDB,
        min_count: int,
        *,
        max_k: int | None | type(Ellipsis) = ...,
    ) -> PrepostResult:
        """The k>2 wave loop only, over a shared ``PreparedDB``. Any
        ``min_count >= prepared.min_count_floor`` is served exactly: floor
        structures are supersets, N-list supports are exact DB supports.

        With ``cfg.pipeline_waves`` the loop dispatches wave ``l+1`` before
        blocking on wave ``l``'s supports, so host candidate generation
        overlaps device execution. The one wave of speculation is sound:
        children of candidates that turn out infrequent report supports
        below ``min_count`` themselves (anti-monotonicity), so they can
        never be emitted; once the parent wave's supports arrive, the dead
        branches are pruned from further host enumeration.
        """
        cfg = self.cfg
        max_k = cfg.max_k if max_k is ... else max_k
        if not prepared.support_ordered:
            raise ValueError(
                "PreparedDB was built with an imposed (stream-order) F-list; "
                "its F-list is not a support-descending prefix structure — "
                "mine it through mine_prepared_segments"
            )
        if min_count < prepared.min_count_floor:
            raise ValueError(
                f"min_count={min_count} is looser than the PreparedDB floor "
                f"{prepared.min_count_floor}; re-prepare at the looser threshold"
            )
        fl = prepared.fl
        K = fl.k
        stages = self.last_stage_times = {
            "job1_flist": 0.0, "job2_ppc_pack": 0.0, "f2_scan": 0.0,
            "mining_waves": 0.0,
            # planning counters ride the stage dict into MineResult
            # stage_times_s: candidates shipped, and candidates the host
            # bound killed (dead parent / missing Apriori subset), the most
            # candidate slots one wave put on the device, and the padded
            # slots of all waves (what the kernel computes)
            "planned_candidates": 0.0, "largest_wave": 0.0, "wave_slots": 0.0,
            "host_pruned_parent": 0.0, "host_pruned_subset": 0.0,
        }
        itemsets: dict[tuple[int, ...], int] = {}
        k_act = prepared.k_active(min_count)
        items_arr = np.asarray(fl.items)
        for it, s in zip(
            items_arr[:k_act].tolist(), np.asarray(fl.supports)[:k_act].tolist()
        ):
            itemsets[(int(it),)] = int(s)
        # per-threshold views of the shared floor structures: the F-list
        # prefix and footprint an independent mine at min_count would build
        # (keeps sweep results threshold-dependent, not flat at the floor)
        flist_items = fl.items[:k_act]
        peak = prepared.bytes_at(min_count, self.D)
        if K == 0 or max_k == 1 or not itemsets:
            return PrepostResult(itemsets, flist_items, len(itemsets), len(itemsets), peak)
        if prepared.f1_only:
            raise ValueError(
                "PreparedDB was built with need_waves=False (F1 only); "
                "re-prepare with need_waves=True to mine k >= 2"
            )

        C = prepared.C
        pair_ok = (C + C.T) >= min_count
        # bit-packed planning tables for the vectorized _extensions:
        # pair_packed[r] is pair_ok's row r, prefix_packed[r] the strict
        # prefix mask {q2 : q2 < r} — both 8 ranks per byte
        pair_packed = np.packbits(pair_ok, axis=1)
        prefix_packed = np.packbits(np.tri(K, K, -1, dtype=bool), axis=1)
        packed = prepared.packed
        prev_state = prepared.singleton_state
        qs, ps = np.nonzero(C >= min_count)
        ranks = np.stack([qs, ps], axis=1).astype(np.int32)  # (C, 2) ascending
        parents = ps.astype(np.int64)  # level-2 parents: singleton rank slots
        qarr = qs.astype(np.int32)
        level = 2
        Mb = self._Mb
        slots_per_shard = 0  # of the *previous* wave (for locality bucketing)
        pending = None  # (ranks, slot_of, device supports) of the wave in flight
        # in-kernel early stop is only sound where the kernel sees *final*
        # supports: one data shard (no cross-shard psum completes them
        # later). Off (0) it costs nothing — the mask multiplies by 1.0.
        stop_count = min_count if (cfg.early_stop and self.D == 1) else 0
        from repro.mining.tune import is_pallas

        t0 = time.perf_counter()
        while len(ranks) or pending is not None:
            dispatched = None
            if len(ranks) and (max_k is None or level <= max_k) and len(itemsets) < cfg.max_itemsets:
                parent_arr, base_idx, q_idx, slot_of, Cpad, wave_fn = self._pack_wave(
                    ranks, parents, qarr, level, slots_per_shard
                )
                plan = self._kernel_plan(Cpad, prepared.width)
                stages["planned_candidates"] += float(len(ranks))
                stages["largest_wave"] = max(stages["largest_wave"], float(Cpad))
                stages["wave_slots"] += float(Cpad)
                failures.fire("mine.wave")
                with trace.span("mine.wave", k=level, candidates=len(ranks)):
                    new_state, sups = wave_fn(
                        packed,
                        prev_state,
                        self._shard(parent_arr, self._cand_spec),
                        self._shard(base_idx, self._cand_spec),
                        self._shard(q_idx, self._cand_spec),
                        np.int32(stop_count),
                        la_block=plan.la_block,
                        ly_block=plan.ly_block,
                        batch_block=plan.batch_block,
                        backend=plan.backend,
                        early_stop=plan.early_stop,
                    )
                self.stage_counters["waves"] += 1
                if stop_count and plan.early_stop and is_pallas(plan.backend):
                    self.stage_counters["stop_waves"] += 1
                dispatched = (ranks, parents, slot_of, sups)
                peak = max(peak, int(new_state.size * 4 // max(self.D * Mb, 1)))
                prev_state = new_state
                slots_per_shard = Cpad // Mb
                level += 1
            if not cfg.pipeline_waves and dispatched is not None:
                # degrade: block right away (no speculative wave in flight,
                # so the parent column is never consulted)
                pending = (dispatched[0], dispatched[2], dispatched[3])
                dispatched = None

            surv_mask = None  # boolean over the settled wave's device slots
            surv_ranks = surv_slots = None
            if pending is not None:
                p_ranks, p_slots, p_sups = pending
                with trace.span("mine.reduce", k=level - 1):
                    host = np.asarray(jax.device_get(p_sups))  # blocks on wave l-1
                svals = host[p_slots]
                keep = svals >= min_count
                if keep.any():
                    emit_items = np.sort(items_arr[p_ranks[keep]], axis=1)
                    for t, s in zip(emit_items.tolist(), svals[keep].tolist()):
                        itemsets[tuple(t)] = int(s)
                surv_mask = np.zeros(host.shape[0], bool)
                surv_mask[p_slots[keep]] = True
                surv_ranks, surv_slots = p_ranks[keep], p_slots[keep]
                pending = None

            if dispatched is not None:
                d_ranks, d_parents, d_slot_of, d_sups = dispatched
                if surv_mask is not None:
                    # speculative wave l was enumerated before wave l-1's
                    # supports arrived; drop children of dead parents from
                    # further enumeration (their own supports self-filter)
                    kept = surv_mask[d_parents]
                    stages["host_pruned_parent"] += float((~kept).sum())
                    d_ranks, d_slot_of = d_ranks[kept], d_slot_of[kept]
                    if cfg.early_stop:
                        sub = self._apriori_kept(d_ranks, surv_ranks)
                        if sub is not None:
                            stages["host_pruned_subset"] += float((~sub).sum())
                            d_ranks, d_slot_of = d_ranks[sub], d_slot_of[sub]
                pending = (d_ranks, d_slot_of, d_sups)
                ranks, parents, qarr = self._extensions(
                    d_ranks, d_slot_of, pair_packed, prefix_packed, K
                )
            elif surv_mask is not None and not cfg.pipeline_waves:
                ranks, parents, qarr = self._extensions(
                    surv_ranks, surv_slots, pair_packed, prefix_packed, K
                )
                if cfg.early_stop and len(ranks):
                    # un-pipelined, the closure check lands *before* dispatch:
                    # doomed candidates never ship to the device at all
                    sub = self._apriori_kept(ranks, surv_ranks)
                    if sub is not None:
                        stages["host_pruned_subset"] += float((~sub).sum())
                        ranks, parents, qarr = ranks[sub], parents[sub], qarr[sub]
            else:
                ranks = np.empty((0, 2), np.int32)
                parents = np.empty(0, np.int64)
                qarr = np.empty(0, np.int32)

        stages["mining_waves"] = time.perf_counter() - t0
        return PrepostResult(itemsets, flist_items, len(itemsets), len(itemsets), peak)

    def extend_with_sentinel(self, prepared: PreparedDB):
        """``(packed_ext, singleton_ext)``: the prepared N-list buffers with
        one all-invalid rank row appended at index ``K_s`` — the slot
        ``SegmentHandle.g2l`` routes globally-known-but-locally-absent items
        to. Re-device_put keeps the per-shard layout explicit."""
        if prepared.packed is None:
            raise ValueError("cannot extend an F1-only PreparedDB (no N-lists packed)")
        pad = np.broadcast_to(
            np.array([INF32, -1, 0], np.int32), (self.D, 1, prepared.width, 3)
        )
        ext = jnp.concatenate([prepared.packed, jnp.asarray(pad)], axis=1)
        ext = jax.device_put(ext, NamedSharding(self.mesh, P(self._da, None, None, None)))
        return ext, ext[:, :, :, 2]

    def mine_prepared_segments(
        self,
        handles: "list[SegmentHandle]",
        items: np.ndarray,
        supports: np.ndarray,
        C: np.ndarray,
        min_count: int,
        *,
        max_k: int | None | type(Ellipsis) = ...,
        peak_base: int = 0,
        executor=None,
        weights=None,
        seed=None,
        seed_out=None,
    ) -> PrepostResult:
        """The k>2 wave loop over a *segmented* database (the streaming
        reduce step): candidates are planned once against the global
        F-lists (``items``/``supports`` in stream-rank order, ``C`` the
        summed upper-triangular F2 matrix in the same rank space), each
        wave launches the fused intersect kernel once per segment, and the
        per-candidate supports are summed across segments before
        thresholding — exact because segments partition the transactions,
        so itemset supports are additive over them.

        Every segment carries its own merged-N-list state chain between
        waves (a segment is one partition's PPC forest); the *slot* layout
        (``_pack_wave``) is global and shared, so parent gathers at levels
        > 2 need no per-segment translation — only base/extension item
        indices (and the level-2 singleton parents) route through each
        segment's ``g2l``. Pipelining semantics match ``mine_prepared``.

        ``executor`` abstracts *where* waves run: the default
        ``LocalSegmentExecutor(self, handles)`` executes them in-process
        (exactly the pre-refactor behavior); ``repro.mining.distributed``
        passes a remote executor that broadcasts each wave to worker
        processes and sums their support vectors — the planning loop here
        is identical either way, which is what makes the distributed path
        bit-identical by construction.

        ``weights`` (or an executor carrying a ``weights`` attribute)
        switches the cross-segment reduce to the float64 weighted sum of
        time-decayed mining: ``supports``/``C``/``min_count`` are then
        read as float accumulations and emitted supports are floats; the
        per-segment device path is untouched (integer-exact), only the
        host reduce and threshold run in float.

        ``seed`` prunes with a standing query's previous waves (exact
        integer mode only): a dict of per-itemset support *upper bounds*
        — typically the exact supports the previous refresh collected,
        inflated by the rows appended since (each new row raises any
        support by at most 1, and expiry only lowers it). A candidate
        whose bound misses ``min_count`` is provably infrequent and is
        dropped before dispatch (``host_pruned_seed``) along with — by
        anti-monotonicity — the whole subtree it would have opened; a
        candidate absent from the seed is always kept. The emitted
        answer is therefore bit-identical to an unseeded mine.
        ``seed_out``, if a dict, collects the exact reduced support of
        every candidate this mine settles (frequent or not) — the raw
        material for the next refresh's seed.
        """
        cfg = self.cfg
        max_k = cfg.max_k if max_k is ... else max_k
        items_arr = np.asarray(items, np.int32)
        if executor is None:
            executor = LocalSegmentExecutor(self, handles, weights=weights)
        elif weights is not None:
            raise ValueError(
                "pass decay weights through the executor, not alongside one"
            )
        weighted = getattr(executor, "weights", None) is not None
        supports = np.asarray(supports, np.float64 if weighted else np.int64)
        as_sup = float if weighted else int
        K = len(items_arr)
        stages = self.last_stage_times = {
            "job1_flist": 0.0, "job2_ppc_pack": 0.0, "f2_scan": 0.0,
            "mining_waves": 0.0,
            "planned_candidates": 0.0, "largest_wave": 0.0, "wave_slots": 0.0,
            "host_pruned_parent": 0.0, "host_pruned_subset": 0.0,
            "host_pruned_seed": 0.0,
        }
        itemsets: dict[tuple[int, ...], int] = {}
        freq = supports >= min_count
        # result F-list stays support-descending (ties: item asc) whatever
        # the stream-rank order is — the contract every miner reports
        f_items = items_arr[freq]
        f_sups = supports[freq]
        order = np.lexsort((f_items, -f_sups))
        flist_items = f_items[order]
        for it, s in zip(flist_items.tolist(), f_sups[order].tolist()):
            itemsets[(int(it),)] = as_sup(s)
        peak = int(peak_base)
        if K == 0 or max_k == 1 or not itemsets or executor.n_segments == 0:
            return PrepostResult(itemsets, flist_items, len(itemsets), len(itemsets), peak)

        seed_keep = None
        if seed is not None and not weighted:

            def seed_keep(ranks_):
                cand = np.sort(items_arr[ranks_], axis=1)
                return np.fromiter(
                    (seed.get(tuple(t), min_count) >= min_count
                     for t in cand.tolist()),
                    bool, len(cand),
                )

        pair_ok = (C + C.T) >= min_count
        pair_packed = np.packbits(pair_ok, axis=1)
        prefix_packed = np.packbits(np.tri(K, K, -1, dtype=bool), axis=1)
        executor.begin()
        qs, ps = np.nonzero(C >= min_count)
        ranks = np.stack([qs, ps], axis=1).astype(np.int32)
        parents = ps.astype(np.int64)
        qarr = qs.astype(np.int32)
        level = 2
        Mb = self._Mb
        slots_per_shard = 0
        pending = None  # (ranks, slot_of, [per-segment device supports])

        t0 = time.perf_counter()
        while len(ranks) or pending is not None:
            if seed_keep is not None and len(ranks):
                km = seed_keep(ranks)
                if not km.all():
                    stages["host_pruned_seed"] += float((~km).sum())
                    ranks, parents, qarr = ranks[km], parents[km], qarr[km]
            dispatched = None
            if len(ranks) and (max_k is None or level <= max_k) and len(itemsets) < cfg.max_itemsets:
                parent_arr, base_idx, q_idx, slot_of, Cpad, wave_fn = self._pack_wave(
                    ranks, parents, qarr, level, slots_per_shard
                )
                # stop_count stays 0: per-segment supports are partial until
                # the cross-segment reduce, so only the host bound prunes here
                stages["planned_candidates"] += float(len(ranks))
                stages["largest_wave"] = max(stages["largest_wave"], float(Cpad))
                stages["wave_slots"] += float(Cpad)
                with trace.span("mine.wave", k=level, candidates=len(ranks),
                                segments=executor.n_segments):
                    token = executor.dispatch(
                        level, parent_arr, base_idx, q_idx, wave_fn is self._wave_local
                    )
                dispatched = (ranks, parents, slot_of, token)
                peak = max(peak, int(executor.state_bytes))
                slots_per_shard = Cpad // Mb
                level += 1
            if not cfg.pipeline_waves and dispatched is not None:
                pending = (dispatched[0], dispatched[2], dispatched[3])
                dispatched = None

            surv_mask = None
            surv_ranks = surv_slots = None
            if pending is not None:
                p_ranks, p_slots, p_token = pending
                # the streaming reduce: per-candidate supports summed over
                # segments (additivity over disjoint partitions), THEN
                # thresholded — this blocks on the settled wave
                with trace.span("mine.reduce", k=level - 1):
                    host = executor.collect(p_token)
                peak = max(peak, int(executor.state_bytes))
                svals = host[p_slots]
                keep = svals >= min_count
                if seed_out is not None and len(p_ranks):
                    # exact settled supports of EVERY candidate (dead ones
                    # included — near-frontier corpses are what the next
                    # refresh's seed prunes)
                    all_items = np.sort(items_arr[p_ranks], axis=1)
                    for t, s in zip(all_items.tolist(), svals.tolist()):
                        seed_out[tuple(t)] = as_sup(s)
                if keep.any():
                    emit_items = np.sort(items_arr[p_ranks[keep]], axis=1)
                    for t, s in zip(emit_items.tolist(), svals[keep].tolist()):
                        itemsets[tuple(t)] = as_sup(s)
                surv_mask = np.zeros(host.shape[0], bool)
                surv_mask[p_slots[keep]] = True
                surv_ranks, surv_slots = p_ranks[keep], p_slots[keep]
                pending = None

            if dispatched is not None:
                d_ranks, d_parents, d_slot_of, d_token = dispatched
                if surv_mask is not None:
                    kept = surv_mask[d_parents]
                    stages["host_pruned_parent"] += float((~kept).sum())
                    d_ranks, d_slot_of = d_ranks[kept], d_slot_of[kept]
                    if cfg.early_stop:
                        sub = self._apriori_kept(d_ranks, surv_ranks)
                        if sub is not None:
                            stages["host_pruned_subset"] += float((~sub).sum())
                            d_ranks, d_slot_of = d_ranks[sub], d_slot_of[sub]
                pending = (d_ranks, d_slot_of, d_token)
                ranks, parents, qarr = self._extensions(
                    d_ranks, d_slot_of, pair_packed, prefix_packed, K
                )
            elif surv_mask is not None and not cfg.pipeline_waves:
                ranks, parents, qarr = self._extensions(
                    surv_ranks, surv_slots, pair_packed, prefix_packed, K
                )
                if cfg.early_stop and len(ranks):
                    sub = self._apriori_kept(ranks, surv_ranks)
                    if sub is not None:
                        stages["host_pruned_subset"] += float((~sub).sum())
                        ranks, parents, qarr = ranks[sub], parents[sub], qarr[sub]
            else:
                ranks = np.empty((0, 2), np.int32)
                parents = np.empty(0, np.int64)
                qarr = np.empty(0, np.int32)

        stages["mining_waves"] = time.perf_counter() - t0
        return PrepostResult(itemsets, flist_items, len(itemsets), len(itemsets), peak)

    def mine(
        self,
        rows: np.ndarray,
        n_items: int,
        min_count: int,
        *,
        max_k: int | None | type(Ellipsis) = ...,
    ) -> PrepostResult:
        """One-shot mine = ``prepare`` at ``min_count`` + ``mine_prepared``.
        ``max_k=...`` inherits the config's cap; an explicit value overrides
        it per call (the bound jits are level-cap agnostic, so a warm miner
        serves any ``max_k``)."""
        max_k = self.cfg.max_k if max_k is ... else max_k
        prepared = self.prepare(
            rows, n_items, min_count, need_waves=max_k is None or max_k > 1
        )
        res = self.mine_prepared(prepared, min_count, max_k=max_k)
        # one-shot path pays its own prep: fold the real stage times back in
        self.last_stage_times.update(prepared.stage_times)
        return res
