"""Lowering scheduled TIN statements to executable JAX (paper §IV).

This is the Fig. 9a code-generation algorithm adapted to XLA's static-SPMD
model (DESIGN.md §2):

1. **Plan**: for the distributed index variable, create the *initial level
   partition* — universe partitions for coordinate-value loops, non-zero
   partitions for coordinate-position loops — then derive full
   coordinate-tree partitions of every accessed tensor with
   image/preimage (``partition_tensor_rows`` / ``partition_tensor_nonzeros``)
   and replicate tensors not indexed by the distributed variable
   (``partitionRemainingCoordinateTrees`` → TDN replication).
2. **Materialize**: pack per-color sub-tensors into stacked padded arrays.
3. **Emit**: select the specialized leaf kernel for (expression signature ×
   strategy space × format), wrap it in the distributed loop — `jax.vmap`
   over the color axis for the single-process simulation backend, or
   `jax.shard_map` over a real mesh axis for SPMD execution — and place the
   collectives implied by ``communicate`` (replication = all-gather ahead of
   the loop; overlapping output roots = reduction after it).

The result is a *bespoke compiled function* per (computation, format,
data distribution, computation distribution) — the paper's compilation
thesis, versus interpretation (see core/interp.py for the CTF analog).
"""
from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .cache import BATCH_BUCKETS, LRUCache, avals_key, batch_bucket
from . import formats as fmt
from . import levels
from .partition import (CONVERT_CACHE_STATS, SHARD_CACHE_STATS,
                        ShardedTensor, TensorPartition,
                        block_aligned_row_bounds, clear_convert_cache,
                        clear_shard_cache, convert_tensor_cached,
                        elastic_row_bounds, fingerprint_memo,
                        materialize_add_stream, materialize_bcsr_nnz,
                        materialize_bcsr_rows, materialize_coo_nnz,
                        materialize_csr_rows, materialize_dense_cols,
                        materialize_dense_grid, materialize_dense_rows,
                        materialize_dense_rows_pieces, materialize_pieces,
                        materialize_replicated,
                        materialize_replicated_elastic, partition_by_bounds,
                        partition_tensor_nonzeros, partition_tensor_rows,
                        replicate_tensor, tensor_fingerprint,
                        weights_fingerprint)
from .schedule import DistStrategy, Schedule
from .tdn import Distribution, Machine
from .tensor import Tensor
from .tin import Access, Assignment, IndexVar, Mul

log = logging.getLogger(__name__)
from ..runtime import telemetry
from ..kernels import ref as K
from ..kernels.layout import (pack_mat_inner_blocks, pack_mat_row_blocks,
                              pack_rowwindow_blocks, pack_vec_blocks)


@dataclasses.dataclass
class AxisComm:
    """Per-machine-axis communication ledger for grid-distributed kernels.

    ``broadcast_bytes`` / ``reduce_bytes`` hold the TOTAL distinct payload
    moved along this axis (summed over the orthogonal axis's groups); each
    payload byte reaches / leaves ``size - 1`` peers, so the wire cost is
    ``payload * (size - 1)``. Attributing movement to the axis that carries
    it is what makes the SUMMA win visible: a 2-D SpMM broadcasts the dense
    operand's k-windows along x only and reduces output partials along y
    only, strictly less than 1-D's full replication at equal piece count."""

    size: int = 1
    broadcast_bytes: int = 0
    reduce_bytes: int = 0

    def network_bytes(self) -> int:
        return (self.broadcast_bytes + self.reduce_bytes) * \
            max(self.size - 1, 0)

    def as_dict(self) -> Dict[str, int]:
        return {"size": self.size, "broadcast_bytes": self.broadcast_bytes,
                "reduce_bytes": self.reduce_bytes,
                "network_bytes": self.network_bytes()}


@dataclasses.dataclass
class CommStats:
    """Communication model for the lowered kernel (drives §Roofline).

    ``replicate_bytes``: payload all-gathered to every color before the
    distributed loop (paper's `communicate` at the loop).
    ``reduce_bytes``: overlapping-output payload reduced after the loop
    (non-zero strategies).
    ``redistribute_bytes``: data-vs-computation distribution mismatch cost
    (paper §II-D final paragraph — legal but costed).
    ``axes``: per-machine-axis breakdown for grid (multi-axis) schedules —
    bytes live EITHER in the flat fields (1-D strategies) or in ``axes``
    (grid strategies), never both, so totals never double count.
    ``overlap_total_bytes`` / ``overlap_hidden_bytes``: set by the
    double-buffered executor (distributed.executor.run_overlapped) — how
    much of the shard-transfer traffic was in flight while a leaf kernel
    ran. Attribution only: these RE-DESCRIBE bytes already counted above,
    so they never enter ``total_network_bytes``."""

    pieces: int = 1
    replicate_bytes: int = 0
    reduce_bytes: int = 0
    redistribute_bytes: int = 0
    axes: Dict[str, AxisComm] = dataclasses.field(default_factory=dict)
    overlap_total_bytes: int = 0
    overlap_hidden_bytes: int = 0

    def total_network_bytes(self) -> int:
        # all-gather of b bytes to P nodes moves b*(P-1); reductions likewise
        p = max(self.pieces - 1, 0)
        return (self.replicate_bytes + self.reduce_bytes) * p + \
            self.redistribute_bytes + \
            sum(a.network_bytes() for a in self.axes.values())

    def as_dict(self) -> Dict[str, int]:
        out = {
            "pieces": self.pieces,
            "replicate_bytes": self.replicate_bytes,
            "reduce_bytes": self.reduce_bytes,
            "redistribute_bytes": self.redistribute_bytes,
            "total_network_bytes": self.total_network_bytes(),
        }
        if self.axes:
            out["axes"] = {n: a.as_dict() for n, a in self.axes.items()}
        if self.overlap_total_bytes:
            out["overlap_total_bytes"] = self.overlap_total_bytes
            out["overlap_hidden_bytes"] = self.overlap_hidden_bytes
        return out


# ---------------------------------------------------------------------------
# Re-plan fast path: plan memoization + compiled-runner reuse. Together with
# partition.SHARD_CACHE these make re-lowering over unchanged inputs
# near-free — the expensive assembly (partition walk, numpy shard packing,
# jit re-tracing) happens once; a straggler re-plan or repeated solve pays
# only content fingerprinting + execution.
# ---------------------------------------------------------------------------

# Memoized plans: (signature, strategy, pieces, weights, operand
# fingerprints) -> {name: TensorPartition}. An unchanged schedule over
# unchanged operands skips the partitioning walk entirely; _plans_equal is
# the differential check (tests assert a memoized plan equals a freshly
# computed one).
_PLAN_CACHE = LRUCache(capacity=64)
PLAN_CACHE_STATS = _PLAN_CACHE.stats

# Compiled runners: (emitter name, static trace constants, shard array
# shapes/dtypes) -> the jitted compute fn. The emitter name encodes
# expression × strategy × format family (bcsr emitters are distinct
# functions); shard avals subsume the declared-format component because the
# emitters are format-general once shards are materialized (the densified
# row-window view). Reusing the jitted callable object is what lets jax's
# compilation cache hit instead of re-tracing per lower.
_RUNNER_CACHE = LRUCache(capacity=128)
RUNNER_CACHE_STATS = _RUNNER_CACHE.stats


def set_plan_cache_capacity(capacity: int) -> None:
    _PLAN_CACHE.set_capacity(capacity)


def set_runner_cache_capacity(capacity: int) -> None:
    _RUNNER_CACHE.set_capacity(capacity)


def clear_lowering_caches() -> None:
    """Drop plan, runner, shard, tuned-plan, and SPMD-executable caches —
    the cold path, used by benchmarks to measure what re-lowering cost
    before the caches."""
    _PLAN_CACHE.clear()
    _RUNNER_CACHE.clear()
    clear_shard_cache()
    clear_convert_cache()
    import sys
    executor = sys.modules.get("repro.distributed.executor")
    if executor is not None:     # deferred: executor imports this module
        executor.clear_spmd_cache()
    plan_search = sys.modules.get("repro.core.plan_search")
    if plan_search is not None:  # deferred: the planner imports this module
        plan_search.clear_tuned_plan_cache()


@dataclasses.dataclass
class CacheStats:
    """Per-lower cache effectiveness, snapshotted onto LoweredKernel.cache
    (alongside CommStats): how much of this lower's plan / shard-packing /
    jit-tracing work was reused from previous lowers."""

    plan_hits: int = 0
    plan_misses: int = 0
    shard_hits: int = 0
    shard_misses: int = 0
    runner_hits: int = 0
    runner_misses: int = 0
    convert_hits: int = 0
    convert_misses: int = 0
    # schedule="auto" tuned-plan cache (core.plan_search): a hit means the
    # lower skipped the candidate search entirely.
    tuned_hits: int = 0
    tuned_misses: int = 0

    @property
    def shard_reuse(self) -> float:
        """Fraction of shard-cache lookups this lower served from cache —
        the elastic-resize metric (relower asserts ≥ 0.5 reuse on a
        migration-style P→P−1; bench_fault reports it). 0.0 when the
        lower did no shard lookups at all."""
        total = self.shard_hits + self.shard_misses
        return self.shard_hits / total if total else 0.0

    @property
    def warm(self) -> bool:
        """True when the lower re-assembled nothing (full fast path)."""
        return (self.plan_misses == 0 and self.shard_misses == 0
                and self.runner_misses == 0 and self.convert_misses == 0
                and self.tuned_misses == 0)

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


def _tuned_cache_stats() -> Dict[str, int]:
    """Tuned-plan cache counters, read lazily: plan_search imports this
    module, so lower only sees its stats once the planner is in use."""
    import sys
    plan_search = sys.modules.get("repro.core.plan_search")
    if plan_search is None:
        return {"hits": 0, "misses": 0}
    return plan_search.TUNED_PLAN_CACHE_STATS


def _cache_snapshot() -> Tuple[int, ...]:
    tuned = _tuned_cache_stats()
    return (PLAN_CACHE_STATS["hits"], PLAN_CACHE_STATS["misses"],
            SHARD_CACHE_STATS["hits"], SHARD_CACHE_STATS["misses"],
            RUNNER_CACHE_STATS["hits"], RUNNER_CACHE_STATS["misses"],
            CONVERT_CACHE_STATS["hits"], CONVERT_CACHE_STATS["misses"],
            tuned["hits"], tuned["misses"])


def _cache_delta(snap: Tuple[int, ...]) -> CacheStats:
    now = _cache_snapshot()
    d = [b - a for a, b in zip(snap, now)]
    return CacheStats(plan_hits=d[0], plan_misses=d[1], shard_hits=d[2],
                      shard_misses=d[3], runner_hits=d[4], runner_misses=d[5],
                      convert_hits=d[6], convert_misses=d[7],
                      tuned_hits=d[8], tuned_misses=d[9])


@dataclasses.dataclass
class LoweredKernel:
    """A compiled distributed sparse kernel + its plan artifacts.

    ``fallbacks`` records every operand the lowering engine had to convert
    because no direct kernel exists for its declared format (each entry is
    ``"name: <from> -> <to>"``); an empty list means the cell lowered
    directly. ``declared_formats`` keeps the structured form (operand name
    → declared format key) — the plans hold the CONVERTED tensors, so the
    declared key is only recoverable from here. The conformance matrix
    reports this census.
    """

    stmt: Assignment
    strategy: DistStrategy
    machine: Machine
    plans: Dict[str, TensorPartition]
    shards: Dict[str, ShardedTensor]
    runner: Callable[[], Any]
    comm: CommStats
    leaf_name: str
    fallbacks: List[str] = dataclasses.field(default_factory=list)
    declared_formats: Dict[str, str] = dataclasses.field(default_factory=dict)
    cache: CacheStats = dataclasses.field(default_factory=CacheStats)
    # schedule="auto" provenance: the winning plan_search.SchedulePoint
    # (estimated/measured costs, tile choice), None for hand schedules.
    tuned: Optional[Any] = None

    def run(self):
        return self.runner()

    def cell_id(self) -> str:
        """Conformance-matrix cell ID: ``<expr>/<format>/<strategy>/<mesh>``
        (e.g. ``spmm/dcsr/nnz/4x1``). The format component is the sparse
        operand's DECLARED format — a fallback cell keeps its declared key
        and is distinguished by a non-empty ``fallbacks`` list."""
        name = self._dist_sparse_name()
        key = "dense"
        if name is not None:
            key = self.declared_formats.get(
                name, fmt.format_key(self.plans[name].tensor.format))
        return (f"{expression_key(self.stmt.signature())}/{key}/"
                f"{self.strategy.space_label}/{self.strategy.mesh_label}")

    def imbalance(self) -> float:
        name = self._dist_sparse_name()
        return self.plans[name].imbalance() if name in self.plans else 0.0

    def _dist_sparse_name(self) -> Optional[str]:
        for acc in self.stmt.rhs.accesses():
            if acc.tensor.format.is_sparse:
                return acc.tensor.name
        return None

    def explain(self) -> str:
        """Human-readable plan provenance: what was chosen, what it costs,
        and — for ``schedule="auto"`` lowers — every candidate the
        autoscheduler scored and why this one won."""
        lines = [f"kernel {self.cell_id()}  leaf={self.leaf_name}",
                 f"  schedule: space={self.strategy.space} "
                 f"mesh={self.strategy.mesh_label} "
                 f"pieces={self.strategy.pieces}"]
        if self.fallbacks:
            lines.append("  fallbacks: " + "; ".join(self.fallbacks))
        t = self.tuned
        if t is not None:
            cands = getattr(t, "candidates", None) or []
            lines.append(
                f"  autoscheduler winner: {t.label} "
                f"est={t.est_cost_s:.3e}s"
                + (f" measured={t.measured_s:.3e}s"
                   if t.measured_s is not None else " (not measured)"))
            if cands:
                lines.append(f"  candidates scored: {len(cands)} "
                             "(model cost order; top-K measured)")
                for i, c in enumerate(cands):
                    meas = (f" measured={c['measured_s']:.3e}s"
                            if c.get("measured_s") is not None else "")
                    mark = " <- winner" if c["label"] == t.label else ""
                    err = (f" FAILED {c['error']}" if c.get("error")
                           else "")
                    lines.append(f"    {i + 1:2d}. {c['label']:<28s} "
                                 f"est={c['est_cost_s']:.3e}s{meas}{mark}"
                                 f"{err}")
        else:
            lines.append("  hand-picked schedule (no candidate search ran)")
        comm = self.comm
        if comm.axes:
            per_ax = ", ".join(
                f"{n}: bcast={a.broadcast_bytes} reduce={a.reduce_bytes}"
                for n, a in comm.axes.items())
            lines.append(f"  comm: {per_ax} "
                         f"(net={comm.total_network_bytes()})")
        else:
            lines.append(
                f"  comm: replicate={comm.replicate_bytes} "
                f"reduce={comm.reduce_bytes} "
                f"redistribute={comm.redistribute_bytes} "
                f"(net={comm.total_network_bytes()})")
        cs = self.cache
        lines.append(
            f"  cache: plan {cs.plan_hits}h/{cs.plan_misses}m, "
            f"shard {cs.shard_hits}h/{cs.shard_misses}m, "
            f"runner {cs.runner_hits}h/{cs.runner_misses}m, "
            f"tuned {cs.tuned_hits}h/{cs.tuned_misses}m"
            + (" [warm]" if cs.warm else ""))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _scatter_rows(global_shape, blocks, row_start, row_count):
    """Assemble per-color padded row blocks into the global output (the
    inverse of the row partition; disjoint rows → add == set; overlapping
    rows (nnz strategy) → correct reduction)."""
    P, max_rows = blocks.shape[0], blocks.shape[1]
    out = jnp.zeros(global_shape, dtype=blocks.dtype)
    idx = row_start[:, None] + jnp.arange(max_rows, dtype=row_start.dtype)[None, :]
    mask = jnp.arange(max_rows)[None, :] < row_count[:, None]
    idx = jnp.clip(idx, 0, global_shape[0] - 1)
    flat_blocks = blocks.reshape((P * max_rows,) + blocks.shape[2:])
    flat_idx = idx.reshape(-1)
    flat_mask = mask.reshape(-1)
    mshape = (-1,) + (1,) * (blocks.ndim - 2)
    return out.at[flat_idx].add(flat_blocks * flat_mask.reshape(mshape).astype(blocks.dtype))


def _scatter_vals(total_nnz, val_blocks, nnz_start, nnz_count):
    P, max_nnz = val_blocks.shape
    out = jnp.zeros((total_nnz,), dtype=val_blocks.dtype)
    idx = nnz_start[:, None] + jnp.arange(max_nnz, dtype=nnz_start.dtype)[None, :]
    mask = jnp.arange(max_nnz)[None, :] < nnz_count[:, None]
    idx = jnp.clip(idx, 0, max(total_nnz - 1, 0))
    return out.at[idx.reshape(-1)].add((val_blocks * mask).reshape(-1))


def _nbytes(t: Tensor) -> int:
    if t.format.is_all_dense:
        return int(np.prod(t.shape)) * t.vals.dtype.itemsize
    if t.format.is_blocked:
        # block-granular payload: one (br, bc) tile + one block coord per
        # stored block position, plus the block-grid pos arrays
        tile = int(np.prod(t.format.block_shape)) * t.vals.dtype.itemsize
        n_blocks = int(t.vals.shape[0]) if t.vals.ndim else 0
        n = n_blocks * (tile + 4)
        for ld in t.levels:
            if ld.pos is not None:
                n += ld.pos.nbytes
        return n
    n = t.nnz * (t.vals.dtype.itemsize + 4)  # vals + one crd per level approx
    for ld in t.levels:
        if ld.pos is not None:
            n += ld.pos.nbytes
    return n


def _scatter_block_vals(total_blocks, tile_blocks, nnz_start, nnz_count):
    """Blocked value-region assembly: per-color (br, bc) output tiles into
    the global stored-block axis — ``_scatter_rows`` with the block axis as
    the row dimension."""
    br, bc = tile_blocks.shape[2], tile_blocks.shape[3]
    return _scatter_rows((max(total_blocks, 1), br, bc), tile_blocks,
                         nnz_start, nnz_count)[:total_blocks]


def _scatter_by_val_idx(total, out, val_idx, nnz_count):
    """Permuted value-region assembly: scatter per-color leaf outputs
    (scalar slots or (br, bc) tiles) home by their ``val_idx`` map —
    global storage positions recorded by a permuted (transpose) walk or a
    non-contiguous grid tiling. Padding slots are masked by ``nnz_count``.
    The trace-side twin of executor._assemble_vals."""
    mask = (jnp.arange(out.shape[1])[None, :]
            < nnz_count[:, None]).astype(out.dtype)
    idx = jnp.clip(val_idx, 0, max(total - 1, 0)).reshape(-1)
    m = mask.reshape(mask.shape + (1,) * (out.ndim - 2))
    flat = (out * m).reshape((-1,) + out.shape[2:])
    return jnp.zeros((total,) + out.shape[2:], out.dtype).at[idx].add(flat)


# ---------------------------------------------------------------------------
# Format dispatch: which kernel family handles a signature, and whether it
# supports a sparse operand's format directly (queried from the kernel
# modules themselves — the level-iterator capability contract lives with
# the leaves). Modules are resolved LAZILY: they import
# jax.experimental.pallas at top level, which interpret-only / planning-only
# users of core.lower should not pay for.
# ---------------------------------------------------------------------------

_SIG_KERNEL = {
    "d1(i)=s2(i,j)*d1(j)": ("spmv", "spmv"),
    "d2(i,j)=s2(i,k)*d2(k,j)": ("spmm", "spmm"),
    "s2(i,j)=s2(i,j)+s2(i,j)+s2(i,j)": ("spadd3", "spadd3"),
    "s2(i,j)=s2(i,j)*d2(i,k)*d2(k,j)": ("sddmm", "sddmm"),
    "s2(i,j)=s3(i,j,k)*d1(k)": ("spttv", "spmttkrp"),
    "d2(i,l)=s3(i,j,k)*d2(j,l)*d2(k,l)": ("spmttkrp", "spmttkrp"),
}


def _kernel_supports(module: str):
    import importlib
    return importlib.import_module(f"..kernels.{module}",
                                   package=__package__).supports


def expression_key(sig: str) -> str:
    """Short expression name for conformance cell IDs (``spmm`` in
    ``spmm/dcsr/nnz/4x1``); falls back to the raw signature."""
    entry = _SIG_KERNEL.get(sig)
    return entry[0] if entry else sig


def _normalize_operands(
    stmt: Assignment, space: str,
) -> Tuple[Assignment, List[str], Dict[str, str]]:
    """Format-conversion fallback (logged): every sparse rhs operand whose
    format the selected kernel family cannot iterate directly is converted
    to the canonical target (CSR / CSF). The returned statement is what the
    planner and emitters see; the fallback census (display strings + the
    structured name → declared-key map) is recorded on the LoweredKernel
    and surfaced by the conformance matrix."""
    sig = stmt.signature()
    entry = _SIG_KERNEL.get(sig)
    if entry is None:
        return stmt, [], {}
    kernel_name, module = entry
    supports = _kernel_supports(module)
    mapping: Dict[str, Tensor] = {}
    fallbacks: List[str] = []
    declared: Dict[str, str] = {}
    # Blocked operands of a multi-operand family (spadd3) must share ONE
    # block layout — the tile-union leaves merge tiles positionally. Mixed
    # layouts force the blocked operands through the conversion fallback.
    sparse_ops = {acc.tensor.name: acc.tensor for acc in stmt.rhs.accesses()
                  if acc.tensor.format.is_sparse}
    force_convert: set = set()
    if (len(sparse_ops) > 1
            and any(t.format.is_blocked for t in sparse_ops.values())
            and len({t.format for t in sparse_ops.values()}) > 1):
        force_convert = {name for name, t in sparse_ops.items()
                         if t.format.is_blocked}
    for acc in stmt.rhs.accesses():
        t = acc.tensor
        if not t.format.is_sparse or t.name in mapping:
            continue
        if supports(t.format, space) and t.name not in force_convert:
            continue
        if not isinstance(t, Tensor):   # TensorVar dry-run: nothing to convert
            continue
        target = fmt.conversion_target(t.format)
        declared[t.name] = fmt.format_key(t.format)
        fallbacks.append(
            f"{t.name}: {fmt.format_key(t.format)} -> {fmt.format_key(target)}")
        log.warning(
            "no direct %s/%s kernel for %s stored as %s; converting to %s "
            "(conformance cell falls back)",
            kernel_name, space, t.name, t.format, target)
        mapping[t.name] = convert_tensor_cached(t, target)
    return stmt.with_tensors(mapping), fallbacks, declared


# ---------------------------------------------------------------------------
# The lowering entry point
# ---------------------------------------------------------------------------

def lower(
    stmt: Assignment,
    machine: Machine,
    schedule: Union[Schedule, str, None] = None,
    distributions: Optional[Dict[str, Distribution]] = None,
    jit: bool = True,
    weights: Optional[np.ndarray] = None,
    *,
    elastic: bool = False,
    init_bounds: Optional[np.ndarray] = None,
) -> LoweredKernel:
    """Compile a scheduled TIN statement into a distributed executable.

    ``schedule`` may be a hand-built :class:`Schedule`, ``None`` (the
    default 1-D row schedule), or the string ``"auto"`` — the
    cost-model-driven autoscheduler (:mod:`repro.core.plan_search`)
    enumerates strategy × grid-factorization × tile candidates, scores
    them with structural stats + the per-axis byte formulas, optionally
    refines the top-K by timing, and memoizes the winner in a tuned-plan
    cache keyed by content fingerprints (hits observable as
    ``kernel.cache.tuned_hits``).

    ``distributions`` declares the *data* distribution per tensor (TDN). The
    *computation* distribution comes from the schedule. Where they disagree
    the kernel stays correct but `comm.redistribute_bytes` charges the
    reshuffle (paper §II-D).

    ``weights`` (pieces,) skews the non-zero splits toward faster shards —
    the straggler re-plan (runtime/fault.StragglerMitigator emits them;
    re-lowering with new weights is the re-plan, and the plan/shard/runner
    caches make everything the weights did NOT change near-free). Ignored
    by universe (rows) schedules, whose splits are coordinate-driven.

    ``elastic=True`` routes 1-D materialization through PER-PIECE shard
    caching (partition.materialize_pieces): each color is its own
    SHARD_CACHE entry, so a later :func:`relower` onto a resized machine
    reuses every color whose window the resize left alone. The stacked
    arrays are bit-for-bit the whole-set materializers' output (runners
    are shared); the cost is per-color cache keys, so the default path
    keeps its one-entry-per-tensor accounting. ``init_bounds`` (pieces, 2)
    overrides the initial equal split — the elastic-resize entry point
    feeds merged survivor windows here (see relower)."""
    with fingerprint_memo(), telemetry.span(
            "lower", sig=stmt.signature()) as sp:
        k = _lower_impl(stmt, machine, schedule, distributions, jit,
                        weights, elastic=elastic, init_bounds=init_bounds)
        sp.set(cell=k.cell_id(), leaf=k.leaf_name,
               pieces=k.strategy.pieces, warm=k.cache.warm)
        _record_lower_metrics(k)
        return k


def _record_lower_metrics(k: "LoweredKernel") -> None:
    """Fold one lower's cache delta and communication ledger into the
    process metrics registry (+ a trace instant with the cache delta)."""
    cs = k.cache
    for field, v in (("plan", cs.plan_hits), ("shard", cs.shard_hits),
                     ("runner", cs.runner_hits), ("convert", cs.convert_hits),
                     ("tuned", cs.tuned_hits)):
        if v:
            telemetry.METRICS.counter(f"lower.cache.{field}.hits", v)
    for field, v in (("plan", cs.plan_misses), ("shard", cs.shard_misses),
                     ("runner", cs.runner_misses),
                     ("convert", cs.convert_misses),
                     ("tuned", cs.tuned_misses)):
        if v:
            telemetry.METRICS.counter(f"lower.cache.{field}.misses", v)
    telemetry.METRICS.counter("lower.count")
    if k.cache.warm:
        telemetry.METRICS.counter("lower.warm_count")
    comm = k.comm
    if comm.axes:
        for name, ax in comm.axes.items():
            telemetry.METRICS.counter(f"comm.axis.{name}.broadcast_bytes",
                                      ax.broadcast_bytes)
            telemetry.METRICS.counter(f"comm.axis.{name}.reduce_bytes",
                                      ax.reduce_bytes)
    else:
        telemetry.METRICS.counter("comm.replicate_bytes",
                                  comm.replicate_bytes)
        telemetry.METRICS.counter("comm.reduce_bytes", comm.reduce_bytes)
    telemetry.METRICS.counter("comm.network_bytes",
                              comm.total_network_bytes())
    telemetry.instant("lower.cache", **cs.as_dict())


def _lower_impl(stmt, machine, schedule, distributions, jit, weights,
                elastic=False, init_bounds=None):
    snap = _cache_snapshot()
    tuned_point = None
    if isinstance(schedule, str):
        if schedule != "auto":
            raise ValueError(
                f"unknown schedule string {schedule!r}; pass a Schedule, "
                "None, or 'auto'")
        from . import plan_search
        schedule, machine, tuned_point = plan_search.resolve_auto(
            stmt, machine, weights=weights, jit=jit)
    if schedule is None:
        schedule = default_row_schedule(stmt, machine)
    strat = schedule.strategy()
    pieces = strat.pieces
    sig = stmt.signature()

    # Format dispatch: convert operands with no direct kernel (logged).
    stmt, fallbacks, declared_formats = _normalize_operands(stmt, strat.space)

    # Multi-axis (grid) universe schedules route to the grid subsystem:
    # cross-product tile plans, per-axis communication, SUMMA-style
    # emitters. Grid NON-ZERO schedules fall through — a nested pos-split
    # canonicalizes to the flat equal split of the fused position space
    # (pieces = P*Q), so the 1-D nnz machinery lowers them bit-for-bit
    # identically; only the communication attribution (below) and the SPMD
    # mesh shape differ.
    if strat.is_grid and strat.space == "universe":
        from . import grid as grid_mod
        k = grid_mod.lower_grid(stmt, machine, strat, jit=jit,
                                fallbacks=fallbacks,
                                declared_formats=declared_formats,
                                snap=snap, distributions=distributions)
        k.tuned = tuned_point
        return k

    out_t: Tensor = stmt.lhs.tensor
    shards: Dict[str, ShardedTensor] = {}
    comm = CommStats(pieces=pieces)

    # ---- Step 1 & 2 of Fig. 9a: initial + derived partitions --------------
    # Memoized on (signature, strategy, operand fingerprints, weights): an
    # unchanged schedule over unchanged operands skips partitioning.
    plan_span = telemetry.span("lower.plan", sig=sig, space=strat.space,
                               pieces=pieces)
    plan_span.__enter__()
    plan_key = _plan_cache_key(stmt, strat, weights, init_bounds)
    plans = _PLAN_CACHE.get(plan_key) if plan_key is not None else None
    telemetry.instant("lower.plan.cache",
                      hit=plans is not None, memoizable=plan_key is not None)
    if plans is not None:
        # Rebind each memoized plan to the CURRENT statement's tensor
        # objects: the cached plans pin the objects from the lower that
        # populated them, and the key only proves the current tensors'
        # content — a pinned object may have been mutated in place since.
        current: Dict[str, Tensor] = {}
        for acc in stmt.accesses():
            current.setdefault(acc.tensor.name, acc.tensor)
        plans = {name: dataclasses.replace(p, tensor=current[name])
                 for name, p in plans.items()}
    else:
        plans = _compute_plans(stmt, strat, out_t, weights, init_bounds)
        if plan_key is not None:
            # Stored without tensor refs: the cache holds only the small
            # bounds arrays instead of pinning O(nnz) storage of up to
            # `capacity` statements; hits rebind (above) by name, and
            # every plan name is an access name by construction.
            _PLAN_CACHE.put(plan_key, {
                name: dataclasses.replace(p, tensor=None)
                for name, p in plans.items()})
    plan_span.__exit__(None, None, None)

    # ---- materialize -------------------------------------------------------
    mat_span = telemetry.span("lower.materialize", sig=sig, pieces=pieces)
    mat_span.__enter__()
    if (sig, strat.space) in _SELF_MATERIALIZING:
        # spadd3/nnz: the emitter consumes equal (or straggler-weighted)
        # chunks of the CONCATENATED stored-entry stream, packed by the
        # materialization layer (cached — a weighted re-plan re-slices the
        # cached stream). Comm = every chunk's union ships to the root for
        # the cross-chunk merge — coords+vals per entry, a whole (br, bc)
        # tile per entry for blocked operands.
        add_tensors, seen = [], set()
        for acc in stmt.rhs.accesses():
            t = acc.tensor
            if t.format.is_sparse and t.name not in seen:
                seen.add(t.name)
                add_tensors.append(t)
        shards["_addstream"] = materialize_add_stream(add_tensors, pieces,
                                                      weights)
        n_entries = shards["_addstream"].meta["n_entries"]
        if add_tensors and add_tensors[0].format.is_blocked:
            tile = int(np.prod(add_tensors[0].format.block_shape))
            comm.reduce_bytes += n_entries * (8 + tile * 4)
        else:
            comm.reduce_bytes += n_entries * 12
    for name, plan in plans.items():
        t = plan.tensor
        if (sig, strat.space) in _SELF_MATERIALIZING:
            continue  # the emitter packs its own chunks (spadd3/nnz)
        if name == out_t.name and _output_is_assembled(sig):
            continue  # outputs assembled from leaf results, not materialized
        if plan.replicated:
            shards[name] = (materialize_replicated_elastic(t, pieces)
                            if elastic else materialize_replicated(t, pieces))
            comm.replicate_bytes += _nbytes(t)
        elif strat.space == "nnz" and t.format.is_sparse:
            kind = "bcsr_nnz" if t.format.is_blocked else "coo_nnz"
            shards[name] = (materialize_pieces(kind, t, plan) if elastic
                            else (materialize_bcsr_nnz(t, plan)
                                  if t.format.is_blocked
                                  else materialize_coo_nnz(t, plan)))
        elif (t.format.is_sparse and not t.format.is_blocked
                and t.order >= 3 and t.format.levels[1].singleton):
            # trailing-singleton trees (COO3) have no grouped middle level:
            # the universe row plan materializes the FLAT walk (coordinate
            # columns bucketed by row window) and the flat leaves consume it
            shards[name] = (materialize_pieces("coo_nnz", t, plan) if elastic
                            else materialize_coo_nnz(t, plan))
        elif t.format.is_all_dense:
            shards[name] = (
                materialize_dense_rows_pieces(t, plan.root_coord_bounds)
                if elastic
                else materialize_dense_rows(t, plan.root_coord_bounds))
        elif t.format.is_blocked:
            shards[name] = (materialize_pieces("bcsr_rows", t, plan)
                            if elastic else materialize_bcsr_rows(t, plan))
        else:
            shards[name] = (materialize_pieces("csr_rows", t, plan)
                            if elastic else materialize_csr_rows(t, plan))

    # data-vs-computation distribution mismatch cost (C4)
    if distributions:
        for name, d in distributions.items():
            want = plans.get(name)
            if want is None or want.replicated:
                continue
            have = d.plan(plans[name].tensor)
            if not _plans_equal(want, have):
                comm.redistribute_bytes += _nbytes(plans[name].tensor)

    if strat.space == "nnz" and (sig, strat.space) not in _SELF_MATERIALIZING:
        ov = plans[next(iter(plans))]  # position tensor plan
        if ov.tensor.format.dim_of_level(0) != 0:
            # storage root doesn't track output rows (CSC, BCSC): every
            # color reduces a FULL-extent output partial (see
            # _nnz_row_windows / _bcsr_nnz_windows). reduce_bytes is the
            # per-reduction payload; total_network_bytes multiplies by
            # (pieces-1).
            comm.reduce_bytes += _nbytes(out_t)
        elif ov.tensor.format.is_blocked:
            # overlapping BLOCK-rows reduce across colors; the payload per
            # overlapped block-row is its br-row output stripe
            bb = ov.levels[0].coord_bounds
            br = ov.tensor.format.block_shape[0]
            comm.reduce_bytes += int(
                (bb[:, 1] - bb[:, 0]).sum()
                - (bb[:, 1].max() - bb[:, 0].min())
            ) * br * 4
        else:
            # overlapping output rows reduced across colors
            comm.reduce_bytes += int(
                (ov.root_coord_bounds[:, 1] - ov.root_coord_bounds[:, 0]).sum()
                - (ov.root_coord_bounds[:, 1].max()
                   - ov.root_coord_bounds[:, 0].min())
            ) * 4

    # Grid nnz schedules: re-attribute the flat replicate/reduce payload to
    # the machine axes under the hierarchical collective model (broadcast:
    # along x once, then along y within each of the P grid rows; reduce in
    # reverse) — totals are unchanged (b*(PQ-1)), the per-axis ledger is
    # what the comm-volume benches and the SPMD psum scoping read.
    if strat.is_grid:
        m = 1
        axes = {}
        for d in strat.machine_dims:
            axes[d.name] = AxisComm(size=d.size,
                                    broadcast_bytes=m * comm.replicate_bytes,
                                    reduce_bytes=m * comm.reduce_bytes)
            m *= d.size
        comm.axes = axes
        comm.replicate_bytes = 0
        comm.reduce_bytes = 0
    mat_span.__exit__(None, None, None)

    # ---- emit: pick leaf + build runner ------------------------------------
    with telemetry.span("lower.emit", sig=sig, space=strat.space) as esp:
        leaf_name, runner = _emit(stmt, strat, plans, shards, jit=jit)
        esp.set(leaf=leaf_name)
    return LoweredKernel(
        stmt=stmt, strategy=strat, machine=machine, plans=plans,
        shards=shards, runner=runner, comm=comm, leaf_name=leaf_name,
        fallbacks=fallbacks, declared_formats=declared_formats,
        cache=_cache_delta(snap), tuned=tuned_point,
    )


def _plan_cache_key(stmt: Assignment, strat: DistStrategy,
                    weights: Optional[np.ndarray],
                    init_bounds: Optional[np.ndarray] = None,
                    ) -> Optional[Tuple]:
    """Memoization key for the partitioning step: signature + strategy +
    per-operand content fingerprints (+ straggler weights + elastic
    init-bounds override). None disables caching (dry-run TensorVar
    operands have no storage to fingerprint)."""
    ops = []
    for acc in stmt.accesses():
        t = acc.tensor
        if not isinstance(t, Tensor):
            return None
        ops.append((t.name, tensor_fingerprint(t),
                    tuple(v.name for v in acc.idx)))
    from .partition import _crc_arrays
    init_crc = (None if init_bounds is None
                else _crc_arrays(0, np.asarray(init_bounds, dtype=np.int64)))
    return (stmt.signature(), strat.space,
            tuple(v.name for v in strat.vars),
            tuple(d.size for d in strat.machine_dims),
            tuple(strat.replicate),
            weights_fingerprint(weights), init_crc, tuple(ops))


def _compute_plans(stmt: Assignment, strat: DistStrategy, out_t: Tensor,
                   weights: Optional[np.ndarray],
                   init_bounds: Optional[np.ndarray] = None,
                   ) -> Dict[str, TensorPartition]:
    """Fig. 9a steps 1 & 2: initial + derived coordinate-tree partitions.

    ``init_bounds`` replaces the equal initial split (universe: root
    coordinate windows; nnz: split-level position windows) with
    caller-supplied windows — relower's migration bounds, already
    block-aligned because they come from a previous plan of the same
    operands."""
    plans: Dict[str, TensorPartition] = {}
    pieces = strat.pieces
    sig = stmt.signature()
    dist_var = strat.var
    if strat.space == "universe":
        # coordinate-value loop -> createInitialUniversePartitions
        n = stmt.var_extent(dist_var)
        if init_bounds is not None:
            bounds = np.asarray(init_bounds, dtype=np.int64)
        else:
            bounds = partition_by_bounds(n, pieces)
            # A blocked operand distributed on its row dimension snaps the
            # universe split to block-row boundaries so EVERY co-partitioned
            # tensor (dense row operands, the output) shares the same
            # per-color row windows — whichever level stores the rows (BCSR
            # and BCSC).
            for acc in stmt.rhs.accesses():
                t = acc.tensor
                if (t.format.is_sparse and t.format.is_blocked
                        and dist_var in acc.idx
                        and acc.idx.index(dist_var) == 0):
                    bounds = block_aligned_row_bounds(
                        n, pieces, t.format.block_shape[0])
                    break
        for acc in stmt.accesses():
            t = acc.tensor
            if t.name in plans:
                continue
            if dist_var in acc.idx:
                lvl_dim = acc.idx.index(dist_var)
                if t.format.level_of_dim(lvl_dim) == 0:
                    # distributed dim at the storage root: the image chain
                    plans[t.name] = partition_tensor_rows(t, bounds)
                    continue
                if lvl_dim == 0 and t.format.is_sparse:
                    # column-major root (CSC/BCSC): the transpose walk
                    # realizes the same row windows (partition routes it)
                    plans[t.name] = partition_tensor_rows(t, bounds)
                    continue
            # not indexed by the distributed var at the root -> communicate
            # fetches the whole tensor per color (replication)
            plans[t.name] = replicate_tensor(t, pieces)
    elif (sig, strat.space) in _SELF_MATERIALIZING:
        # spadd3/nnz: plan each operand's equal nnz split (imbalance ~0 by
        # construction); the packed chunk shards come from the
        # materialization layer at materialize time.
        for acc in stmt.rhs.accesses():
            t = acc.tensor
            if t.name in plans:
                continue
            if t.format.is_sparse:
                plans[t.name] = partition_tensor_nonzeros(t, pieces)
            else:
                plans[t.name] = replicate_tensor(t, pieces)
    else:
        # coordinate-position loop -> createInitialNonZeroPartition of the
        # position-space (sparse) tensor, then partition the remaining
        # coordinate trees from its derived root partition.
        pos_tensor = None
        for acc in stmt.rhs.accesses():
            if acc.tensor.format.is_sparse:
                pos_tensor = acc.tensor
                break
        if pos_tensor is None:
            raise ValueError("nnz schedule requires a sparse rhs tensor")
        p = partition_tensor_nonzeros(pos_tensor, pieces, weights,
                                      init_bounds=init_bounds)
        plans[pos_tensor.name] = p
        root_bounds = p.root_coord_bounds
        for acc in stmt.accesses():
            t = acc.tensor
            if t.name in plans:
                continue
            if (t is out_t and not t.format.is_sparse
                    and stmt.lhs.idx
                    and stmt.lhs.idx[0] == pos_tensor_root_var(stmt, pos_tensor)):
                plans[t.name] = partition_tensor_rows(t, root_bounds)
            else:
                plans[t.name] = replicate_tensor(t, pieces)
    return plans


def pos_tensor_root_var(stmt: Assignment, pos_tensor: Tensor) -> IndexVar:
    """The index variable iterated at the tensor's STORAGE root level (for
    CSC that is the column variable — non-zero partitions then own column
    windows, and output-row locality is gone)."""
    for acc in stmt.rhs.accesses():
        if acc.tensor is pos_tensor:
            return acc.idx[pos_tensor.format.dim_of_level(0)]
    raise KeyError(pos_tensor.name)


def _output_is_assembled(sig: str) -> bool:
    # sparse outputs (sddmm, spttv, spadd3) are assembled from leaf results
    return sig.startswith("s")


# (sig, space) pairs whose emitter packs its own shard chunks at emit time
# (no per-tensor materialization wanted; see _emit_spadd3_nnz).
_SELF_MATERIALIZING = {
    ("s2(i,j)=s2(i,j)+s2(i,j)+s2(i,j)", "nnz"),
}


def _plans_equal(a: TensorPartition, b: TensorPartition) -> bool:
    if a.replicated != b.replicated:
        return False
    if (a.vals_bounds is None) != (b.vals_bounds is None):
        return False
    if a.vals_bounds is not None and not np.array_equal(a.vals_bounds, b.vals_bounds):
        return False
    if (a.root_coord_bounds is None) != (b.root_coord_bounds is None):
        return False
    if a.root_coord_bounds is not None and \
            not np.array_equal(a.root_coord_bounds, b.root_coord_bounds):
        return False
    return True


def default_row_schedule(stmt: Assignment, machine: Machine) -> Schedule:
    """The paper's Fig. 1 schedule generalized: divide the first result
    variable over the machine's first dimension, distribute, communicate."""
    i = stmt.result_vars[0]
    io, ii = IndexVar(f"{i.name}o"), IndexVar(f"{i.name}i")
    s = Schedule(stmt, machine)
    s.divide(i, io, ii, machine.dims[0]).distribute(io)
    s.communicate(stmt.tensors(), io)
    return s


def default_nnz_schedule(stmt: Assignment, machine: Machine) -> Schedule:
    """Fuse all sparse loops and split non-zeros evenly (paper §II-D)."""
    spa = stmt.sparse_accesses()[0]
    s = Schedule(stmt, machine)
    vs = list(spa.idx)
    f = vs[0]
    for v in vs[1:]:
        nf = IndexVar(f"{f.name}{v.name}")
        s.fuse(f, v, nf)
        f = nf
    fo, fi = IndexVar(f"{f.name}o"), IndexVar(f"{f.name}i")
    s.pos_split(f, fo, fi, machine.dims[0]).distribute(fo)
    s.communicate(stmt.tensors(), fo)
    return s


def default_grid_schedule(stmt: Assignment, machine: Machine) -> Schedule:
    """2-D universe schedule — the paper's ``distribute((i, k) → (x, y))``:
    divide the sparse operand's two index variables over the machine's two
    dimensions and distribute both, tiling the operand onto the processor
    grid (SUMMA-style for SpMM/SpMV, owner-computes tiles for SDDMM)."""
    spa = stmt.sparse_accesses()[0]
    if len(spa.idx) < 2 or len(machine.dims) < 2:
        raise ValueError("grid schedule needs a 2-D sparse operand and a "
                         "2-D machine")
    i, k2 = spa.idx[0], spa.idx[1]
    io, ii = IndexVar(f"{i.name}o"), IndexVar(f"{i.name}i")
    ko, ki = IndexVar(f"{k2.name}o"), IndexVar(f"{k2.name}i")
    s = Schedule(stmt, machine)
    s.divide(i, io, ii, machine.dims[0])
    s.divide(k2, ko, ki, machine.dims[1])
    s.distribute(io, ko)
    s.communicate(stmt.tensors(), io)
    return s


def default_grid_nnz_schedule(stmt: Assignment, machine: Machine) -> Schedule:
    """2-D non-zero schedule: fuse the sparse loops, then NEST the position
    split over both machine dimensions — color (p, q) owns block p*Q+q of
    the fused non-zero stream (canonically equal to the flat P*Q split, so
    2-D nnz cells are bit-for-bit their Px1 counterparts)."""
    if len(machine.dims) < 2:
        raise ValueError("grid nnz schedule needs a 2-D machine")
    spa = stmt.sparse_accesses()[0]
    s = Schedule(stmt, machine)
    vs = list(spa.idx)
    f = vs[0]
    for v in vs[1:]:
        nf = IndexVar(f"{f.name}{v.name}")
        s.fuse(f, v, nf)
        f = nf
    outers = []
    cur = f
    for d in machine.dims:
        co, ci = IndexVar(f"{cur.name}o"), IndexVar(f"{cur.name}i")
        s.pos_split(cur, co, ci, d)
        outers.append(co)
        cur = ci
    s.distribute(*outers)
    s.communicate(stmt.tensors(), outers[0])
    return s


def default_grid3_schedule(stmt: Assignment, machine: Machine) -> Schedule:
    """3-D universe schedule over an order-3 machine grid. An order-3
    sparse operand maps its three index variables onto the three machine
    dimensions (P×Q×R COO bricks); an order-2 operand nests a second
    divide of its column variable so the grid reads ``i → x, j → (y, z)``
    (the joint Q·R column split used by spadd3)."""
    if len(machine.dims) < 3:
        raise ValueError("grid3 schedule needs a 3-D machine")
    spa = stmt.sparse_accesses()[0]
    s = Schedule(stmt, machine)
    if len(spa.idx) >= 3:
        outers = []
        for v, d in zip(spa.idx[:3], machine.dims[:3]):
            vo, vi = IndexVar(f"{v.name}o"), IndexVar(f"{v.name}i")
            s.divide(v, vo, vi, d)
            outers.append(vo)
        s.distribute(*outers)
        s.communicate(stmt.tensors(), outers[0])
        return s
    i, j = spa.idx[0], spa.idx[1]
    io, ii = IndexVar(f"{i.name}o"), IndexVar(f"{i.name}i")
    jo, ji = IndexVar(f"{j.name}o"), IndexVar(f"{j.name}i")
    jio, jii = IndexVar(f"{ji.name}o"), IndexVar(f"{ji.name}i")
    s.divide(i, io, ii, machine.dims[0])
    s.divide(j, jo, ji, machine.dims[1])
    s.divide(ji, jio, jii, machine.dims[2])
    s.distribute(io, jo, jio)
    s.communicate(stmt.tensors(), io)
    return s


def default_replicated_schedule(stmt: Assignment, machine: Machine) -> Schedule:
    """2.5-D communication-avoiding schedule: tile the sparse operand over
    the first two machine dimensions (as the 2-D grid schedule does) and
    split the remaining dense loop variable over the third, replicating
    the sparse operand along it — each z-layer computes a disjoint slab of
    the dense contraction, so the cross-grid reduction shrinks from a
    (Q·R−1)-hop all-reduce to (Q−1) hops at the cost of broadcasting the
    sparse operand R−1 extra times."""
    if len(machine.dims) < 3:
        raise ValueError("replicated schedule needs a 3-D machine")
    spa = stmt.sparse_accesses()[0]
    v0, v1 = spa.idx[0], spa.idx[1]
    rest = [v for v in stmt.all_vars if v not in spa.idx]
    if not rest:
        raise ValueError("replicated schedule needs a loop variable outside "
                         "the sparse operand's index set")
    v2 = rest[0]
    s = Schedule(stmt, machine)
    outers = []
    for v, d in zip((v0, v1, v2), machine.dims[:3]):
        vo, vi = IndexVar(f"{v.name}o"), IndexVar(f"{v.name}i")
        s.divide(v, vo, vi, d)
        outers.append(vo)
    s.distribute(*outers)
    s.replicate([spa.tensor], machine.dims[2])
    s.communicate(stmt.tensors(), outers[0])
    return s


# ---------------------------------------------------------------------------
# Elastic re-plan: mesh-as-data. A Schedule traces against ONE machine, but
# the STRATEGY it canonicalizes to is plain data (space, grid rank,
# replication, tile) — so moving a lowered kernel to a different machine is
# a pure function of (strategy, new machine), not a re-trace of user
# schedule code. relower() is the elastic entry point: rebuild the
# schedule family on the new machine, derive migration-friendly initial
# bounds, and re-lower with per-piece shard caching so everything the
# resize did not touch is a cache hit.
# ---------------------------------------------------------------------------


def rebuild_schedule(stmt: Assignment, machine: Machine,
                     strat: DistStrategy) -> Schedule:
    """Re-instantiate ``strat``'s schedule family against a NEW machine —
    the same reconstruction the autoscheduler's SchedulePoint.build uses
    (core/plan_search.py), driven here by an existing strategy instead of
    a search candidate."""
    nd = len(machine.dims)
    if strat.replicate and nd >= 3:
        s = default_replicated_schedule(stmt, machine)
    elif nd >= 3:
        s = default_grid3_schedule(stmt, machine)
    elif nd == 2:
        s = (default_grid_schedule(stmt, machine)
             if strat.space == "universe"
             else default_grid_nnz_schedule(stmt, machine))
    elif strat.space == "universe":
        s = default_row_schedule(stmt, machine)
    else:
        s = default_nnz_schedule(stmt, machine)
    if strat.tile is not None:
        s.tile_hint(*strat.tile)
    return s


def _elastic_init_bounds(kernel: LoweredKernel) -> Optional[np.ndarray]:
    """The initial split the kernel's plans were derived from: universe →
    the (block-aligned) root row windows; nnz → the position tensor's
    split-level windows (== vals_bounds under full fusion / block split).
    None when no migration-style reuse applies (grids, spadd3/nnz whose
    per-operand splits are independent)."""
    strat = kernel.strategy
    if strat.is_grid:
        return None
    if (kernel.stmt.signature(), strat.space) in _SELF_MATERIALIZING:
        return None
    if strat.space == "universe":
        for p in kernel.plans.values():
            if not p.replicated and p.root_coord_bounds is not None:
                return np.asarray(p.root_coord_bounds, dtype=np.int64)
        return None
    for acc in kernel.stmt.rhs.accesses():
        if acc.tensor.format.is_sparse:
            p = kernel.plans.get(acc.tensor.name)
            if p is not None and p.vals_bounds is not None:
                return np.asarray(p.vals_bounds, dtype=np.int64)
            return None
    return None


def relower(kernel: LoweredKernel, new_machine: Machine, *,
            dead: Optional[int] = None,
            weights: Optional[np.ndarray] = None,
            jit: bool = True) -> LoweredKernel:
    """Re-plan a lowered kernel for a DIFFERENT machine — shrunk, grown,
    or re-factorized — reusing every cache entry the resize leaves valid.

    ``dead`` names the lost piece for a P→P−1 shrink: its window is merged
    into a neighbor (partition.elastic_row_bounds) instead of re-splitting
    equally, so P−2 of the surviving windows — and their per-piece shard
    cache entries, seeded by a previous ``lower(..., elastic=True)`` — are
    bitwise unchanged. Reuse is observable as ``kernel.cache.shard_reuse``
    (≥ 50% asserted in tests/bench for row-split resizes). Without
    ``dead`` (or for grids / weighted re-plans) the new machine gets a
    fresh equal split; replicated operands still hit regardless.

    ``weights`` forwards to the straggler re-plan path — e.g.
    ``relower(kernel, kernel.machine, weights=w)`` re-balances in place
    on the SAME machine."""
    stmt = kernel.stmt
    old = kernel.strategy
    schedule = rebuild_schedule(stmt, new_machine, old)
    new_strat = schedule.strategy()
    init = None
    if (dead is not None and weights is None
            and not old.is_grid and not new_strat.is_grid
            and new_strat.space == old.space
            and new_strat.pieces == old.pieces - 1):
        ob = _elastic_init_bounds(kernel)
        if ob is not None and ob.shape[0] == old.pieces:
            init = elastic_row_bounds(ob, dead)
    return lower(stmt, new_machine, schedule=schedule, jit=jit,
                 weights=weights, elastic=True, init_bounds=init)


# ---------------------------------------------------------------------------
# Leaf emission — ONE format-generic emitter per expression × strategy,
# parameterized by the operands' LEVEL TREES (core/levels.py). An emitter
# never asks "which format?"; it asks the tree which walk the shards were
# materialized from — blocked (tile leaves), grouped (pos/crd leaves), flat
# trailing-singleton (coordinate-column leaves) — and whether the walk was
# permuted (``val_idx`` scatter maps from the transpose walk). Every
# emitter returns ``(leaf_name, runner)``; the leaf name records the
# selected leaf family and is the SPMD builder dispatch key
# (distributed/executor.py SPMD_BUILDERS).
# ---------------------------------------------------------------------------

def _emit(stmt, strat, plans, shards, jit=True) -> Tuple[str, Callable]:
    sig = stmt.signature()
    emitter = _EMITTERS.get((sig, strat.space))
    if emitter is None:
        return (f"generic[{sig}|{strat.space}]",
                _emit_generic_fallback(stmt, strat, plans, shards, jit=jit))
    return emitter(stmt, strat, plans, shards, jit=jit)


def _runner(jit, name, static, arrays, build):
    """Compiled-runner cache front-end used by every emitter.

    ``build()`` returns the raw compute fn; all per-lower DATA must flow
    through its arguments (``arrays`` is the argument prototype used for the
    shapes/dtypes key component) and every Python constant baked into the
    trace must be listed in ``static``. On a key match the previously
    jitted callable is returned, so jax's compilation cache hits instead of
    re-tracing — this is what makes a warm re-lower skip compilation."""
    if not jit:
        return build()
    key = (name, tuple(static), avals_key(arrays))

    def _jit_build():
        with telemetry.span("lower.jit", leaf=name):
            return jax.jit(build())

    return _RUNNER_CACHE.get_or_build(key, _jit_build)


def _nnz_row_windows(B: ShardedTensor, n: int):
    """Row-window parameters for a flat (coordinate-column) shard set.
    When the storage root tracks output rows — row-major nnz splits AND
    universe flat walks, whose windows are then disjoint — leaves compute
    into the shard's root window; otherwise (CSC) every shard computes a
    full-extent partial and the scatter reduces the overlap."""
    a = B.arrays
    if B.meta.get("root_dim", 0) == 0 and B.meta["max_rows"] > 0:
        return a["row_start"], a["row_count"], int(B.meta["max_rows"])
    pieces = B.pieces
    row_start = jnp.zeros((pieces,), dtype=jnp.int32)
    row_count = jnp.full((pieces,), n, dtype=jnp.int32)
    return row_start, row_count, int(n)


def _bcsr_nnz_windows(B: ShardedTensor):
    """Block-row window parameters for a blocked nnz shard set. Column-
    major roots (BCSC — the root tracks block-columns) and empty shard
    sets fall back to full-grid windows, so leaves reduce over the whole
    block grid and clip bounds / segment counts stay positive."""
    a = B.arrays
    max_brows = int(B.meta["max_brows"])
    if B.meta.get("root_dim", 0) == 0 and max_brows > 0:
        return a["brow_start"], a["row_start"], a["row_count"], max_brows
    pieces = B.pieces
    n = int(B.meta["n_rows"])
    brow_start = jnp.zeros((pieces,), dtype=jnp.int32)
    row_start = jnp.zeros((pieces,), dtype=jnp.int32)
    row_count = jnp.full((pieces,), n, dtype=jnp.int32)
    return brow_start, row_start, row_count, max(int(B.meta["grid_rows"]), 1)


# -- SpMV -------------------------------------------------------------------

def _emit_spmv_rows(stmt, strat, plans, shards, jit=True):
    Bt = stmt.rhs.accesses()[0].tensor
    B = shards[Bt.name]
    c = shards[stmt.rhs.accesses()[1].tensor.name]
    n = stmt.lhs.tensor.shape[0]
    a = B.arrays
    if levels.tree_of(Bt).blocked:
        c_blk = pack_vec_blocks(np.asarray(c.arrays["vals"]),
                                int(B.meta["grid_cols"]), int(B.meta["bc"]))

        def fn(pos, crd, tiles, cb, row_start, row_count):
            blocks = jax.vmap(K.leaf_bcsr_spmv_rows,
                              in_axes=(0, 0, 0, None))(
                pos, crd, tiles, cb)                 # (P, max_brows * br)
            return _scatter_rows((n,), blocks, row_start, row_count)

        args = (a["pos1"], a["crd1"], a["vals"], c_blk,
                a["row_start"], a["row_count"])
        f = _runner(jit, "bcsr_spmv_rows", (n,), args, lambda: fn)
        return "bcsr_spmv_rows", lambda: np.asarray(f(*args))

    cv = c.arrays["vals"]

    def fn(pos, crd, vals, cvec, row_start, row_count):
        blocks = jax.vmap(K.leaf_spmv_rows, in_axes=(0, 0, 0, None))(
            pos, crd, vals, cvec)
        return _scatter_rows((n,), blocks, row_start, row_count)

    args = (a["pos1"], a["crd1"], a["vals"], cv,
            a["row_start"], a["row_count"])
    f = _runner(jit, "spmv_rows", (n,), args, lambda: fn)
    return "spmv_rows", lambda: np.asarray(f(*args))


def _emit_spmv_nnz(stmt, strat, plans, shards, jit=True):
    Bt = stmt.rhs.accesses()[0].tensor
    B = shards[Bt.name]
    c = shards[stmt.rhs.accesses()[1].tensor.name]
    n = stmt.lhs.tensor.shape[0]
    a = B.arrays
    if levels.tree_of(Bt).blocked:
        brow_start, row_start, row_count, max_brows = _bcsr_nnz_windows(B)
        c_blk = pack_vec_blocks(np.asarray(c.arrays["vals"]),
                                int(B.meta["grid_cols"]), int(B.meta["bc"]))

        def fn(bd0, bd1, tiles, cb, brow_start, row_start, row_count):
            rl = jnp.clip(bd0 - brow_start[:, None], 0, max_brows - 1)
            blocks = jax.vmap(
                K.leaf_bcsr_spmv_nnz, in_axes=(0, 0, 0, None, None))(
                rl, bd1, tiles, cb, max_brows)       # (P, max_brows * br)
            return _scatter_rows((n,), blocks, row_start, row_count)

        args = (a["bdim0"], a["bdim1"], a["vals"], c_blk,
                brow_start, row_start, row_count)
        f = _runner(jit, "bcsr_spmv_nnz", (n, max_brows), args, lambda: fn)
        return "bcsr_spmv_nnz", lambda: np.asarray(f(*args))

    row_start, row_count, max_rows = _nnz_row_windows(B, n)
    cv = c.arrays["vals"]

    def fn(rows, cols, vals, cvec, row_start, row_count):
        rl = jnp.clip(rows - row_start[:, None], 0, max_rows - 1)
        blocks = jax.vmap(K.leaf_spmv_nnz, in_axes=(0, 0, 0, None, None))(
            rl, cols, vals, cvec, max_rows)
        return _scatter_rows((n,), blocks, row_start, row_count)

    args = (a["dim0"], a["dim1"], a["vals"], cv, row_start, row_count)
    f = _runner(jit, "spmv_nnz", (n, max_rows), args, lambda: fn)
    return "spmv_nnz", lambda: np.asarray(f(*args))


# -- SpMM -------------------------------------------------------------------

def _emit_spmm_rows(stmt, strat, plans, shards, jit=True):
    Bacc, Cacc = stmt.rhs.accesses()
    B, C = shards[Bacc.tensor.name], shards[Cacc.tensor.name]
    out_shape = stmt.lhs.tensor.shape
    a = B.arrays
    if levels.tree_of(Bacc.tensor).blocked:
        C_blk = pack_mat_row_blocks(np.asarray(C.arrays["vals"]),
                                    int(B.meta["grid_cols"]),
                                    int(B.meta["bc"]))

        def fn(pos, crd, tiles, Cb, row_start, row_count):
            blocks = jax.vmap(K.leaf_bcsr_spmm_rows,
                              in_axes=(0, 0, 0, None))(
                pos, crd, tiles, Cb)                 # (P, max_brows*br, J)
            return _scatter_rows(out_shape, blocks, row_start, row_count)

        args = (a["pos1"], a["crd1"], a["vals"], C_blk,
                a["row_start"], a["row_count"])
        f = _runner(jit, "bcsr_spmm_rows", out_shape, args, lambda: fn)
        return "bcsr_spmm_rows", lambda: np.asarray(f(*args))

    Cv = C.arrays["vals"]

    def fn(pos, crd, vals, Cmat, row_start, row_count):
        blocks = jax.vmap(K.leaf_spmm_rows, in_axes=(0, 0, 0, None))(
            pos, crd, vals, Cmat)
        return _scatter_rows(out_shape, blocks, row_start, row_count)

    args = (a["pos1"], a["crd1"], a["vals"], Cv,
            a["row_start"], a["row_count"])
    f = _runner(jit, "spmm_rows", out_shape, args, lambda: fn)
    return "spmm_rows", lambda: np.asarray(f(*args))


def _emit_spmm_nnz(stmt, strat, plans, shards, jit=True):
    Bacc, Cacc = stmt.rhs.accesses()
    B, C = shards[Bacc.tensor.name], shards[Cacc.tensor.name]
    out_shape = stmt.lhs.tensor.shape
    a = B.arrays
    if levels.tree_of(Bacc.tensor).blocked:
        brow_start, row_start, row_count, max_brows = _bcsr_nnz_windows(B)
        C_blk = pack_mat_row_blocks(np.asarray(C.arrays["vals"]),
                                    int(B.meta["grid_cols"]),
                                    int(B.meta["bc"]))

        def fn(bd0, bd1, tiles, Cb, brow_start, row_start, row_count):
            rl = jnp.clip(bd0 - brow_start[:, None], 0, max_brows - 1)
            blocks = jax.vmap(
                K.leaf_bcsr_spmm_nnz, in_axes=(0, 0, 0, None, None))(
                rl, bd1, tiles, Cb, max_brows)
            return _scatter_rows(out_shape, blocks, row_start, row_count)

        args = (a["bdim0"], a["bdim1"], a["vals"], C_blk,
                brow_start, row_start, row_count)
        f = _runner(jit, "bcsr_spmm_nnz", out_shape + (max_brows,), args,
                    lambda: fn)
        return "bcsr_spmm_nnz", lambda: np.asarray(f(*args))

    row_start, row_count, max_rows = _nnz_row_windows(B, out_shape[0])
    Cv = C.arrays["vals"]

    def fn(rows, cols, vals, Cmat, row_start, row_count):
        rl = jnp.clip(rows - row_start[:, None], 0, max_rows - 1)
        blocks = jax.vmap(K.leaf_spmm_nnz, in_axes=(0, 0, 0, None, None))(
            rl, cols, vals, Cmat, max_rows)
        return _scatter_rows(out_shape, blocks, row_start, row_count)

    args = (a["dim0"], a["dim1"], a["vals"], Cv, row_start, row_count)
    f = _runner(jit, "spmm_nnz", out_shape + (max_rows,), args, lambda: fn)
    return "spmm_nnz", lambda: np.asarray(f(*args))


# -- SpAdd3 -----------------------------------------------------------------

def _emit_spadd3_rows(stmt, strat, plans, shards, jit=True):
    """Fused three-way add over shared row windows. Scalar trees: two-phase
    coordinate union per shard, host assembly into CSR. Blocked trees:
    tile union at block granularity (duplicate blocks merge by summing
    (br, bc) tiles), host assembly with Tensor.from_blocks — the output
    format follows the inputs' blocked format. Transpose-walked shards
    (CSC/BCSC) feed the SAME leaves: the walk already delivered row-window
    locality."""
    accs = stmt.rhs.accesses()
    Bs = [shards[acc.tensor.name] for acc in accs]
    Bt = accs[0].tensor
    n_rows, n_cols = stmt.lhs.tensor.shape
    if levels.tree_of(Bt).blocked:
        br, bc = int(Bs[0].meta["br"]), int(Bs[0].meta["bc"])

        def fn(args):
            (p1, c1, t1), (p2, c2, t2), (p3, c3, t3) = args
            return jax.vmap(K.leaf_bcsr_spadd3_rows)(
                p1, c1, t1, p2, c2, t2, p3, c3, t3)

        args = tuple((S.arrays["pos1"], S.arrays["crd1"], S.arrays["vals"])
                     for S in Bs)
        flat = tuple(x for trip in args for x in trip)
        f = _runner(jit, "bcsr_spadd3_rows", (n_rows, n_cols, br, bc), flat,
                    lambda: fn)

        def run():
            rows, cols, tiles, counts = (np.asarray(x) for x in f(args))
            brs = np.asarray(Bs[0].arrays["brow_start"])
            out_coords, out_tiles = [], []
            for p in range(rows.shape[0]):
                k = int(counts[p])
                out_coords.append(
                    np.stack([rows[p, :k] + brs[p], cols[p, :k]], axis=1))
                out_tiles.append(tiles[p, :k])
            return Tensor.from_blocks(
                stmt.lhs.tensor.name, (n_rows, n_cols), Bt.format,
                np.concatenate(out_coords), np.concatenate(out_tiles),
                dedupe=False)    # block-row windows are disjoint
        return "bcsr_spadd3_rows", run

    def fn(args):
        (p1, c1, v1), (p2, c2, v2), (p3, c3, v3) = args
        leaf = partial(K.leaf_spadd3_rows, n_cols=n_cols)
        return jax.vmap(leaf)(p1, c1, v1, p2, c2, v2, p3, c3, v3)

    args = tuple(
        (S.arrays["pos1"], S.arrays["crd1"], S.arrays["vals"]) for S in Bs)
    flat = tuple(x for trip in args for x in trip)
    f = _runner(jit, "spadd3_rows", (n_rows, n_cols), flat, lambda: fn)

    def run():
        rows, cols, vals, counts = (np.asarray(x) for x in f(args))
        # global assembly: offset shard-local rows by row_start
        out_rows, out_cols, out_vals = [], [], []
        rs = np.asarray(Bs[0].arrays["row_start"])
        for p in range(rows.shape[0]):
            k = int(counts[p])
            out_rows.append(rows[p, :k] + rs[p])
            out_cols.append(cols[p, :k])
            out_vals.append(vals[p, :k])
        coords = np.stack([np.concatenate(out_rows),
                           np.concatenate(out_cols)], 1)
        return Tensor.from_coo(stmt.lhs.tensor.name, (n_rows, n_cols),
                               coords, np.concatenate(out_vals),
                               fmt.CSR(), dedupe=True)

    return "spadd3_rows", run


def _emit_spadd3_nnz(stmt, strat, plans, shards, jit=True):
    """Non-zero SpAdd: the coordinate-position loop of an addition iterates
    the CONCATENATED stored-entry stream of all addends; splitting it evenly
    is the load-balanced strategy (paper §II-D applied to additions — the
    union position space is the natural fused space). The packed chunks
    come from the materialization layer (``materialize_add_stream``, keyed
    ``_addstream`` in the shard set) so a straggler re-plan re-slices a
    cached stream instead of re-walking the operands. Scalar trees union
    coordinates, blocked trees union whole tiles; boundary-straddling
    duplicates merge in the host assembly's dedupe."""
    Bt = stmt.rhs.accesses()[0].tensor
    n_rows, n_cols = stmt.lhs.tensor.shape
    pieces = strat.pieces
    S = shards["_addstream"]
    a = S.arrays
    max_c = int(S.meta["max_nnz"])
    if levels.tree_of(Bt).blocked:
        gr = int(S.meta["grid_rows"])
        br, bc = int(S.meta["br"]), int(S.meta["bc"])

        def fn(bd0, bd1, tiles, cnt):
            leaf = partial(K.leaf_bcsr_spadd_union_chunk, n_brows=gr)
            return jax.vmap(leaf)(bd0, bd1, tiles, cnt)

        f = _runner(jit, "bcsr_spadd3_nnz", (gr, br, bc),
                    (a["dim0"], a["dim1"], a["vals"], a["nnz_count"]),
                    lambda: fn)

        def run():
            if max_c == 0:
                return Tensor.from_blocks(
                    stmt.lhs.tensor.name, (n_rows, n_cols), Bt.format,
                    np.zeros((0, 2), np.int64),
                    np.zeros((0, br, bc), np.float32))
            rows, cols, tiles, counts = (np.asarray(x) for x in
                                         f(a["dim0"], a["dim1"], a["vals"],
                                           jnp.asarray(a["nnz_count"])))
            out_coords, out_tiles = [], []
            for p in range(rows.shape[0]):
                k = int(counts[p])
                out_coords.append(
                    np.stack([rows[p, :k], cols[p, :k]], axis=1))
                out_tiles.append(tiles[p, :k])
            return Tensor.from_blocks(
                stmt.lhs.tensor.name, (n_rows, n_cols), Bt.format,
                np.concatenate(out_coords), np.concatenate(out_tiles),
                dedupe=True)
        return "bcsr_spadd3_nnz", run

    def fn(rows, cols, v, cnt):
        leaf = partial(K.leaf_spadd_union_chunk, n_rows=n_rows)
        return jax.vmap(leaf)(rows, cols, v, cnt)

    f = _runner(jit, "spadd3_nnz", (n_rows,),
                (a["dim0"], a["dim1"], a["vals"], a["nnz_count"]),
                lambda: fn)

    def run():
        if max_c == 0:
            return Tensor.from_coo(stmt.lhs.tensor.name, (n_rows, n_cols),
                                   np.zeros((0, 2), np.int64),
                                   np.zeros((0,), np.float32), fmt.CSR())
        r, c, v, k = (np.asarray(x) for x in
                      f(a["dim0"], a["dim1"], a["vals"],
                        jnp.asarray(a["nnz_count"])))
        out_r, out_c, out_v = [], [], []
        for p in range(pieces):
            kk = int(k[p])
            out_r.append(r[p, :kk])
            out_c.append(c[p, :kk])
            out_v.append(v[p, :kk])
        coords_out = np.stack(
            [np.concatenate(out_r), np.concatenate(out_c)], axis=1)
        return Tensor.from_coo(stmt.lhs.tensor.name, (n_rows, n_cols),
                               coords_out, np.concatenate(out_v),
                               fmt.CSR(), dedupe=True)

    return "spadd3_nnz", run


# -- SDDMM ------------------------------------------------------------------

def _emit_sddmm_rows(stmt, strat, plans, shards, jit=True):
    """Row-based SDDMM: B and C's matching row block local per color, D
    replicated; output vals stay aligned with B's stored positions
    (pattern-preserving universe strategy). Ordered walks scatter back by
    value-space intervals; transpose-walked shards (CSC/BCSC) scatter home
    through their ``val_idx`` permutation instead."""
    accs = stmt.rhs.accesses()
    B = shards[accs[0].tensor.name]
    C = shards[accs[1].tensor.name]
    D = shards[accs[2].tensor.name]
    Bt = accs[0].tensor
    a = B.arrays
    if levels.tree_of(Bt).blocked:
        br, bc = int(B.meta["br"]), int(B.meta["bc"])
        max_brows = int(B.meta["max_brows"])
        # local C row blocks: pad the per-color row windows to the block grid
        C_blk = pack_rowwindow_blocks(C.arrays["vals"], max_brows, br)
        D_blk = pack_mat_inner_blocks(np.asarray(D.arrays["vals"]),
                                      int(B.meta["grid_cols"]), bc)
        total_blocks = int(Bt.levels[1].nnz or 0)
        if "val_idx" in a:
            def fn(pos, crd, tiles, Cl, Db, val_idx, nnz_count):
                def leaf(pos_, crd_, tiles_, Cl_):
                    brow = K.rows_from_pos(pos_, crd_.shape[0])
                    return K.leaf_bcsr_sddmm(brow, crd_, tiles_, Cl_, Db)
                out = jax.vmap(leaf)(pos, crd, tiles, Cl)
                return _scatter_by_val_idx(total_blocks, out, val_idx,
                                           nnz_count)

            args = (a["pos1"], a["crd1"], a["vals"], C_blk, D_blk,
                    a["val_idx"], a["nnz_count"])
            f = _runner(jit, "bcsr_sddmm_rows", (total_blocks, br, bc),
                        args, lambda: fn)
        else:
            vb = plans[Bt.name].vals_bounds
            nnz_start = jnp.asarray(vb[:, 0].astype(np.int32))
            nnz_count = jnp.asarray((vb[:, 1] - vb[:, 0]).astype(np.int32))

            def fn(pos, crd, tiles, Cl, Db, nnz_start, nnz_count):
                def leaf(pos_, crd_, tiles_, Cl_):
                    brow = K.rows_from_pos(pos_, crd_.shape[0])
                    return K.leaf_bcsr_sddmm(brow, crd_, tiles_, Cl_, Db)
                out = jax.vmap(leaf)(pos, crd, tiles, Cl)
                return _scatter_block_vals(total_blocks, out, nnz_start,
                                           nnz_count)

            args = (a["pos1"], a["crd1"], a["vals"], C_blk, D_blk,
                    nnz_start, nnz_count)
            f = _runner(jit, "bcsr_sddmm_rows", (total_blocks,), args,
                        lambda: fn)

        def run():
            new_tiles = np.asarray(f(*args))
            return Tensor(stmt.lhs.tensor.name, Bt.shape, Bt.format,
                          Bt.levels, new_tiles, Bt.dtype)
        return "bcsr_sddmm_rows", run

    Cv = C.arrays["vals"]                   # (P, max_rows, K) row blocks
    Dv = D.arrays["vals"]                   # (K, m) replicated
    total_nnz = Bt.nnz
    if "val_idx" in a:
        def fn(pos, crd, vals, Cl, Dm, val_idx, nnz_count):
            out = jax.vmap(K.leaf_sddmm_rows, in_axes=(0, 0, 0, 0, None))(
                pos, crd, vals, Cl, Dm)
            return _scatter_by_val_idx(total_nnz, out, val_idx, nnz_count)

        args = (a["pos1"], a["crd1"], a["vals"], Cv, Dv, a["val_idx"],
                a["nnz_count"])
        f = _runner(jit, "sddmm_rows", (total_nnz,), args, lambda: fn)
    else:
        vb = plans[Bt.name].vals_bounds
        nnz_start = jnp.asarray(vb[:, 0].astype(np.int32))
        nnz_count = jnp.asarray((vb[:, 1] - vb[:, 0]).astype(np.int32))

        def fn(pos, crd, vals, Cl, Dm, nnz_start, nnz_count):
            out = jax.vmap(K.leaf_sddmm_rows, in_axes=(0, 0, 0, 0, None))(
                pos, crd, vals, Cl, Dm)
            return _scatter_vals(total_nnz, out, nnz_start, nnz_count)

        args = (a["pos1"], a["crd1"], a["vals"], Cv, Dv, nnz_start,
                nnz_count)
        f = _runner(jit, "sddmm_rows", (total_nnz,), args, lambda: fn)

    def run():
        new_vals = np.asarray(f(*args))
        return Tensor(stmt.lhs.tensor.name, Bt.shape, Bt.format, Bt.levels,
                      new_vals, Bt.dtype)

    return "sddmm_rows", run


def _emit_sddmm_nnz(stmt, strat, plans, shards, jit=True):
    accs = stmt.rhs.accesses()
    B = shards[accs[0].tensor.name]
    C = shards[accs[1].tensor.name]
    D = shards[accs[2].tensor.name]
    Bt = accs[0].tensor
    a = B.arrays
    vb = plans[Bt.name].vals_bounds
    nnz_start = jnp.asarray(vb[:, 0].astype(np.int32))
    if levels.tree_of(Bt).blocked:
        br, bc = int(B.meta["br"]), int(B.meta["bc"])
        C_blk = pack_mat_row_blocks(np.asarray(C.arrays["vals"]),
                                    int(B.meta["grid_rows"]), br)
        D_blk = pack_mat_inner_blocks(np.asarray(D.arrays["vals"]),
                                      int(B.meta["grid_cols"]), bc)
        total_blocks = int(Bt.levels[1].nnz or 0)

        def fn(bd0, bd1, tiles, Cb, Db, counts, nnz_start):
            out = jax.vmap(K.leaf_bcsr_sddmm,
                           in_axes=(0, 0, 0, None, None))(
                bd0, bd1, tiles, Cb, Db)
            return _scatter_block_vals(total_blocks, out, nnz_start, counts)

        args = (a["bdim0"], a["bdim1"], a["vals"], C_blk, D_blk,
                a["nnz_count"], nnz_start)
        f = _runner(jit, "bcsr_sddmm_nnz", (total_blocks,), args,
                    lambda: fn)

        def run():
            new_tiles = np.asarray(f(*args))
            return Tensor(stmt.lhs.tensor.name, Bt.shape, Bt.format,
                          Bt.levels, new_tiles, Bt.dtype)
        return "bcsr_sddmm_nnz", run

    Cv, Dv = C.arrays["vals"], D.arrays["vals"]
    total_nnz = Bt.nnz

    def fn(rows, cols, vals, Cm, Dm, counts, nnz_start):
        out = jax.vmap(K.leaf_sddmm_nnz, in_axes=(0, 0, 0, None, None))(
            rows, cols, vals, Cm, Dm)
        return _scatter_vals(total_nnz, out, nnz_start, counts)

    args = (a["dim0"], a["dim1"], a["vals"], Cv, Dv, a["nnz_count"],
            nnz_start)
    f = _runner(jit, "sddmm_nnz", (total_nnz,), args, lambda: fn)

    def run():
        new_vals = np.asarray(f(*args))
        out = stmt.lhs.tensor
        return Tensor(out.name, Bt.shape, Bt.format, Bt.levels, new_vals,
                      Bt.dtype)

    return "sddmm_nnz", run


# -- SpTTV ------------------------------------------------------------------

def _spttv_flat_runner(stmt, shards, jit, name):
    """Flat-walk SpTTV: per-position products; (i, j) assembly happens on
    host (the result pattern is the walk's ij columns; duplicates merge in
    from_coo). Consumed by BOTH the nnz strategy and the universe strategy
    over trailing-singleton trees (COO3), whose shard sets are the same
    coordinate-column convention; ``name`` keeps the runner-cache label
    truthful about which strategy compiled it."""
    accs = stmt.rhs.accesses()
    B = shards[accs[0].tensor.name]
    c = shards[accs[1].tensor.name]
    Bt = accs[0].tensor
    a = B.arrays
    cv = c.arrays["vals"]

    def fn(dk, vals, cvec):
        return vals * jnp.take(cvec, dk, axis=0)

    f = _runner(jit, name, (), (a["dim2"], a["vals"], cv), lambda: fn)

    def run():
        prod = np.asarray(f(a["dim2"], a["vals"], cv)).ravel()
        di = np.asarray(a["dim0"]).ravel().astype(np.int64)
        dj = np.asarray(a["dim1"]).ravel().astype(np.int64)
        counts = np.asarray(a["nnz_count"])
        mask = np.zeros(prod.shape[0], bool)
        mn = a["dim0"].shape[1]
        for p in range(counts.shape[0]):
            mask[p * mn: p * mn + counts[p]] = True
        coords = np.stack([di[mask], dj[mask]], 1)
        # the assembled output format follows the input's (i, j) levels
        out_fmt = fmt.Format(Bt.format.levels[:2])
        return Tensor.from_coo(stmt.lhs.tensor.name, Bt.shape[:2], coords,
                               prod[mask], out_fmt, dedupe=True)

    return run


def _emit_spttv_rows(stmt, strat, plans, shards, jit=True):
    accs = stmt.rhs.accesses()
    Bt = accs[0].tensor
    if levels.tree_of(Bt).trailing_singletons:
        # no grouped middle level: the universe plan materialized the flat
        # walk bucketed by row window — consume it with the flat leaf
        return "spttv_flat_rows", _spttv_flat_runner(stmt, shards, jit,
                                                     "spttv_flat_rows")
    B = shards[Bt.name]
    c = shards[accs[1].tensor.name]
    a = B.arrays
    cv = c.arrays["vals"]
    # output pattern = B's (i,j) level; vals live at level-1 positions
    ij_bounds = plans[Bt.name].levels[1].pos_bounds
    total_ij = Bt.levels[1].nnz
    ij_start = jnp.asarray(ij_bounds[:, 0].astype(np.int32))
    ij_count = jnp.asarray(
        (ij_bounds[:, 1] - ij_bounds[:, 0]).astype(np.int32))

    def fn(pos1, crd1, pos2, crd2, vals, cvec, ij_start, ij_count):
        out = jax.vmap(K.leaf_spttv_rows, in_axes=(0, 0, 0, 0, 0, None))(
            pos1, crd1, pos2, crd2, vals, cvec)
        return _scatter_vals(total_ij, out, ij_start, ij_count)

    args = (a["pos1"], a["crd1"], a["pos2"], a["crd2"], a["vals"], cv,
            ij_start, ij_count)
    f = _runner(jit, "spttv_rows", (total_ij,), args, lambda: fn)

    def run():
        new_vals = np.asarray(f(*args))
        # output tensor: (i,j) matrix with B's ij pattern, in the format
        # the input's first two levels spell — CSF yields CSR, DCSF yields
        # DCSR (the output format follows the input's)
        import copy
        lv = [copy.copy(Bt.levels[0]), copy.copy(Bt.levels[1])]
        out_fmt = fmt.Format(Bt.format.levels[:2])
        return Tensor(stmt.lhs.tensor.name, Bt.shape[:2], out_fmt, lv,
                      new_vals, Bt.dtype)

    return "spttv_rows", run


def _emit_spttv_nnz(stmt, strat, plans, shards, jit=True):
    return "spttv_nnz", _spttv_flat_runner(stmt, shards, jit, "spttv_nnz")


# -- SpMTTKRP ---------------------------------------------------------------

def _spmttkrp_flat_runner(stmt, shards, jit, name):
    """Flat-walk MTTKRP: per-position (i, j, k) contributions segment-summed
    into the shard's row window. Consumed by the nnz strategy (overlapping
    windows, reduced by the scatter) AND the universe strategy over
    trailing-singleton trees (COO3 — disjoint windows, same leaf)."""
    accs = stmt.rhs.accesses()
    B = shards[accs[0].tensor.name]
    C = shards[accs[1].tensor.name]
    D = shards[accs[2].tensor.name]
    out_shape = stmt.lhs.tensor.shape
    a = B.arrays
    row_start, row_count, max_rows = _nnz_row_windows(B, out_shape[0])
    Cv, Dv = C.arrays["vals"], D.arrays["vals"]

    def fn(di, dj, dk, vals, Cm, Dm, row_start, row_count):
        rl = jnp.clip(di - row_start[:, None], 0, max_rows - 1)
        blocks = jax.vmap(
            K.leaf_spmttkrp_nnz, in_axes=(0, 0, 0, 0, None, None, None))(
            rl, dj, dk, vals, Cm, Dm, max_rows)
        return _scatter_rows(out_shape, blocks, row_start, row_count)

    args = (a["dim0"], a["dim1"], a["dim2"], a["vals"], Cv, Dv,
            row_start, row_count)
    f = _runner(jit, name, out_shape + (max_rows,), args, lambda: fn)
    return lambda: np.asarray(f(*args))


def _emit_spmttkrp_rows(stmt, strat, plans, shards, jit=True):
    accs = stmt.rhs.accesses()
    Bt = accs[0].tensor
    if levels.tree_of(Bt).trailing_singletons:
        return "spmttkrp_flat_rows", _spmttkrp_flat_runner(
            stmt, shards, jit, "spmttkrp_flat_rows")
    B = shards[Bt.name]
    C = shards[accs[1].tensor.name]
    D = shards[accs[2].tensor.name]
    out_shape = stmt.lhs.tensor.shape
    a = B.arrays
    Cv, Dv = C.arrays["vals"], D.arrays["vals"]

    def fn(pos1, crd1, pos2, crd2, vals, Cm, Dm, row_start, row_count):
        blocks = jax.vmap(
            K.leaf_spmttkrp_rows, in_axes=(0, 0, 0, 0, 0, None, None))(
            pos1, crd1, pos2, crd2, vals, Cm, Dm)
        return _scatter_rows(out_shape, blocks, row_start, row_count)

    args = (a["pos1"], a["crd1"], a["pos2"], a["crd2"], a["vals"], Cv, Dv,
            a["row_start"], a["row_count"])
    f = _runner(jit, "spmttkrp_rows", out_shape, args, lambda: fn)
    return "spmttkrp_rows", lambda: np.asarray(f(*args))


def _emit_spmttkrp_nnz(stmt, strat, plans, shards, jit=True):
    return "spmttkrp_nnz", _spmttkrp_flat_runner(stmt, shards, jit,
                                                 "spmttkrp_nnz")


def _emit_generic_fallback(stmt, strat, plans, shards, jit=True):
    """Correctness fallback for arbitrary TIN: densify and einsum.

    Kept for generality (the paper supports *all* of tensor algebra); not a
    performance path and flagged as such by leaf_name."""
    del strat, plans, shards

    def run():
        from .interp import interpret
        return interpret(stmt)

    return run


# One generic emitter per expression × strategy — the whole specialization
# table. Format variation lives in the level trees the emitters query, not
# in this table.
_EMITTERS = {
    ("d1(i)=s2(i,j)*d1(j)", "universe"): _emit_spmv_rows,
    ("d1(i)=s2(i,j)*d1(j)", "nnz"): _emit_spmv_nnz,
    ("d2(i,j)=s2(i,k)*d2(k,j)", "universe"): _emit_spmm_rows,
    ("d2(i,j)=s2(i,k)*d2(k,j)", "nnz"): _emit_spmm_nnz,
    ("s2(i,j)=s2(i,j)+s2(i,j)+s2(i,j)", "universe"): _emit_spadd3_rows,
    ("s2(i,j)=s2(i,j)+s2(i,j)+s2(i,j)", "nnz"): _emit_spadd3_nnz,
    ("s2(i,j)=s2(i,j)*d2(i,k)*d2(k,j)", "universe"): _emit_sddmm_rows,
    ("s2(i,j)=s2(i,j)*d2(i,k)*d2(k,j)", "nnz"): _emit_sddmm_nnz,
    ("s2(i,j)=s3(i,j,k)*d1(k)", "universe"): _emit_spttv_rows,
    ("s2(i,j)=s3(i,j,k)*d1(k)", "nnz"): _emit_spttv_nnz,
    ("d2(i,l)=s3(i,j,k)*d2(j,l)*d2(k,l)", "universe"): _emit_spmttkrp_rows,
    ("d2(i,l)=s3(i,j,k)*d2(j,l)*d2(k,l)", "nnz"): _emit_spmttkrp_nnz,
}


# ---------------------------------------------------------------------------
# Serving fast path (ISSUE 10): request batching over a lowered kernel.
#
# A request queue of B right-hand-side vectors against one frozen sparse
# operand is ONE SpMM: stacking the vectors as columns promotes SpMV to
# SpMM (or widens an SpMM), so B requests share a single plan, a single
# shard materialization of the sparse operand, and a single jitted runner.
# Batch sizes are padded up to a bucket (cache.batch_bucket) so the
# runner caches see at most len(buckets) distinct widths under mixed
# traffic. The per-call work is only: pack the batch columns, re-pack the
# dense RHS shard (rebind_dense — no plan, no fingerprinting, runner-cache
# hit), execute, slice the per-request outputs back out.
# ---------------------------------------------------------------------------

def _materialize_dense_operand(t: Tensor, plan: TensorPartition, pieces: int,
                               cache: bool = False) -> ShardedTensor:
    """Re-pack ONE all-dense operand under its existing partition geometry
    — the same branch structure the 1-D and grid lowering paths use, minus
    every sparse case (rebinds only ever swap dense request data)."""
    if plan.replicated:
        return materialize_replicated(t, pieces, cache=cache)
    if plan.grid is not None:
        return materialize_dense_grid(t, plan.levels[0].coord_bounds,
                                      plan.levels[1].coord_bounds,
                                      cache=cache)
    if plan.root_coord_bounds is None:
        return materialize_dense_cols(t, plan.levels[1].coord_bounds,
                                      cache=cache)
    return materialize_dense_rows(t, plan.root_coord_bounds, cache=cache)


def rebind_dense(kernel: LoweredKernel, mapping: Dict[str, Tensor], *,
                 jit: bool = True, cache: bool = False) -> LoweredKernel:
    """A copy of ``kernel`` with dense operands swapped by name.

    The partition geometry is kept (bounds depend only on shapes and the
    sparse pattern, both unchanged), so the swap re-packs just the named
    operands' shards and re-emits — a pure runner-cache hit when the new
    values have the old shapes. This is the serving hot path: no plan
    recompute, no content fingerprinting of any operand. ``comm`` is
    carried over unchanged (the model depends on shapes, not values).

    Only all-dense operands can rebind; a sparse swap changes the
    partition itself and must go through ``lower()`` / ``relower()``."""
    strat = kernel.strategy
    stmt = kernel.stmt.with_tensors(mapping)
    plans = dict(kernel.plans)
    shards = dict(kernel.shards)
    for name, t in mapping.items():
        old = plans.get(name)
        if old is None:
            raise KeyError(f"operand {name!r} not in kernel plans "
                           f"({sorted(plans)})")
        if (old.tensor is not None and old.tensor.format.is_sparse) \
                or t.format.is_sparse:
            raise ValueError(
                f"rebind_dense only swaps all-dense operands; {name!r} is "
                "sparse — re-plan through lower()/relower() instead")
        plans[name] = dataclasses.replace(old, tensor=t)
        if name in shards:
            shards[name] = _materialize_dense_operand(
                t, plans[name], strat.pieces, cache=cache)
    if strat.is_grid and strat.space == "universe":
        from . import grid as grid_mod
        gp = grid_mod.compute_grid_plan(stmt, strat)
        leaf_name, runner = grid_mod._emit_grid(stmt, strat, gp, plans,
                                                shards, jit=jit)
    else:
        leaf_name, runner = _emit(stmt, strat, plans, shards, jit=jit)
    return dataclasses.replace(kernel, stmt=stmt, plans=plans,
                               shards=shards, runner=runner,
                               leaf_name=leaf_name)


#: Batchable signatures: per-request RHS shape, promoted signature.
_BATCHABLE = {
    "d1(i)=s2(i,j)*d1(j)": "spmv",        # requests are (m,) vectors
    "d2(i,j)=s2(i,k)*d2(k,j)": "spmm",    # requests are (m, jw) panels
}


@dataclasses.dataclass
class _BucketEntry:
    kernel: LoweredKernel
    rhs_name: str
    out_name: str
    bucket: int
    jw: int                      # per-request column width (1 for spmv)
    m: int                       # RHS rows


class BatchedKernel:
    """Bucketized request batching over one scheduled sparse statement.

    ``run_many([x_0, ..., x_{B-1}])`` stacks the request vectors (or
    fixed-width panels) as columns of one dense RHS, pads the batch up to
    the smallest registered bucket, executes the per-bucket lowered SpMM
    once, and slices per-request outputs back out. Each bucket lowers
    lazily exactly once — one plan, one set of sparse shards, one jitted
    runner — and later batches of any size in that bucket reuse all three
    via :func:`rebind_dense`.

    ``schedule`` may be a Schedule, None, the string ``"auto"``, or a
    callable ``(stmt, machine) -> Schedule`` applied to the PROMOTED
    statement (e.g. ``default_nnz_schedule`` / ``default_grid_schedule``).
    ``mesh`` routes execution through the shard_map SPMD executor instead
    of the vmap simulation (bounded identically: _SPMD_RUN_CACHE keys on
    the bucket-padded avals).
    """

    def __init__(self, stmt: Assignment, machine: Machine,
                 schedule: Any = None, *, buckets=BATCH_BUCKETS,
                 jit: bool = True, mesh: Any = None):
        sig = stmt.signature()
        if sig not in _BATCHABLE:
            raise NotImplementedError(
                f"lower_batched supports {sorted(_BATCHABLE)}; got {sig}")
        self.stmt = stmt
        self.machine = machine
        self.schedule = schedule
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.jit = jit
        self.mesh = mesh
        self.kind = _BATCHABLE[sig]
        self._entries: Dict[int, _BucketEntry] = {}

    # -- construction ------------------------------------------------------
    def _promoted_stmt(self, bucket: int) -> Tuple[Assignment, str, str, int]:
        stmt = self.stmt
        sparse_acc = stmt.rhs.accesses()[0]
        rhs_acc = stmt.rhs.accesses()[-1]
        rhs_name = rhs_acc.tensor.name
        out_name = stmt.lhs.tensor.name
        n = stmt.lhs.tensor.shape[0]
        m = rhs_acc.tensor.shape[0]
        if self.kind == "spmv":
            # promote a(i) = B(i,j) * c(j)  →  A(i,j) = B(i,k) * C(k,j):
            # each request vector is one column of C. Index vars are
            # rebuilt with the canonical SpMM names (the emitter table and
            # default schedules key on them); a caller-tuned schedule is
            # passed as a callable over the promoted statement.
            i, k, j = IndexVar("i"), IndexVar("k"), IndexVar("j")
            out = Tensor.zeros_dense(out_name, (n, bucket))
            X = Tensor.from_dense(rhs_name,
                                  np.zeros((m, bucket), np.float32))
            bstmt = Assignment(
                Access(out, (i, j)),
                Mul(Access(sparse_acc.tensor, (i, k)),
                    Access(X, (k, j))))
            return bstmt, rhs_name, out_name, 1
        # spmm: widen the dense RHS to bucket panels of the original width
        jw = stmt.lhs.tensor.shape[1]
        out = Tensor.zeros_dense(out_name, (n, bucket * jw))
        X = Tensor.from_dense(rhs_name,
                              np.zeros((m, bucket * jw), np.float32))
        bstmt = stmt.with_tensors({out_name: out, rhs_name: X})
        return bstmt, rhs_name, out_name, jw

    def _entry(self, bucket: int) -> _BucketEntry:
        e = self._entries.get(bucket)
        if e is not None:
            return e
        bstmt, rhs_name, out_name, jw = self._promoted_stmt(bucket)
        sched = self.schedule
        if callable(sched) and not isinstance(sched, Schedule):
            sched = sched(bstmt, self.machine)
        with telemetry.span("serve.batch.lower", bucket=bucket):
            kernel = lower(bstmt, self.machine, schedule=sched, jit=self.jit)
        telemetry.METRICS.counter("serve.buckets_lowered")
        e = _BucketEntry(kernel=kernel, rhs_name=rhs_name,
                         out_name=out_name, bucket=bucket, jw=jw,
                         m=bstmt.rhs.accesses()[-1].tensor.shape[0])
        self._entries[bucket] = e
        return e

    def warm(self, batch: int) -> "BatchedKernel":
        """Pre-lower the bucket that will serve batches of size ``batch``."""
        self._entry(batch_bucket(batch, self.buckets))
        return self

    # -- execution ---------------------------------------------------------
    def run_many(self, rhs_batch) -> List[np.ndarray]:
        """Execute one batched step over ``len(rhs_batch)`` requests and
        return the per-request outputs ((n,) each for spmv requests,
        (n, jw) for spmm panels), bit-for-bit equal to running the
        original statement once per request."""
        B = len(rhs_batch)
        bucket = batch_bucket(B, self.buckets)
        with telemetry.span("serve.batch", requests=B, bucket=bucket) as sp:
            e = self._entry(bucket)
            buf = np.zeros((e.m, bucket * e.jw), np.float32)
            for r, x in enumerate(rhs_batch):
                x = np.asarray(x, np.float32)
                if e.jw == 1:
                    buf[:, r] = x.reshape(e.m)
                else:
                    buf[:, r * e.jw:(r + 1) * e.jw] = x.reshape(e.m, e.jw)
            X = Tensor.from_dense(e.rhs_name, buf)
            e.kernel = rebind_dense(e.kernel, {e.rhs_name: X},
                                    jit=self.jit, cache=False)
            if self.mesh is not None:
                from ..distributed.executor import to_spmd
                y = np.asarray(to_spmd(e.kernel, self.mesh)())
            else:
                y = np.asarray(e.kernel.run())
            sp.set(leaf=e.kernel.leaf_name)
        telemetry.METRICS.counter("serve.requests", B)
        telemetry.METRICS.counter("serve.batches")
        telemetry.METRICS.observe("serve.batch.occupancy", B / bucket)
        telemetry.METRICS.observe("serve.batch.padded_slot_waste",
                                  (bucket - B) / bucket)
        if e.jw == 1:
            return [y[:, r] for r in range(B)]
        return [y[:, r * e.jw:(r + 1) * e.jw] for r in range(B)]

    def explain(self) -> str:
        lines = [f"batched kernel over {self.stmt.signature()} "
                 f"buckets={self.buckets}"]
        for b, e in sorted(self._entries.items()):
            lines.append(f"  bucket {b}: leaf={e.kernel.leaf_name} "
                         f"pieces={e.kernel.strategy.pieces}")
        return "\n".join(lines)


def lower_batched(stmt: Assignment, machine: Machine, batch: int = 8,
                  schedule: Any = None, *, buckets=BATCH_BUCKETS,
                  jit: bool = True, mesh: Any = None) -> BatchedKernel:
    """Batched-serving entry point: a :class:`BatchedKernel` for ``stmt``
    with the bucket covering ``batch`` pre-lowered (one plan + one jitted
    runner, shared by every later ``run_many`` call in that bucket)."""
    return BatchedKernel(stmt, machine, schedule, buckets=buckets,
                         jit=jit, mesh=mesh).warm(batch)
