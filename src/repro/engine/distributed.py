"""Distributed federation executor: the paper's endpoint/engine architecture
mapped onto a TPU mesh (DESIGN.md §2).

Layout
------
* ``data`` axis  = federation endpoints.  The leading source axis of the
  triple tables holds every source, padded to a multiple of the axis size,
  and each data shard holds a block of sources: all nine FedBench sources
  on one chip, or a few of them per chip on a larger mesh.
* ``model`` axis = intra-endpoint parallelism: each source's triples are
  **hash-partitioned by subject** across the model axis, so star-shaped
  subqueries (subject joins) execute entirely shard-locally.  A star is
  scanned and joined per source (vmapped over the local block), never across
  two endpoints — the paper's "subqueries evaluated at the endpoint"
  invariant, in SPMD form — and the local sources' results are then
  flattened into the shard's rows.
* ``pod`` axis   = query-batch data parallelism (multi-pod dry-run).

Cross-star joins exchange rows by join-key hash over the model axis
(``all_to_all``) and/or gather the build side over the data axis
(``all_gather``) — the *transferred tuples* of the paper are literally the
collective bytes of this engine, which is what Odyssey's optimizer minimizes.

All relations are bounded buffers (operators.py).  ``execute`` reads every
operator's overflow flag once at the end of a run; on any overflow it runs
the whole plan again with the capacity doubled, up to a bound taken from the
device's memory, past which it raises ``CapacityExceededError``.  It never
returns truncated rows.
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.trace import span
from repro.core.planner import (
    JoinPlanNode,
    PhysicalPlan,
    PlanNode,
    SubqueryNode,
    query_signature,
)
from repro.engine import operators as ops
from repro.engine.local import ExecutionResult
from repro.query.algebra import TriplePattern, Var
from repro.rdf.dataset import Federation

_SHARDED = P("data", "model")

# Memory model of ``capacity_bound``, held to the compiled programs by
# tests/test_tpu_compile.py.  A device holds its block of source tables and
# temporaries the size of the block: ``_TABLE_COPIES`` of them whatever the
# plan, ``_SCAN_COPIES`` more for each pattern (its match mask and sort
# order).  Per capacity row, local source and device whose rows the exchange
# gathers, it holds ``_LIVE_COPIES`` copies of a row of the plan's widest
# relation plus ``_INDEX_WORDS`` int32 index words (sort orders, offsets).
_TABLE_COPIES = 2
_SCAN_COPIES = 2
_LIVE_COPIES = 4
_INDEX_WORDS = 8


class AlgebraFallbackWarning(UserWarning):
    """The SPMD engine received an OPTIONAL/UNION/FILTER plan and degraded it
    to ``LocalEngine`` instead of failing (``ExecutionResult.fallback`` names
    the substitution).  Filterable: the fallback changes *where* the plan
    runs, never its rows."""


class CapacityExceededError(RuntimeError):
    """A plan still overflows its bounded buffers at the largest capacity
    the device's memory allows.  Raised instead of returning truncated
    rows."""


def _has_algebra_nodes(node: PlanNode) -> bool:
    """True iff the plan tree contains any non-conjunctive operator (the
    forms ``_eval_node`` deliberately rejects)."""
    if isinstance(node, SubqueryNode):
        return False
    if isinstance(node, JoinPlanNode):
        return _has_algebra_nodes(node.left) or _has_algebra_nodes(node.right)
    return True


@dataclass
class DistRelation:
    """Host handle to a device-sharded bounded relation.

    ``columns`` names the variable each column binds (``None`` for a
    constant position).  A variable may own several columns (a join key's
    right-hand copy, a variable repeated inside a star); ``execute`` keeps
    only the rows in which all of them agree."""

    data: jax.Array          # (d, m, rows, C) int32
    valid: jax.Array         # (d, m, rows) bool
    columns: list[str | None]
    partitioned_by: str | None = None  # var whose hash partitions the model axis


@dataclass
class DistMetrics:
    transferred_tuples: int = 0
    capacity: int = 0            # rows per shard and operator of the answering run
    capacity_retries: int = 0    # doublings paid before the answering run
    retry_ms: float = 0.0        # host wall time of the overflowed attempts


@dataclass
class _Run:
    """One attempt at a plan under one capacity.  Overflow flags and shipped
    counts stay on the device until the attempt ends, so an attempt costs
    one host sync, not one per operator."""

    cap: int
    overflow: list = field(default_factory=list)
    shipped: list = field(default_factory=list)   # device counts of shipped rows


def _enc_pattern(tp: TriplePattern) -> list[int]:
    s, p, o = tp.constants()
    return [s if s is not None else -1, p if p is not None else -1,
            o if o is not None else -1]


# ---------------------------------------------------------------------------
# jitted SPMD steps (shared by the engine and the dry-run lowering)
# ---------------------------------------------------------------------------

def _star_program(mesh: Mesh, n_pat: int, cap: int):
    """Scan + subject-join the ``n_pat`` patterns of one star at every local
    source, shard-local.  ``cap`` bounds each source's rows.

    Output columns: [subject, obj_0, ..., obj_{n_pat-1}]; the shard's rows
    are its local sources' results back to back."""

    def one_source(table, rows_ok, patterns):
        rel, valid, ovf = ops.scan_pattern(table, rows_ok, patterns[0], cap,
                                           (0, 2))
        for k in range(1, n_pat):
            nxt, nvalid, o2 = ops.scan_pattern(table, rows_ok, patterns[k],
                                               cap, (0, 2))
            rel, valid, o3 = ops.merge_join(rel, valid, 0, nxt, nvalid, 0, cap)
            # drop the right side's copy of the subject (at column k + 1)
            keep = list(range(rel.shape[1]))
            keep.remove(k + 1)
            rel = rel[:, keep]
            ovf = ovf | o2 | o3
        return rel, valid, ovf

    def per_shard(tables, trow, source_on, patterns):
        rows_ok = trow[:, 0] & source_on[:, None]
        rel, valid, ovf = jax.vmap(one_source, in_axes=(0, 0, None))(
            tables[:, 0], rows_ok, patterns)
        rows = rel.shape[0] * cap
        return (rel.reshape(1, 1, rows, n_pat + 1),
                valid.reshape(1, 1, rows), jnp.any(ovf).reshape(1, 1))

    per_shard.__name__ = f"fed_star{n_pat}"    # the program's name in a trace
    return jax.jit(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(_SHARDED, _SHARDED, P("data"), P()),
        out_specs=(_SHARDED, _SHARDED, _SHARDED), check_vma=False))


def _bucket_rank(dest: jax.Array):
    """Rows ordered by destination bucket (stably), the ordered buckets, and
    each ordered row's rank within its bucket."""
    pos = jnp.arange(dest.shape[0], dtype=jnp.int32)
    sorted_dest, order = jax.lax.sort((dest, pos), num_keys=1, is_stable=True)
    return order, sorted_dest, pos - ops.run_start(sorted_dest, pos)


def _exchange_program(mesh: Mesh, cap: int, right_partitioned: bool):
    """Repartition rows over the model axis by hash of a key column, then
    merge-join against a local build side: the distributed hash join.

    ``right_partitioned``: the build side is already hash-partitioned by
    its join key over the model axis (true when joining a star on its
    subject), so the model-axis gather is skipped — m× fewer build bytes
    on the wire."""
    m = mesh.shape["model"]
    bucket_cap = cap // m

    def per_shard(lrel, lvalid, rrel, rvalid, lkey, rkey):
        lrel = lrel[0, 0]
        lvalid = lvalid[0, 0]
        rrel = rrel[0, 0]
        rvalid = rvalid[0, 0]
        ncols = lrel.shape[1]
        # --- exchange left rows by key % m over the model axis -------------
        keyv = jnp.take(lrel, lkey, axis=1)
        dest = jnp.where(lvalid, keyv % m, m)  # m = drop bucket
        order, sorted_dest, idx_in_dest = _bucket_rank(dest)
        ovf = jnp.max(jnp.where(sorted_dest < m, idx_in_dest, 0)) >= bucket_cap
        slot = jnp.clip(idx_in_dest, 0, bucket_cap - 1)
        row_ok = (sorted_dest < m) & (idx_in_dest < bucket_cap)
        tgt = jnp.where(row_ok, sorted_dest, m)  # OOB rows are dropped
        send = jnp.zeros((m, bucket_cap, ncols), jnp.int32)
        send = send.at[tgt, slot].set(lrel[order], mode="drop")
        svalid = jnp.zeros((m, bucket_cap), bool)
        svalid = svalid.at[tgt, slot].set(row_ok, mode="drop")
        shipped = jnp.sum(svalid)
        recv = jax.lax.all_to_all(send, "model", 0, 0, tiled=True)
        vrecv = jax.lax.all_to_all(svalid, "model", 0, 0, tiled=True)
        lrel2 = recv.reshape(-1, ncols)
        lvalid2 = vrecv.reshape(-1)
        # --- gather the build side across the federation -------------------
        if right_partitioned:
            # build rows already live on the model shard of their key:
            # gather over sources (data) only — m× fewer bytes
            rrel_g = jax.lax.all_gather(rrel, "data", tiled=True)
            rvalid_g = jax.lax.all_gather(rvalid, "data", tiled=True)
            shipped = shipped + jnp.sum(rvalid)
        else:
            rrel_g = jax.lax.all_gather(rrel, "model", tiled=True)
            rvalid_g = jax.lax.all_gather(rvalid, "model", tiled=True)
            rrel_g = jax.lax.all_gather(rrel_g, "data", tiled=True)
            rvalid_g = jax.lax.all_gather(rvalid_g, "data", tiled=True)
            shipped = shipped + jnp.sum(rvalid)
            # keep only build rows whose key hashes to this model shard
            my = jax.lax.axis_index("model")
            rkeyv = jnp.take(rrel_g, rkey, axis=1)
            rvalid_g = rvalid_g & ((rkeyv % m) == my)
        out, ovalid, o2 = ops.merge_join(lrel2, lvalid2, lkey, rrel_g, rvalid_g,
                                         rkey, cap)
        shipped_total = jax.lax.psum(jax.lax.psum(shipped, "model"), "data")
        ovf_any = jax.lax.psum(
            jax.lax.psum((ovf | o2).astype(jnp.int32), "model"), "data") > 0
        return (out[None, None], ovalid[None, None],
                ovf_any[None, None], shipped_total[None, None])

    per_shard.__name__ = "fed_exchange_part" if right_partitioned else "fed_exchange"
    return jax.jit(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(_SHARDED, _SHARDED, _SHARDED, _SHARDED, P(), P()),
        out_specs=(_SHARDED, _SHARDED, _SHARDED, _SHARDED), check_vma=False))


def _collect_program(mesh: Mesh):
    """Gather a sharded relation to every shard (replicated result)."""

    def per_shard(rel, valid):
        rel = rel[0, 0]
        valid = valid[0, 0]
        rel_g = jax.lax.all_gather(rel, "model", tiled=True)
        val_g = jax.lax.all_gather(valid, "model", tiled=True)
        rel_g = jax.lax.all_gather(rel_g, "data", tiled=True)
        val_g = jax.lax.all_gather(val_g, "data", tiled=True)
        return rel_g[None, None], val_g[None, None]

    per_shard.__name__ = "fed_collect"
    return jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(_SHARDED, _SHARDED),
        out_specs=(P(None, None), P(None, None)), check_vma=False))


def source_slots(n_sources: int, mesh: Mesh) -> int:
    """Length of the leading source axis: every source gets a slot, padded
    to a multiple of the data axis so each data shard holds a block."""
    d = mesh.shape["data"]
    return -(-n_sources // d) * d


def capacity_bound(bytes_limit: int, mesh: Mesh, n_slots: int, table_cap: int,
                   width: int) -> int:
    """The largest capacity (a power of two) whose programs fit in
    ``bytes_limit`` bytes of one device, for a plan whose widest relation
    has ``width`` columns, on ``mesh`` with ``n_slots`` source slots of
    ``table_cap`` triples per model shard (see ``_TABLE_COPIES``).  Every
    pattern contributes at most two of the ``width`` columns."""
    d, m = mesh.shape["data"], mesh.shape["model"]
    local = n_slots // d
    block = local * table_cap * (3 * 4 + 1)          # (s, p, o) int32 + valid
    fixed = block * (_TABLE_COPIES + _SCAN_COPIES * (width // 2))
    per_row = 4 * local * d * m * (_LIVE_COPIES * width + _INDEX_WORDS)
    rows = max(1, (bytes_limit - fixed) // per_row)
    return 1 << (int(rows).bit_length() - 1)


class DistributedEngine:
    """Executes conjunctive PhysicalPlans on a (data, model) mesh.

    ``cap`` is the capacity tried first: the rows each operator may emit
    per shard (per local source, for star scans).  ``execute`` doubles it
    on overflow up to ``max_cap``.  The capacity a query needed is kept by
    the query's signature (``query_signature``), so its repeats start there
    and re-run no overflowing attempt, while every other query starts at
    ``cap`` again (never above its own bound).
    """

    def __init__(self, fed: Federation, mesh: Mesh, cap: int = 2048,
                 table_cap: int | None = None, partition_aware: bool = False):
        # partition_aware: skip the model-axis gather of the build side when
        # it is already hash-partitioned by the join key (§Perf optimization;
        # baseline engines gather unconditionally)
        self.partition_aware = partition_aware
        self.fed = fed
        self.mesh = mesh
        self.cap = cap
        self.d = mesh.shape["data"]
        self.m = mesh.shape["model"]
        self.n_slots = source_slots(len(fed.sources), mesh)
        parts = [src.table.s % self.m for src in fed.sources]
        longest = max((int(np.bincount(p, minlength=self.m).max())
                       for p in parts if len(p)), default=1)
        if table_cap is None:
            table_cap = 1 << (longest - 1).bit_length()
        elif table_cap < longest:
            raise ValueError(
                f"table_cap={table_cap} cannot hold a source partition of "
                f"{longest} triples")
        self.table_cap = table_cap

        tables = np.zeros((self.n_slots, self.m, table_cap, 3), np.int32)
        trow = np.zeros((self.n_slots, self.m, table_cap), bool)
        for sid, (src, part) in enumerate(zip(fed.sources, parts)):
            t = src.table
            for mm in range(self.m):
                rows = np.flatnonzero(part == mm)
                k = len(rows)
                tables[sid, mm, :k, 0] = t.s[rows]
                tables[sid, mm, :k, 1] = t.p[rows]
                tables[sid, mm, :k, 2] = t.o[rows]
                trow[sid, mm, :k] = True
        sh = NamedSharding(mesh, _SHARDED)
        self.tables = jax.device_put(tables, sh)
        self.trow = jax.device_put(trow, sh)
        self.n_triples = int(trow.sum())
        self._programs: dict[tuple, object] = {}
        self._caps: dict[tuple, int] = {}      # query signature -> capacity

    def _program(self, key: tuple, build):
        fn = self._programs.get(key)
        if fn is None:
            fn = self._programs[key] = build()
        return fn

    def bytes_limit(self) -> int:
        """One device's memory: its ``bytes_limit``, or for a backend that
        reports none (the CPU) the host's physical memory."""
        stats = self.mesh.devices.flat[0].memory_stats() or {}
        return stats.get("bytes_limit") or (
            os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))

    def max_cap(self, width: int) -> int:
        """``capacity_bound`` for this engine's layout and device."""
        return capacity_bound(self.bytes_limit(), self.mesh, self.n_slots,
                              self.table_cap, width)

    # ------------------------------------------------------------------
    # plan execution
    # ------------------------------------------------------------------
    def _eval_star(self, node: SubqueryNode, run: _Run) -> DistRelation:
        assert len(node.stars) == 1, "merged leaves run on the exclusive path"
        pats = list(node.patterns)
        for tp in pats:
            if isinstance(tp.p, Var):
                raise NotImplementedError(
                    f"the SPMD engine scans constant predicates only; {tp} "
                    "has a variable predicate — run this plan on "
                    "repro.engine.local.LocalEngine")
        enc = np.array([_enc_pattern(tp) for tp in pats], np.int32)
        on = np.zeros(self.n_slots, bool)
        on[list(node.sources)] = True
        star = self._program(("star", len(pats), run.cap),
                             lambda: _star_program(self.mesh, len(pats), run.cap))
        rel, valid, ovf = star(
            self.tables, self.trow,
            jax.device_put(on, NamedSharding(self.mesh, P("data"))),
            jnp.asarray(enc))
        run.overflow.append(ovf)
        subj = pats[0].s.name if isinstance(pats[0].s, Var) else None
        cols = [subj] + [tp.o.name if isinstance(tp.o, Var) else None
                         for tp in pats]
        return DistRelation(rel, valid, cols, partitioned_by=subj)

    def _eval_node(self, node: PlanNode, run: _Run) -> DistRelation:
        if isinstance(node, SubqueryNode):
            if len(node.stars) == 1:
                return self._eval_star(node, run)
            # exclusive group ("single SPARQL query to one endpoint", §3.4):
            # evaluate each star then join; rows stay within the source.
            return self._join_merged_leaf(node, run)
        if not isinstance(node, JoinPlanNode):
            raise NotImplementedError(
                f"the SPMD engine executes conjunctive (Subquery/Join) plans "
                f"only; got {type(node).__name__} — run OPTIONAL/UNION/FILTER "
                "plans on repro.engine.local.LocalEngine")
        left = self._eval_node(node.left, run)
        right = self._eval_node(node.right, run)
        return self._join(left, right, node.join_vars, run)

    def _join_merged_leaf(self, node: SubqueryNode, run: _Run) -> DistRelation:
        from repro.core.decomposition import decompose
        from repro.query.algebra import BGPQuery

        graph = decompose(BGPQuery(list(node.patterns)))
        rels: list[DistRelation] = []
        for star in graph.stars:
            sub = SubqueryNode(stars=[0], patterns=star.patterns, sources=node.sources)
            rels.append(self._eval_star(sub, run))
        out = rels[0]
        for r in rels[1:]:
            jv = sorted((set(out.columns) & set(r.columns)) - {None})
            out = self._join(out, r, jv, run)
        return out

    def _join(self, left: DistRelation, right: DistRelation, join_vars: list[str],
              run: _Run) -> DistRelation:
        if not join_vars:
            raise NotImplementedError(
                "the SPMD engine does not execute cartesian joins")
        # the first variable keys the exchange; the others are enforced as
        # column equalities when the result is collected
        jv = join_vars[0]
        lkey = left.columns.index(jv)
        rkey = right.columns.index(jv)
        right_part = self.partition_aware and right.partitioned_by == jv
        exchange = self._program(
            ("exchange", run.cap, right_part),
            lambda: _exchange_program(self.mesh, run.cap, right_part))
        rel, valid, ovf, shipped = exchange(
            left.data, left.valid, right.data, right.valid,
            jnp.int32(lkey), jnp.int32(rkey))
        run.overflow.append(ovf)
        run.shipped.append(shipped)
        return DistRelation(rel, valid, left.columns + right.columns,
                            partitioned_by=jv)

    def _attempt(self, plan: PhysicalPlan, cap: int):
        """Run the plan once under ``cap``; ``None`` if any operator
        overflowed, else the collected relation and the run."""
        run = _Run(cap)
        rel = self._eval_node(plan.root, run)
        with span("engine.readback"):
            overflowed = any(np.any(f) for f in jax.device_get(run.overflow))
        if overflowed:
            return None
        with span("engine.collect"):
            collect = self._program(("collect",), lambda: _collect_program(self.mesh))
            data, valid = collect(rel.data, rel.valid)
            data, valid = np.asarray(data), np.asarray(valid)
        return rel, data, valid, run

    def execute(self, plan: PhysicalPlan) -> ExecutionResult:
        if _has_algebra_nodes(plan.root):
            # degrade, don't die: the SPMD kernels are conjunctive-only
            # (``_eval_node`` still raises -- that contract is pinned), so
            # OPTIONAL/UNION/FILTER plans run on the host engine with the
            # substitution named on the result instead of surfacing a bare
            # NotImplementedError to serving code
            from repro.engine.local import LocalEngine

            warnings.warn(
                "SPMD engine received an OPTIONAL/UNION/FILTER plan; "
                "degrading to LocalEngine (result.fallback = "
                "'local:algebra'; rows are identical, DistMetrics are not "
                "collected)", AlgebraFallbackWarning, stacklevel=2)
            res = LocalEngine(self.fed).execute(plan)
            return dataclasses.replace(res, fallback="local:algebra")
        # every pattern contributes at most its subject and object columns
        bound = self.max_cap(2 * len(plan.query.patterns))
        key = query_signature(plan.query)[0]
        cap, retries, retry_ms = min(self._caps.get(key, self.cap), bound), 0, 0.0
        while True:
            t0 = time.perf_counter()
            with span("engine.attempt", cap=cap) as attempt:
                got = self._attempt(plan, cap)
                attempt.set_metadata(overflowed=int(got is None))
            if got is not None:
                break
            # an overflowed attempt ends at its overflow readback, so its
            # device time is in here
            retry_ms += (time.perf_counter() - t0) * 1e3
            if 2 * cap > bound:
                raise CapacityExceededError(
                    f"plan for {plan.query.name or 'query'} overflows at "
                    f"capacity {cap} rows per shard, and {2 * cap} exceeds "
                    f"the device-memory bound of {bound}")
            cap *= 2
            retries += 1
        self._caps[key] = cap
        with span("engine.assemble"):
            out, n_shipped = self._assemble(plan, *got)
        metrics = DistMetrics(transferred_tuples=n_shipped, capacity=cap,
                              capacity_retries=retries, retry_ms=retry_ms)
        return ExecutionResult(rows=out, metrics=metrics, plan=plan,
                               stats_epoch=plan.stats_epoch)

    @staticmethod
    def _assemble(plan: PhysicalPlan, rel: DistRelation, data: np.ndarray,
                  valid: np.ndarray, run: _Run) -> tuple[dict, int]:
        """The answer's projected rows from the collected relation, and the
        rows the run shipped."""
        rows = data.reshape(-1, len(rel.columns))[valid.reshape(-1)]
        first: dict[str, int] = {}
        for j, c in enumerate(rel.columns):
            if c is None:
                continue
            if c in first:
                rows = rows[rows[:, first[c]] == rows[:, j]]
            else:
                first[c] = j
        proj = plan.query.effective_projection()
        out: dict[str, np.ndarray] = {v: rows[:, first[v]] for v in proj}
        if plan.query.distinct and len(rows):
            stacked = np.stack([out[v] for v in proj], axis=1)
            _, idx = np.unique(stacked, axis=0, return_index=True)
            out = {v: out[v][np.sort(idx)] for v in proj}
        n_shipped = sum(int(np.ravel(s)[0]) for s in jax.device_get(run.shipped))
        return out, n_shipped


# ---------------------------------------------------------------------------
# dry-run lowering (no data, ShapeDtypeStructs only)
# ---------------------------------------------------------------------------

def fed_dryrun_lower(mesh: Mesh, cap: int = 8192, table_cap: int = 1 << 20,
                     n_pat1: int = 3, n_pat2: int = 2, optimized: bool = False,
                     n_sources: int | None = None):
    """Lower the canonical federated query step (two star scans + distributed
    hash join + collect) on an abstract federation of ``n_sources``
    endpoints (default: one per data shard) in ``DistributedEngine``'s
    layout, ``table_cap`` triples per (source, model) shard.  Returns the
    jax ``Lowered`` artifact.

    On the multi-pod mesh the engine replicates across the ``pod`` axis
    (independent query streams); tables and relations shard over
    (data, model) exactly as single-pod.
    """
    n_slots = source_slots(n_sources or mesh.shape["data"], mesh)
    m = mesh.shape["model"]
    sds = jax.ShapeDtypeStruct
    sharded = NamedSharding(mesh, _SHARDED)
    replicated = NamedSharding(mesh, P())
    tables_s = sds((n_slots, m, table_cap, 3), jnp.int32, sharding=sharded)
    trow_s = sds((n_slots, m, table_cap), jnp.bool_, sharding=sharded)
    on_s = sds((n_slots,), jnp.bool_, sharding=NamedSharding(mesh, P("data")))
    pat1_s = sds((n_pat1, 3), jnp.int32, sharding=replicated)
    pat2_s = sds((n_pat2, 3), jnp.int32, sharding=replicated)

    star1 = _star_program(mesh, n_pat1, cap)
    star2 = _star_program(mesh, n_pat2, cap)
    # optimized: the right star joins on its own subject, which is exactly its
    # model-axis partition key — skip the model gather of the build side
    exchange = _exchange_program(mesh, cap, right_partitioned=optimized)
    collect = _collect_program(mesh)

    def fed_query_step(tables, trow, on1, pat1, on2, pat2):
        r1, v1, o1 = star1(tables, trow, on1, pat1)
        r2, v2, o2 = star2(tables, trow, on2, pat2)
        out, ov, o3, shipped = exchange(r1, v1, r2, v2,
                                        jnp.int32(1), jnp.int32(0))
        rows, valid = collect(out, ov)
        return rows, valid, (o1 | o2 | o3), shipped

    return jax.jit(fed_query_step).lower(tables_s, trow_s, on_s, pat1_s,
                                         on_s, pat2_s)
