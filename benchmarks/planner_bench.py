"""Planner micro-benchmark — the optimizer hot path.

Three measurements per star count (4-9 stars):
  * ``dp_join_order`` (vectorized bitmask DP + memoized statistics),
  * ``dp_join_order_ref`` (the seed's frozenset DP, unmemoized statistics),
  * uncached ``OdysseyOptimizer.optimize`` (plan cache off; statistics memos
    warm, as in steady-state serving) vs a plan-cache hit on the same query.

Benchmark queries are chains of linked stars synthesized from the CP
statistics themselves (each bridge predicate provably links two CSs; each
star is fleshed out with predicates that co-occur in the bridged CS), kept
only if source selection leaves >= 1 source per star.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import fixture, geomean
from repro.core.cost import CostModel
from repro.core.decomposition import decompose
from repro.core.join_order import dp_join_order, dp_join_order_ref
from repro.core.planner import OdysseyOptimizer, plans_equal
from repro.core.source_selection import select_sources
from repro.query.algebra import BGPQuery, Const, TriplePattern, Var

STAR_COUNTS = (4, 6, 7, 8, 9)
BATCH_SIZE = 64
MIN_BATCH_SPEEDUP = 3.0     # batched vs sequential planning, cold plan cache


def chain_query(stats, n_stars: int, k_extra: int, rng) -> BGPQuery:
    """Chain of ``n_stars`` star meta-nodes linked via CP-backed predicates."""
    pats: list[TriplePattern] = []
    cur = int(rng.integers(len(stats.cs)))
    last_cs = 0

    def outgoing(src: int):
        out = [(stats.intra_cp[src], src)] if stats.intra_cp[src].n_cp else []
        for (a, b), fcp in stats.fed_cp.items():
            if a == src and fcp.n_cp:
                out.append((fcp, b))
        return out

    for i in range(n_stars - 1):
        cand = outgoing(cur)
        if not cand:  # dead end: fall back to any source with outgoing CPs
            starts = [s for s in range(len(stats.cs)) if outgoing(s)]
            if not starts:
                raise RuntimeError("federation has no CP-linked sources")
            cur = starts[int(rng.integers(len(starts)))]
            cand = outgoing(cur)
        cp, nxt = cand[int(rng.integers(len(cand)))]
        r = int(rng.integers(cp.n_cp))
        pred, cs1, cs2 = int(cp.pred[r]), int(cp.cs1[r]), int(cp.cs2[r])
        extras = [int(p) for p in stats.cs[cur].preds_of(cs1) if int(p) != pred]
        rng.shuffle(extras)
        for j, p in enumerate(extras[:k_extra]):
            pats.append(TriplePattern(Var(f"x{i}"), Const(p), Var(f"x{i}_v{j}")))
        pats.append(TriplePattern(Var(f"x{i}"), Const(pred), Var(f"x{i + 1}")))
        cur, last_cs = nxt, cs2
    extras = [int(p) for p in stats.cs[cur].preds_of(last_cs)]
    for j, p in enumerate(extras[:k_extra]):
        pats.append(TriplePattern(Var(f"x{n_stars - 1}"), Const(p),
                                  Var(f"x{n_stars - 1}_v{j}")))
    return BGPQuery(pats, distinct=True, projection=["x0"], name=f"CH{n_stars}")


def planner_query(stats, n_stars: int, seed: int, k_extra: int = 3) -> BGPQuery:
    """A chain query whose stars all survive source selection."""
    rng = np.random.default_rng(seed)
    for _ in range(80):
        q = chain_query(stats, n_stars, k_extra, rng)
        graph = decompose(q)
        sel = select_sources(graph, stats)
        if len(graph.stars) == n_stars and all(len(s) for s in sel.star_sources):
            return q
    return q  # degenerate fallback: still a valid planning workload


def _median_ms(fn, reps: int) -> float:
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


# -- batch scenario: template instantiation ----------------------------------

def object_variants(q: BGPQuery, fed, k: int) -> list[BGPQuery]:
    """``k`` instances of ``q`` differing only in a constant object bound to
    a non-link pattern — the FedBench-style templated workload: same shape,
    same pricing, distinct signatures."""
    from repro.core.decomposition import decompose

    g = decompose(q)
    structural = {e.var for e in g.edges if e.var}
    structural |= {s.subject.name for s in g.stars if isinstance(s.subject, Var)}
    structural |= set(q.projection)
    for star in reversed(g.stars):
        for tp in star.patterns:
            if isinstance(tp.p, Const) and isinstance(tp.o, Var) \
                    and tp.o.name not in structural \
                    and not any(e.pattern is tp for e in g.edges):
                objs = sorted({int(o) for src in fed.sources
                               for o in np.unique(src.table.o[src.table.p == tp.p.tid])})
                if len(objs) >= 2:
                    return [BGPQuery([TriplePattern(p.s, p.p,
                                                    Const(objs[j % len(objs)])
                                                    if p is tp else p.o)
                                      for p in q.patterns], distinct=q.distinct,
                                     projection=q.projection,
                                     name=f"{q.name}o{j}")
                            for j in range(k)]
    return []


def subject_variants(q: BGPQuery, fed, k: int) -> list[BGPQuery]:
    """``k`` instances of ``q`` with the first star's subject bound to
    different entities: same shape, but distinct selections and estimates —
    these become real stacked members of the shape's DP sweep."""
    from repro.core.decomposition import decompose

    g = decompose(q)
    star = g.stars[0]
    if not isinstance(star.subject, Var):
        return []
    name = star.subject.name
    if any(isinstance(tp.o, Var) and tp.o.name == name
           for st in g.stars for tp in st.patterns):
        return []
    proj = [v for v in q.projection if v != name] or \
        [v for s in g.stars[1:] if isinstance(s.subject, Var)
         for v in (s.subject.name,)][:1]
    if not proj:
        return []
    preds = set(star.bound_preds())
    out: list[BGPQuery] = []
    seen: set[int] = set()
    for src in fed.sources:
        t = src.table
        for sid in np.unique(t.s):
            sid = int(sid)
            if sid not in seen and preds <= set(t.p[t.s == sid].tolist()):
                seen.add(sid)
                pats = [TriplePattern(Const(sid) if isinstance(p.s, Var)
                                      and p.s.name == name else p.s, p.p, p.o)
                        for p in q.patterns]
                out.append(BGPQuery(pats, distinct=q.distinct, projection=proj,
                                    name=f"{q.name}s{sid}"))
                if len(out) >= k:
                    return out
    return out


def batch_workload(stats, fed, size: int = BATCH_SIZE) -> list[BGPQuery]:
    """A mixed-shape, cold-cache planning batch: several star counts, object-
    constant template instances, subject-constant instances (distinct
    selections within a shape) and some exact duplicates."""
    q3 = planner_query(stats, 3, seed=101, k_extra=3)
    q4 = planner_query(stats, 4, seed=202, k_extra=3)
    q5 = planner_query(stats, 5, seed=303, k_extra=3)
    q6 = planner_query(stats, 6, seed=404, k_extra=3)
    base: list[BGPQuery] = []
    base += object_variants(q4, fed, 16)
    base += subject_variants(q5, fed, 12)
    base += object_variants(q6, fed, 12)
    base += [q3] * 8
    base += [q3, q4, q5, q6]
    batch = list(base)
    while len(batch) < size:
        batch.append(base[len(batch) % len(base)])
    return batch[:size]


def run_batch(scale: float = 1.0, size: int = BATCH_SIZE, reps: int = 5,
              assert_speedup: bool = False):
    """The truly-batched planning scenario: a ``size``-query mixed-shape
    batch planned cold (plan cache off on both sides, statistics memos warm
    as in steady-state serving) — ``optimize_batch`` vs the sequential
    ``optimize`` loop.  Verifies per-query plan equality, reports the
    throughput multiple, and (under ``assert_speedup``, the CI smoke) fails
    hard below ``MIN_BATCH_SPEEDUP``."""
    fed, gt, stats, _ = fixture(scale)
    batch = batch_workload(stats, fed, size)

    # steady-state: formula-level memos warm for both sides, plan caches off
    OdysseyOptimizer(stats, plan_cache_size=0).optimize_batch(batch)

    def loop():
        opt = OdysseyOptimizer(stats, plan_cache_size=0)
        return [opt.optimize(q) for q in batch]

    rep_holder = {}

    def batched():
        opt = OdysseyOptimizer(stats, plan_cache_size=0)
        plans = opt.optimize_batch(batch)
        rep_holder["report"] = opt.last_batch_report
        return plans

    plans_l, plans_b = loop(), batched()
    for q, a, b in zip(batch, plans_l, plans_b):
        assert plans_equal(a, b), f"batched plan differs from loop: {q.name}"

    loop_ms = _median_ms(loop, reps)
    batch_ms = _median_ms(batched, reps)
    speedup = loop_ms / max(batch_ms, 1e-9)
    r = rep_holder["report"]
    text = "\n".join([
        "== Batched planning (optimize_batch vs sequential loop, cold cache) ==",
        f"batch {len(batch)} queries: {r.n_shapes} shapes, {r.n_priced} priced "
        f"DP members, {r.n_selections} selection fixpoints, "
        f"{r.duplicates} duplicates",
        f"sequential loop : {loop_ms:9.2f} ms  ({loop_ms / len(batch):.3f} ms/query)",
        f"optimize_batch  : {batch_ms:9.2f} ms  ({batch_ms / len(batch):.3f} ms/query)",
        f"planning throughput: {speedup:.1f}x (target >= {MIN_BATCH_SPEEDUP}x)",
    ])
    csv = [
        (f"planner/batch{len(batch)}_loop_us", loop_ms * 1e3 / len(batch),
         f"{loop_ms:.1f}ms_total"),
        (f"planner/batch{len(batch)}_batched_us", batch_ms * 1e3 / len(batch),
         f"{speedup:.1f}x_vs_loop"),
    ]
    metrics = {"batch_throughput_x": speedup}
    if assert_speedup and speedup < MIN_BATCH_SPEEDUP:
        raise SystemExit(
            f"batched planning regression: optimize_batch is only "
            f"{speedup:.1f}x the sequential loop at batch {len(batch)} "
            f"(need >= {MIN_BATCH_SPEEDUP}x)\n{text}")
    return csv, text, metrics


def run(scale: float = 1.0, reps: int = 9, seeds_per_size: int = 2):
    fed, gt, stats, _ = fixture(scale)
    cm = CostModel()
    csv: list[tuple] = []
    lines = ["== Planner micro-benchmark (bitmask DP vs reference DP) ==",
             f"{'query':8}{'stars':>6}{'bitmask ms':>12}{'ref ms':>10}"
             f"{'speedup':>9}{'cold ms':>9}{'hit ms':>9}{'cache x':>9}"]
    speedups_6plus = []
    cache_ratios = []
    for n in STAR_COUNTS:
        for si in range(seeds_per_size):
            q = planner_query(stats, n, seed=170 + n + 300 * si, k_extra=4)
            graph = decompose(q)
            if len(graph.stars) != n:       # degenerate fallback query: the
                continue                    # >=6-star numbers must not shrink
            sel = select_sources(graph, stats)
            new_tree = dp_join_order(graph, stats, sel, cm, q.distinct)   # warm
            ref_tree = dp_join_order_ref(graph, stats, sel, cm, q.distinct)
            assert new_tree.leaf_order() == ref_tree.leaf_order()
            assert np.isclose(new_tree.cost, ref_tree.cost, rtol=1e-9)
            new_ms = _median_ms(lambda: dp_join_order(graph, stats, sel, cm, q.distinct), reps)
            ref_ms = _median_ms(lambda: dp_join_order_ref(graph, stats, sel, cm, q.distinct), reps)

            cold_opt = OdysseyOptimizer(stats, plan_cache_size=0)
            cold_ms = _median_ms(lambda: cold_opt.optimize(q), reps)
            hot_opt = OdysseyOptimizer(stats)
            hot_opt.optimize(q)                                           # fill cache
            hit_ms = _median_ms(lambda: hot_opt.optimize(q), reps)

            speedup = ref_ms / max(new_ms, 1e-9)
            cache_x = ref_ms / max(hit_ms, 1e-9)
            if n >= 6:
                speedups_6plus.append(speedup)
                cache_ratios.append(cache_x)
            name = f"{q.name}.{si}"
            lines.append(f"{name:8}{n:>6}{new_ms:>12.3f}{ref_ms:>10.3f}"
                         f"{speedup:>8.1f}x{cold_ms:>9.3f}{hit_ms:>9.4f}{cache_x:>8.0f}x")
            csv.append((f"planner/bitmask_dp_{n}star_{si}", new_ms * 1e3,
                        f"{speedup:.1f}x_vs_ref"))
            csv.append((f"planner/plan_cache_hit_{n}star_{si}", hit_ms * 1e3,
                        f"{cache_x:.0f}x_vs_ref"))
    metrics = {}
    if speedups_6plus:
        metrics = {"planner_geomean_speedup_x": geomean(speedups_6plus),
                   "planner_cache_hit_x": geomean(cache_ratios)}
        lines.append(f"geomean speedup (>=6 stars): {geomean(speedups_6plus):.1f}x "
                     f"(target >=5x); cached re-plan {geomean(cache_ratios):.0f}x "
                     f"(target >=50x)")
    else:
        lines.append("no >=6-star queries survived source selection at this scale")
    return csv, "\n".join(lines), metrics


def run_dp_backends(reps: int = 3, batch: int = 8):
    """Guarded jax-vs-numpy sweep comparison: one shape group planned
    through ``dp_join_order_batch`` with ``dp_backend='numpy'`` (in-process
    array ops) and ``dp_backend='jax'`` (the device-resident
    ``repro.kernels.dp_layer`` sweep program: the whole layer schedule runs
    as one XLA-compiled ``lax.scan`` over the B=batch member stack, so per
    planning call the host pays one dispatch instead of a per-layer
    round-trip).  Sized at the n=12 / B=8 point the resident path is built
    for — large enough that the fused device program beats numpy even on
    CPU.  Verifies the two backends return bit-identical plans, then
    reports ``dp_sweep_jax_vs_numpy_x`` (= numpy_ms / jax_ms) into
    ``results/bench_quick.json``; the CI gate holds it above a hard floor
    of 1.0 — the jax backend regressing to slower-than-numpy fails CI."""
    from repro.core.join_order import dp_join_order_batch
    from repro.rdf.shapes import shaped_planning_inputs

    cm = CostModel()
    graph, stats, sel, q = shaped_planning_inputs("tree", 12, seed=41)
    graphs, sels = [graph] * batch, [sel] * batch

    def sweep(backend):
        return dp_join_order_batch(graphs, stats, sels, cm, q.distinct,
                                   dp_backend=backend)

    def fingerprint(t):
        out = [(t.kind, t.strategy, tuple(sorted(t.stars)), t.cost,
                t.cardinality, tuple(t.sources) if t.sources else None)]
        if t.left is not None:
            out += fingerprint(t.left) + fingerprint(t.right)
        return out

    trees_np, trees_jx = sweep("numpy"), sweep("jax")   # warm memos + jit
    for a, b in zip(trees_np, trees_jx):
        assert fingerprint(a) == fingerprint(b), \
            "jax DP backend diverged from numpy plans"
    np_ms = _median_ms(lambda: sweep("numpy"), reps)
    jx_ms = _median_ms(lambda: sweep("jax"), reps)
    ratio = np_ms / max(jx_ms, 1e-9)
    import jax

    jax.clear_caches()      # the x64 sweep programs are one-shot in a bench
                            # run; don't carry them under the peak-RSS guard
    text = "\n".join([
        "== DP sweep backends (dp_join_order_batch, one shape group) ==",
        f"{q.name} x{batch} members: numpy {np_ms:.2f} ms, jax (resident "
        f"sweep program) {jx_ms:.2f} ms -> jax/numpy {ratio:.3f}x",
        "guarded: the gate requires the resident jax sweep to beat numpy "
        "(hard floor 1.0)",
    ])
    csv = [(f"planner/dp_sweep_numpy_b{batch}", np_ms * 1e3, "numpy_backend"),
           (f"planner/dp_sweep_jax_b{batch}", jx_ms * 1e3,
            f"{ratio:.3f}x_vs_numpy")]
    return csv, text, {"dp_sweep_jax_vs_numpy_x": ratio}


def run_large(quick: bool = False, reps: int = 3):
    """Large-star scaling: the chunked + connected bitmask DP on synthetic
    chains / trees / cliques past the old 14-star ``MAX_BITMASK_STARS``
    cliff, with a reference-DP comparison at the largest size the reference
    can run in bench time (acceptance: >= 3x at >= 14 stars) and the traced
    peak of the DP's allocations (budget: ``DP_BLOCK_BYTES``)."""
    import tracemalloc

    from repro.core.join_order import DP_BLOCK_BYTES
    from repro.rdf.shapes import shaped_planning_inputs

    cm = CostModel()
    ref_n = 12 if quick else 14
    scenarios = ((("chain", (12, 14, 16)), ("tree", (14,)), ("clique", (12,)))
                 if quick else
                 (("chain", (14, 16, 18)), ("tree", (14, 16)), ("clique", (12, 14))))
    lines_note = "no reference comparison ran"
    csv: list[tuple] = []
    lines = ["== Large-star planner scaling (chunked + connected bitmask DP) ==",
             f"{'query':10}{'stars':>6}{'bitmask ms':>12}{'peak MB':>9}"
             f"{'ref ms':>10}{'speedup':>9}"]
    for shape, sizes in scenarios:
        for n in sizes:
            graph, stats, sel, q = shaped_planning_inputs(shape, n, seed=29 + n)
            dp_join_order(graph, stats, sel, cm, q.distinct)      # warm memos
            tracemalloc.start()
            tree = dp_join_order(graph, stats, sel, cm, q.distinct)
            _, peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            peak_mb = peak / 2**20
            assert peak <= DP_BLOCK_BYTES + (1 << 26), \
                f"{shape}{n}: traced peak {peak_mb:.0f} MB blew the tile budget"
            new_ms = _median_ms(
                lambda: dp_join_order(graph, stats, sel, cm, q.distinct), reps)
            row = f"{q.name:10}{n:>6}{new_ms:>12.2f}{peak_mb:>9.1f}"
            derived = f"peak_{peak_mb:.1f}MB"
            if shape == "chain" and n == ref_n:
                t0 = time.perf_counter()
                ref = dp_join_order_ref(graph, stats, sel, cm, q.distinct)
                ref_ms = (time.perf_counter() - t0) * 1e3
                assert tree.leaf_order() == ref.leaf_order()
                assert np.isclose(tree.cost, ref.cost, rtol=1e-9)
                speedup = ref_ms / max(new_ms, 1e-9)
                row += f"{ref_ms:>10.1f}{speedup:>8.1f}x"
                derived = f"{speedup:.1f}x_vs_ref_{derived}"
                lines_note = (f"{ref_n}-star chain speedup vs reference DP: "
                              f"{speedup:.1f}x (target >= 3x)")
            lines.append(row)
            csv.append((f"planner/large_{shape}_{n}star", new_ms * 1e3, derived))
    lines.append(lines_note)
    return csv, "\n".join(lines)


if __name__ == "__main__":
    import sys

    csv, text, _ = run(scale=0.25)
    csv_b, text_b, _ = run_batch(scale=0.25, assert_speedup=True)
    csv_l, text_l = run_large(quick=True)
    print(text + "\n\n" + text_b + "\n\n" + text_l, file=sys.stderr)
    for name, us, derived in csv + csv_b + csv_l:
        print(f"{name},{us:.3f},{derived}")
