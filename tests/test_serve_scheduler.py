"""Continuous-batching serving scheduler (``repro.serve``): shape-affine
deadline-driven admission, backpressure at the watermark, plan/execute
pipeline overlap, streaming completion, and per-request planning
attribution.  Scheduling is pure policy — the pipeline-vs-sync differential
pins down that it can never change answers."""
import threading
import time

import pytest

from repro.core.batch_planner import (
    AFFINITY_TIERS,
    AffinityKey,
    BatchPlanReport,
    plan_affinity,
)
from repro.engine.local import ExecutionResult, LocalEngine, naive_evaluate
from repro.serve import (
    AdmissionController,
    ArrivalQueue,
    BackpressureError,
    QueryServeEngine,
    ServeBase,
    ServeStats,
)

from benchmarks.planner_bench import object_variants, subject_variants


class FakeClock:
    """Deterministic engine clock: tests advance ``t`` by hand."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class _Req:
    """Minimal request for controller-level tests."""

    def __init__(self, qid: int, deadline: float = 100.0):
        self.qid = qid
        self.deadline = deadline


def _key(sig, sel=None, pr=None, sh=None) -> AffinityKey:
    return AffinityKey(signature=(sig,), selection=None if sel is None else (sel,),
                       pricing=None if pr is None else (pr,),
                       shape=None if sh is None else (sh,))


# -- admission controller: deepest shared tier wins ---------------------------

def test_admission_matches_deepest_shared_tier():
    ac = AdmissionController(max_group=8)
    assert ac.add(_Req(0), _key("a", "s1", "p1", "h1"), 10.0) is None
    # one shared tier each, from deepest to shallowest — all land in group 0
    assert ac.add(_Req(1), _key("a", "s9", "p9", "h9"), 10.0) == "signature"
    assert ac.add(_Req(2), _key("b", "s1", "p8", "h8"), 10.0) == "selection"
    assert ac.add(_Req(3), _key("c", "s7", "p1", "h7"), 10.0) == "pricing"
    assert ac.add(_Req(4), _key("d", "s6", "p6", "h1"), 10.0) == "shape"
    # nothing shared: a new group
    assert ac.add(_Req(5), _key("e", "s5", "p5", "h5"), 20.0) is None
    assert len(ac) == 6
    batch, reason = ac.next_batch(now=0.0, force=True)
    assert reason == "forced"
    assert [r.qid for r in batch] == [0, 1, 2, 3, 4]
    batch2, _ = ac.next_batch(now=0.0, force=True)
    assert [r.qid for r in batch2] == [5]
    assert len(ac) == 0 and ac.next_batch(0.0, force=True) is None


def test_admission_deeper_tier_beats_shallower_group():
    """When two open groups match at different tiers the deepest wins: a
    signature match outranks a shape match regardless of group age."""
    ac = AdmissionController(max_group=8)
    ac.add(_Req(0), _key("a", "s1", "p1", "h1"), 10.0)     # old group, shape h1
    ac.add(_Req(1), _key("b", "s2", "p2", "h2"), 10.0)     # young group, sig b
    assert ac.add(_Req(2), _key("b", "s3", "p3", "h1"), 10.0) == "signature"
    batch, _ = ac.next_batch(0.0, force=True)
    assert [r.qid for r in batch] == [0]                   # group 0 is alone


def test_admission_full_group_flushes_before_deadline():
    ac = AdmissionController(max_group=2)
    ac.add(_Req(0), _key("a"), flush_at=1e9)
    assert not ac.ripe(now=0.0)
    ac.add(_Req(1), _key("a"), flush_at=1e9)
    assert ac.ripe(now=0.0)
    batch, reason = ac.next_batch(now=0.0)
    assert reason == "full" and [r.qid for r in batch] == [0, 1]


def test_admission_overflow_remainder_keeps_urgency():
    """A group larger than max_group flushes in chunks; the remainder's
    flush_at re-derives from the members left behind."""
    ac = AdmissionController(max_group=2)
    for qid, dl in enumerate((5.0, 7.0, 9.0)):
        ac.add(_Req(qid, deadline=dl), _key("a"), flush_at=dl)
    batch, reason = ac.next_batch(now=0.0)       # full: first two members
    assert reason == "full" and [r.qid for r in batch] == [0, 1]
    assert ac.next_flush_at() == 9.0             # not the flushed 5.0
    assert ac.next_batch(now=8.0) is None        # not ripe yet
    batch2, reason2 = ac.next_batch(now=9.5)
    assert reason2 == "deadline" and [r.qid for r in batch2] == [2]


def test_arrival_queue_is_fifo():
    aq = ArrivalQueue(max_group=2)
    for qid in range(3):
        aq.add(_Req(qid, deadline=50.0), None, flush_at=50.0)
    assert len(aq) == 3
    batch, reason = aq.next_batch(now=0.0)
    assert reason == "full" and [r.qid for r in batch] == [0, 1]
    assert aq.next_batch(now=0.0) is None
    batch2, reason2 = aq.next_batch(now=60.0)
    assert reason2 == "deadline" and [r.qid for r in batch2] == [2]


# -- engine: deadline-driven flush under a fake clock -------------------------

def test_deadline_flush_without_full_group(tiny_fed, tiny_stats, tiny_workload):
    fed, _ = tiny_fed
    clk = FakeClock()
    eng = QueryServeEngine(fed, tiny_stats, max_batch=64, clock=clk)
    req = eng.submit(tiny_workload[0], deadline=5.0)
    assert req.deadline == 5.0 and req.slo == 5.0
    assert eng.poll() == []                       # t=0: SLO budget not spent
    assert len(eng.queue) == 1
    clk.t = 4.9
    assert eng.poll() == []
    clk.t = 5.1
    done = eng.poll()
    assert [r.qid for r in done] == [req.qid]
    assert req.done and req.rows is not None
    assert eng.serve_stats.n_deadline_flushes == 1
    assert eng.serve_stats.n_full_flushes == 0
    assert eng.serve_stats.n_forced_flushes == 0


def test_group_flushes_at_earliest_member_deadline(tiny_fed, tiny_stats,
                                                   tiny_workload):
    """A late-arriving urgent request drags its whole affinity group forward:
    the group flushes as one batch at the earliest member deadline."""
    fed, _ = tiny_fed
    clk = FakeClock()
    eng = QueryServeEngine(fed, tiny_stats, max_batch=64, clock=clk)
    q = tiny_workload[0]
    lazy = eng.submit(q, deadline=50.0)
    urgent = eng.submit(q, deadline=2.0)
    assert urgent.affinity_tier == "signature"
    clk.t = 2.5
    done = eng.poll()
    assert {r.qid for r in done} == {lazy.qid, urgent.qid}
    assert eng.serve_stats.n_steps == 1           # one batch, one flush
    assert eng.serve_stats.n_deadline_flushes == 1


def test_full_batch_flushes_immediately(tiny_fed, tiny_stats, tiny_workload):
    fed, _ = tiny_fed
    clk = FakeClock()
    eng = QueryServeEngine(fed, tiny_stats, max_batch=2, clock=clk)
    q = tiny_workload[0]
    r0 = eng.submit(q, deadline=1e6)
    assert eng.poll() == []
    r1 = eng.submit(q, deadline=1e6)
    done = eng.poll()                             # t=0, deadlines far away
    assert {r.qid for r in done} == {r0.qid, r1.qid}
    assert eng.serve_stats.n_full_flushes == 1
    assert eng.serve_stats.n_deadline_flushes == 0


def test_engine_affinity_tiers_on_real_queries(tiny_fed, tiny_stats,
                                               tiny_workload):
    """submit() reports the tier a request joined its group at, and it is
    exactly the deepest tier where the affinity keys agree."""
    fed, _ = tiny_fed
    variants = None
    for q in tiny_workload:
        if len(q.patterns) < 2:
            continue
        ov, sv = object_variants(q, fed, 1), subject_variants(q, fed, 1)
        if ov and sv:
            variants = [q, ov[0], sv[0]]
            break
    assert variants, "workload must yield a templatable query"
    clk = FakeClock()
    eng = QueryServeEngine(fed, tiny_stats, max_batch=64, clock=clk)
    seen_keys: list = []

    def deepest_shared(kv):
        # the controller's contract: the first (deepest) tier whose key any
        # earlier request has registered
        for name, key in kv.tier_keys():
            if any(getattr(k, name) == key for k in seen_keys):
                return name
        return None

    reqs = []
    for v in [variants[0], variants[0]] + variants[1:]:
        kv = plan_affinity(v)
        want = deepest_shared(kv)
        req = eng.submit(v, deadline=100.0)
        assert req.affinity_tier == want, v.name
        assert req.affinity_tier is None or req.affinity_tier in AFFINITY_TIERS
        seen_keys.append(kv)
        reqs.append(req)
    assert reqs[0].affinity_tier is None          # founded the group
    assert reqs[1].affinity_tier == "signature"   # exact duplicate
    assert any(r.affinity_tier in ("selection", "pricing", "shape")
               for r in reqs[2:]), "a variant must share a sub-signature tier"
    n_groups = sum(1 for r in reqs if r.affinity_tier is None)
    clk.t = 200.0
    done = eng.poll()                             # one batch per group
    assert len(done) == len(reqs)
    assert eng.serve_stats.n_steps == n_groups


# -- exactly-once streaming ---------------------------------------------------

def test_poll_never_reports_a_request_twice(tiny_fed, tiny_stats,
                                            tiny_workload):
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats, max_batch=4)
    reqs = [eng.submit(q, deadline=0.0) for q in tiny_workload]
    seen: list[int] = []
    for _ in range(50):
        seen.extend(r.qid for r in eng.poll())
        if len(seen) == len(reqs):
            break
    assert sorted(seen) == [r.qid for r in reqs]
    assert eng.poll() == []                        # drained: nothing new
    assert eng.drain() == []
    assert len(eng.finished) == len(reqs)          # cumulative history stays


def test_completed_iterator_streams_each_once(tiny_fed, tiny_stats,
                                              tiny_workload):
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats, max_batch=4)
    reqs = [eng.submit(q, deadline=0.0) for q in tiny_workload]
    seen = [r.qid for r in eng.completed()]
    assert sorted(seen) == [r.qid for r in reqs]
    assert list(eng.completed()) == []


def test_mixed_step_and_poll_report_disjoint(tiny_fed, tiny_stats,
                                             tiny_workload):
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats, max_batch=2)
    reqs = [eng.submit(q, deadline=0.0) for q in tiny_workload[:6]]
    a = eng.step()
    b = eng.poll()
    c = eng.drain()
    qids = [r.qid for r in a + b + c]
    assert sorted(qids) == [r.qid for r in reqs]
    assert len(set(qids)) == len(qids), "a request was reported twice"


# -- backpressure -------------------------------------------------------------

def test_backpressure_rejects_at_watermark(tiny_fed, tiny_stats,
                                           tiny_workload):
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats, max_batch=8, queue_depth=2,
                           backpressure="reject")
    eng.submit(tiny_workload[0])
    eng.submit(tiny_workload[1])
    with pytest.raises(BackpressureError, match="watermark"):
        eng.submit(tiny_workload[2])
    assert eng.serve_stats.n_rejected == 1
    assert len(eng.queue) == 2                     # the reject queued nothing
    eng.drain()
    eng.submit(tiny_workload[2])                   # space again after drain
    assert eng.drain()[0].rows is not None
    assert eng.serve_stats.n_rejected == 1


def test_backpressure_block_requires_pipeline(tiny_fed, tiny_stats):
    fed, _ = tiny_fed
    with pytest.raises(ValueError, match="pipeline"):
        QueryServeEngine(fed, tiny_stats, backpressure="block", pipeline=False)


def test_backpressure_block_unblocks_when_worker_drains(tiny_fed, tiny_stats,
                                                        tiny_workload):
    fed, _ = tiny_fed
    with QueryServeEngine(fed, tiny_stats, max_batch=4, queue_depth=1,
                          backpressure="block", pipeline=True,
                          handoff_depth=8) as eng:
        done: list = []
        for q in tiny_workload[:4]:
            eng.submit(q, deadline=0.0)            # instantly ripe
            done.extend(eng.poll())
        done.extend(eng.drain())
        assert len(done) == 4
        assert eng.serve_stats.n_blocked >= 1, \
            "queue_depth=1 must have blocked at least one submit"
        assert eng.serve_stats.n_rejected == 0


# -- pipeline overlap ---------------------------------------------------------

def test_pipeline_results_match_synchronous(tiny_fed, tiny_stats,
                                            tiny_workload):
    """The acceptance differential: per-request rows from the pipelined
    affinity engine are byte-identical to the synchronous step() loop (and
    to the ground-truth evaluator on a sample)."""
    fed, _ = tiny_fed
    wave = []
    for q in tiny_workload:
        wave.append(q)
        if len(q.patterns) >= 2:
            wave.extend(object_variants(q, fed, 2))
    wave.extend(tiny_workload[:3])                 # exact duplicates

    sync = QueryServeEngine(fed, tiny_stats, max_batch=8)
    for q in wave:
        sync.submit(q)
    while sync.queue:
        sync.step()
    by_qid_sync = {r.qid: r for r in sync.finished}

    with QueryServeEngine(fed, tiny_stats, max_batch=8, pipeline=True,
                          default_slo_ms=1.0) as pipe:
        reqs = [pipe.submit(q) for q in wave]
        done = list(pipe.completed())
    assert sorted(r.qid for r in done) == [r.qid for r in reqs]
    for r in done:
        s = by_qid_sync[r.qid]
        assert r.query is s.query
        assert set(r.rows) == set(s.rows)
        for v in r.rows:
            assert r.rows[v].tobytes() == s.rows[v].tobytes(), (r.qid, v)
        assert r.stats_epoch == s.stats_epoch
    # spot-check against ground truth on one multi-pattern request
    probe = next(r for r in done if len(r.query.patterns) >= 2)
    want = naive_evaluate(fed, probe.query)
    proj = probe.query.effective_projection()
    n = len(next(iter(probe.rows.values()))) if probe.rows else 0
    got = set(zip(*[probe.rows[v].tolist() for v in proj])) if n else set()
    assert got == want


def test_pipeline_drain_and_counters(tiny_fed, tiny_stats, tiny_workload):
    fed, _ = tiny_fed
    with QueryServeEngine(fed, tiny_stats, max_batch=4, pipeline=True) as eng:
        reqs = [eng.submit(q) for q in tiny_workload]
        done = eng.drain()
        assert sorted(r.qid for r in done) == [r.qid for r in reqs]
        assert eng.drain() == []                   # only-new contract holds
        stats = eng.serve_stats
        assert stats.n_served == len(reqs)
        assert stats.n_planned == eng.optimizer.plan_cache.misses
        flushes = (stats.n_full_flushes + stats.n_deadline_flushes
                   + stats.n_forced_flushes)
        assert flushes == stats.n_steps >= 1


def test_step_raises_in_pipeline_mode(tiny_fed, tiny_stats):
    fed, _ = tiny_fed
    with QueryServeEngine(fed, tiny_stats, pipeline=True) as eng:
        with pytest.raises(RuntimeError, match="poll"):
            eng.step()


def test_worker_death_surfaces_at_next_call(tiny_fed, tiny_stats,
                                            tiny_workload):
    """A planner-thread exception must reach the caller as a RuntimeError on
    the next submit()/poll()/drain() — never a silent thread traceback."""
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats, pipeline=True)
    boom = ValueError("planner exploded")

    def explode(queries):
        raise boom

    eng.optimizer.optimize_batch = explode
    eng.submit(tiny_workload[0], deadline=0.0)
    deadline = time.monotonic() + 5.0
    while eng._worker_error is None and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng._worker_error is boom
    with pytest.raises(RuntimeError, match="planner thread died") as ei:
        eng.poll()
    assert ei.value.__cause__ is boom
    with pytest.raises(RuntimeError, match="planner thread died"):
        eng.submit(tiny_workload[0])
    with pytest.raises(RuntimeError, match="planner thread died"):
        eng.drain()
    eng.close()


def test_close_is_idempotent_and_joins_worker(tiny_fed, tiny_stats,
                                              tiny_workload):
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats, pipeline=True)
    worker = eng._worker
    assert worker.is_alive()
    eng.close()
    assert not worker.is_alive()
    eng.close()                                    # idempotent
    assert threading.active_count() >= 1


# -- per-request planning attribution (the satellite bugfix) ------------------

def test_cache_hit_not_charged_batch_planning_window(tiny_fed, tiny_stats,
                                                     tiny_workload):
    """Regression: the shared ``t_planned = t1`` stamp charged plan-cache
    hits the whole batch's planning window.  A hit is charged its own
    rebind (``optimization_ms``), clamped into the batch window."""
    fed, _ = tiny_fed
    ticks = iter(float(i) for i in range(100))
    eng = QueryServeEngine(fed, tiny_stats, clock=lambda: next(ticks))
    reqs = [eng.submit(q) for q in tiny_workload[:3]]     # clock: 0, 1, 2

    class _P:
        def __init__(self, cached, ms):
            self.cached = cached
            self.optimization_ms = ms
            self.stats_epoch = 0

    plans = [_P(cached=False, ms=900.0),     # cold: full window
             _P(cached=True, ms=50.0),       # hit: its own 50ms rebind
             _P(cached=True, ms=5000.0)]     # degenerate ms: clamped to t1
    eng.optimizer.optimize_batch = lambda queries: plans
    eng.optimizer.last_batch_report = BatchPlanReport(
        n_queries=3, cache_hits=2, n_planned=1, n_shapes=1)
    eng._plan_batch(reqs)                    # clock: t0=3, t1=4
    assert reqs[0].t_planned == 4.0
    assert reqs[1].t_planned == pytest.approx(3.0 + 50.0 * 1e-3)
    assert reqs[2].t_planned == 4.0          # min(t0 + 5s, t1) clamps
    assert reqs[1].planning_latency_s() < reqs[0].planning_latency_s()
    assert reqs[1].plan_ms == 50.0
    assert eng.serve_stats.plan_ms == pytest.approx(1000.0)
    assert eng.serve_stats.plan_cache_hits == 2
    assert eng.serve_stats.n_planned == 1


def test_planning_attribution_end_to_end(tiny_fed, tiny_stats, tiny_workload):
    """With the real planner, an in-batch duplicate's attributed planning
    never exceeds the batch window charged to a cold member."""
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats, max_batch=8)
    q = next(q for q in tiny_workload if len(q.patterns) >= 2)
    cold = eng.submit(q, deadline=0.0)
    dup = eng.submit(q, deadline=0.0)
    eng.drain()
    assert not cold.cached and dup.cached
    assert dup.t_planned <= cold.t_planned
    assert dup.plan_ms <= cold.plan_ms
    assert dup.planning_latency_s() >= 0.0


# -- the unified surface ------------------------------------------------------

def test_query_engine_satisfies_serve_base(tiny_fed, tiny_stats):
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats)
    assert isinstance(eng, ServeBase)
    assert isinstance(eng.serve_stats, ServeStats)


def test_run_until_done_is_deprecated_wrapper(tiny_fed, tiny_stats,
                                              tiny_workload):
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats)
    req = eng.submit(tiny_workload[0])
    with pytest.warns(DeprecationWarning, match="drain"):
        done = eng.run_until_done()
    assert [r.qid for r in done] == [req.qid]


def test_engine_rejects_bad_modes(tiny_fed, tiny_stats):
    fed, _ = tiny_fed
    with pytest.raises(ValueError, match="admission"):
        QueryServeEngine(fed, tiny_stats, admission="lifo")
    with pytest.raises(ValueError, match="backpressure"):
        QueryServeEngine(fed, tiny_stats, backpressure="drop")
    with pytest.raises(ValueError, match="handoff_depth"):
        QueryServeEngine(fed, tiny_stats, pipeline=True, handoff_depth=0)


def test_arrival_admission_mode_still_serves(tiny_fed, tiny_stats,
                                             tiny_workload):
    """The legacy arrival-order policy stays available as the benchmark
    baseline and serves the same answers."""
    fed, _ = tiny_fed
    eng = QueryServeEngine(fed, tiny_stats, max_batch=4, admission="arrival")
    reqs = [eng.submit(q, deadline=0.0) for q in tiny_workload]
    done = eng.drain()
    assert sorted(r.qid for r in done) == [r.qid for r in reqs]
    assert all(r.affinity_tier is None for r in done)


# -- ExecutionResult (the API-redesign satellite) -----------------------------

def test_execution_result_fields_and_shim(tiny_fed, tiny_stats, tiny_workload):
    from repro.core.planner import OdysseyOptimizer

    fed, _ = tiny_fed
    plan = OdysseyOptimizer(tiny_stats).optimize(tiny_workload[0])
    res = LocalEngine(fed).execute(plan)
    assert isinstance(res, ExecutionResult)
    assert res.plan is plan
    assert res.stats_epoch == plan.stats_epoch
    assert res.metrics.requests >= 1 and res.metrics.wall_ms >= 0.0
    with pytest.warns(DeprecationWarning, match="rows, metrics"):
        rows, metrics = res
    assert rows is res.rows and metrics is res.metrics
    with pytest.raises(Exception):
        res.rows = {}                              # frozen


@pytest.mark.parametrize("pipeline", [False, True])
def test_request_stamps_follow_the_request(tiny_fed, tiny_stats, tiny_workload,
                                           pipeline):
    """Every request is stamped in the order it moves: submitted, flushed out
    of admission, planned, done; the admission wait of the served requests is
    summed on ``ServeStats``."""
    fed, _ = tiny_fed
    with QueryServeEngine(fed, tiny_stats, max_batch=4, pipeline=pipeline,
                          default_slo_ms=2.0) as eng:
        reqs = [eng.submit(q) for q in tiny_workload]
        done = eng.drain()
        assert sorted(r.qid for r in done) == [r.qid for r in reqs]
        for r in done:
            assert 0.0 < r.t_submit <= r.t_flushed <= r.t_planned <= r.t_done, r.qid
        assert eng.serve_stats.admission_wait_ms == pytest.approx(
            1e3 * sum(r.t_flushed - r.t_submit for r in done))
        # the local engine runs no capacity retries
        assert eng.serve_stats.retry_ms == 0.0
