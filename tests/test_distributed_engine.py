"""Distributed shard_map engine == the exact oracle.

The multi-device meshes run in a subprocess so the fake-device XLA flag never
contaminates this process (DESIGN.md dry-run rule); the one-device ``(1, 1)``
mesh, which holds all nine sources of the FedBench-shaped federation on one
device, runs in-process.
"""
import os
import subprocess
import sys
from collections import Counter

import jax
import numpy as np
import pytest

from repro.core.planner import OdysseyOptimizer
from repro.engine.distributed import CapacityExceededError, DistributedEngine
from repro.engine.local import naive_evaluate
from repro.query.algebra import BGPQuery, TriplePattern, Var
from repro.rdf.generator import generate_workload

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow  # multi-minute: subprocess + XLA compilation
@pytest.mark.parametrize("mesh", [(2, 2), (4, 2)])
def test_distributed_matches_local(mesh):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dist_selftest", str(mesh[0]), str(mesh[1])],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-2000:]}"
    assert "queries OK" in out.stdout


def _one_device_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def _answers(res, q):
    """The projected rows as a multiset: without DISTINCT, row counts are
    part of the answer."""
    proj = q.effective_projection()
    return Counter(zip(*[res.rows[v].tolist() for v in proj]))


@pytest.fixture(scope="module")
def one_device(tiny_fed, tiny_stats):
    fed, gt = tiny_fed
    queries = [q for q in generate_workload(fed, gt, n_star=4, n_hybrid=4,
                                            n_path=2, seed=5)]
    return fed, queries, OdysseyOptimizer(tiny_stats)


def test_one_device_holds_full_federation(one_device):
    """All nine sources on a (1, 1) mesh: every conjunctive answer equals
    the oracle's, row counts included."""
    fed, queries, opt = one_device
    eng = DistributedEngine(fed, _one_device_mesh(), cap=1024)
    assert len(fed.sources) == 9 and eng.n_slots == 9
    assert eng.n_triples == sum(len(s.table) for s in fed.sources)
    n_run = 0
    for q in queries:
        plan = opt.optimize(q)
        if plan.fallback:
            continue
        res = eng.execute(plan)
        assert res.fallback is None
        assert _answers(res, q) == naive_evaluate(fed, q, bag=True), q.name
        n_run += 1
    assert n_run >= 8
    assert any(not q.distinct for q in queries)


def test_overflow_retries_to_oracle_answer(one_device):
    """A first capacity far below the answer: execute doubles it until no
    operator overflows and returns the oracle's answer, never a prefix."""
    fed, queries, opt = one_device
    eng = DistributedEngine(fed, _one_device_mesh(), cap=8)
    q = max(queries, key=lambda q: len(naive_evaluate(fed, q)))
    want = naive_evaluate(fed, q, bag=True)
    assert want.total() > 8
    res = eng.execute(opt.optimize(q))
    assert res.metrics.capacity_retries >= 1
    assert res.metrics.capacity == 8 << res.metrics.capacity_retries
    assert _answers(res, q) == want
    # the grown capacity is kept: the same plan again needs no retry
    again = eng.execute(opt.optimize(q))
    assert again.metrics.capacity_retries == 0
    assert again.metrics.capacity == res.metrics.capacity
    assert _answers(again, q) == want


def test_grown_capacity_stays_with_its_query(one_device, monkeypatch):
    """The capacity one query grew to is not where a wider query starts,
    and no query starts above its own memory bound."""
    fed, queries, opt = one_device
    eng = DistributedEngine(fed, _one_device_mesh(), cap=8)
    big = max(queries, key=lambda q: len(naive_evaluate(fed, q)))
    first = eng.execute(opt.optimize(big)).metrics
    assert first.capacity_retries >= 1
    wide = max((q for q in queries if q is not big), key=len)
    assert len(wide) > len(big)
    res = eng.execute(opt.optimize(wide))
    assert res.metrics.capacity == 8 << res.metrics.capacity_retries
    assert _answers(res, wide) == naive_evaluate(fed, wide, bag=True)
    # a bound below the capacity ``big`` grew to: its repeat starts at the
    # bound, where it overflowed before, and fails instead of running above it
    below = first.capacity // 2
    monkeypatch.setattr(eng, "max_cap", lambda width: below)
    with pytest.raises(CapacityExceededError,
                       match=f"overflows at capacity {below} "):
        eng.execute(opt.optimize(big))


def test_overflow_past_memory_bound_raises(one_device, monkeypatch):
    fed, queries, opt = one_device
    eng = DistributedEngine(fed, _one_device_mesh(), cap=8)
    q = max(queries, key=lambda q: len(naive_evaluate(fed, q)))
    monkeypatch.setattr(eng, "max_cap", lambda width: 8)
    with pytest.raises(CapacityExceededError, match="overflows at capacity 8"):
        eng.execute(opt.optimize(q))


def test_max_cap_is_power_of_two_within_memory(one_device):
    fed, _, _ = one_device
    eng = DistributedEngine(fed, _one_device_mesh())
    bound = eng.max_cap(12)
    assert bound & (bound - 1) == 0 and bound >= eng.cap
    assert eng.max_cap(24) <= bound          # wider plans get fewer rows


def test_variable_predicate_star_raises(one_device):
    """A star with a variable predicate is refused, not scanned without the
    pattern."""
    fed, queries, opt = one_device
    star = next(q for q in queries if q.name.startswith("ST"))
    x = star.patterns[0].s
    q = BGPQuery(list(star.patterns) + [TriplePattern(x, Var("p"), Var("v"))],
                 projection=["x"], name="VARP")
    plan = opt.optimize(q)
    eng = DistributedEngine(fed, _one_device_mesh())
    with pytest.raises(NotImplementedError, match="variable predicate"):
        eng.execute(plan)


def test_table_cap_too_small_is_refused(tiny_fed):
    fed, _ = tiny_fed
    longest = max(len(s.table) for s in fed.sources)
    with pytest.raises(ValueError, match="cannot hold"):
        DistributedEngine(fed, _one_device_mesh(), table_cap=longest // 2)
    assert np.all(np.asarray(
        DistributedEngine(fed, _one_device_mesh()).trow).sum(axis=(1, 2))
        == [len(s.table) for s in fed.sources])
