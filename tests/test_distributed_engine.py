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
import jax.numpy as jnp
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


def test_retry_ms_times_exactly_the_overflowed_attempts(one_device):
    """``retry_ms`` is the host time of the attempts that overflowed: above
    0 when the plan was run again, 0.0 when the first attempt answered."""
    fed, queries, opt = one_device
    eng = DistributedEngine(fed, _one_device_mesh(), cap=8)
    q = max(queries, key=lambda q: len(naive_evaluate(fed, q)))
    retried = eng.execute(opt.optimize(q)).metrics
    assert retried.capacity_retries >= 1 and retried.retry_ms > 0.0
    again = eng.execute(opt.optimize(q)).metrics      # starts at the grown capacity
    assert again.capacity_retries == 0 and again.retry_ms == 0.0


def _lowered_star(mesh, n_pat=3):
    from repro.engine.distributed import _star_program

    sds = jax.ShapeDtypeStruct
    return _star_program(mesh, n_pat, 64).lower(
        sds((9, 1, 256, 3), np.int32), sds((9, 1, 256), np.bool_), sds((9,), np.bool_),
        sds((n_pat, 3), np.int32))


def _lowered_exchange(mesh, right_partitioned):
    from repro.engine.distributed import _exchange_program

    sds = jax.ShapeDtypeStruct
    rel, valid = sds((1, 1, 64, 3), np.int32), sds((1, 1, 64), np.bool_)
    return _exchange_program(mesh, 64, right_partitioned).lower(
        rel, valid, rel, valid, sds((), np.int32), sds((), np.int32))


@pytest.mark.parametrize("lower", [
    *(lambda mesh, n=n: _lowered_star(mesh, n) for n in (1, 2, 3, 4)),
    lambda mesh: _lowered_exchange(mesh, False),
    lambda mesh: _lowered_exchange(mesh, True),
], ids=["star1", "star2", "star3", "star4", "exchange", "exchange_part"])
def test_executor_programs_hold_no_loop(lower):
    """No star or exchange program holds a ``while`` loop: a sequential
    search (``jnp.searchsorted``'s default) is a loop of dependent gathers
    over every row, which held most of the star programs' device time."""
    assert "stablehlo.while" not in lower(_one_device_mesh()).as_text()


def _searchsorted_bucket_rank(dest):
    """The exchange's bucket order and rank as they were written with
    ``jnp.searchsorted``: the oracle."""
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    rank = jnp.arange(dest.shape[0]) - jnp.searchsorted(sorted_dest, sorted_dest,
                                                       side="left")
    return order, sorted_dest, rank


# (rows, buckets, valid share, key range): invalid rows go to the drop
# bucket ``buckets``
_BUCKET_CASES = {
    "all_dropped": (40, 4, 0.0, 100),
    "one_bucket": (40, 1, 1.0, 100),
    "one_key_of_four_buckets": (40, 4, 1.0, 1),
    "mixed": (200, 4, 0.7, 1000),
}


@pytest.mark.parametrize("case", sorted(_BUCKET_CASES))
def test_bucket_rank_equals_searchsorted(case):
    from repro.engine.distributed import _bucket_rank

    n, m, share, keys = _BUCKET_CASES[case]
    rng = np.random.default_rng(n + m)
    key = rng.integers(0, keys, n).astype(np.int32)
    dest = jnp.asarray(np.where(rng.random(n) < share, key % m, m).astype(np.int32))
    for got, want in zip(_bucket_rank(dest), _searchsorted_bucket_rank(dest)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# valid left rows: none (all dropped), exactly the one bucket's capacity,
# and one more than it
_EXCHANGE_CASES = {"all_dropped": 0, "one_bucket_full": 16, "bucket_overflows": 17}


@pytest.mark.parametrize("right_partitioned", [False, True])
@pytest.mark.parametrize("case", sorted(_EXCHANGE_CASES))
def test_exchange_equals_searchsorted_rank(case, right_partitioned, monkeypatch):
    """On the one-device mesh, the exchange program gives bit for bit the
    rows, validity, overflow flag and shipped count it gives with its
    bucket rank found by binary search."""
    from repro.engine import distributed

    cap, n = 16, 3 * 16        # three local sources' rows, one bucket of cap
    rng = np.random.default_rng(len(case))
    lvalid = np.zeros(n, bool)
    lvalid[rng.permutation(n)[:_EXCHANGE_CASES[case]]] = True
    left = rng.integers(0, 6, (1, 1, n, 3)).astype(np.int32)
    right = rng.integers(0, 60, (1, 1, n, 3)).astype(np.int32)  # few matches
    args = (jnp.asarray(left), jnp.asarray(lvalid.reshape(1, 1, n)), jnp.asarray(right),
            jnp.asarray(rng.random((1, 1, n)) < 0.8), jnp.int32(1), jnp.int32(0))
    mesh = _one_device_mesh()
    got = jax.device_get(distributed._exchange_program(mesh, cap, right_partitioned)(*args))
    monkeypatch.setattr(distributed, "_bucket_rank", _searchsorted_bucket_rank)
    want = jax.device_get(distributed._exchange_program(mesh, cap, right_partitioned)(*args))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    _, valid, ovf, _ = got
    assert bool(np.ravel(ovf)[0]) == (case == "bucket_overflows")
    if case == "one_bucket_full":
        assert np.asarray(valid).any()


def _lowered_collect(mesh):
    from repro.engine.distributed import _collect_program

    sds = jax.ShapeDtypeStruct
    return _collect_program(mesh).lower(sds((1, 1, 64, 3), np.int32),
                                        sds((1, 1, 64), np.bool_))


def _lowered_dp_sweep(mesh):
    from repro.kernels.dp_layer import _build_sweep_program

    sds = jax.ShapeDtypeStruct
    B, size, L, P, C = 2, 16, 3, 8, 4
    idx = [sds((L, P), np.int32)] * 3 + [sds((L, C), np.int32)]
    with jax.enable_x64(True):
        plane = sds((B, size), np.float64)
        return _build_sweep_program().lower(sds((4,), np.float64), *idx,
                                            *[plane] * 6)


@pytest.mark.parametrize("name, lower", [
    ("jit_fed_star3", _lowered_star),
    ("jit_fed_exchange", lambda mesh: _lowered_exchange(mesh, False)),
    ("jit_fed_exchange_part", lambda mesh: _lowered_exchange(mesh, True)),
    ("jit_fed_collect", _lowered_collect),
    ("jit_dp_sweep", _lowered_dp_sweep),
])
def test_device_programs_have_stable_names(name, lower):
    """Each device program's module carries the name a trace shows for it,
    whatever its capacity or shapes."""
    text = lower(_one_device_mesh()).as_text()
    assert f"module @{name} " in text


def test_spans_without_a_profiler_record_nothing(one_device, tmp_path):
    """Served with no profiler session, the executor's spans raise nothing
    and leave nothing for a later session to find."""
    from jax.profiler import ProfileData, TraceAnnotation

    fed, queries, opt = one_device
    eng = DistributedEngine(fed, _one_device_mesh(), cap=8)
    assert not TraceAnnotation.is_enabled()
    res = eng.execute(opt.optimize(queries[0]))
    assert _answers(res, queries[0]) == naive_evaluate(fed, queries[0], bag=True)
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    names = {e.name for plane in pd.planes for line in plane.lines for e in line.events}
    assert not any(n.startswith("repro.") for n in names)
