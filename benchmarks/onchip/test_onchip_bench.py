"""CPU tests of the on-chip benchmark, at a tiny scale: the copied generator,
the plain reference, the traffic generator, the trace reduction, cells found
by name, the result line, the refusal without a chip, and the comparison
that decides ``correct`` (sound runs pass; the control and each planted
fault fail)."""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

from obench import datagen, harness, loadgen, tracing  # noqa: E402
from obench.control import control_engine_class  # noqa: E402
from obench.reference import Query, Reference  # noqa: E402

CELLS = ["fedbench9.bound_open", "largerdf13.heavy_closed"]
SHRINK = {"fedbench9": 400, "largerdf13": 800}     # entity counts divided by


def make_tiny_root(dst: Path) -> Path:
    """A checkout holding the benchmark with its configurations cut to a
    size the CPU runs in seconds, and the program beside it."""
    (dst / "benchmarks").mkdir(parents=True)
    shutil.copytree(HERE, dst / harness.BENCH,
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dst)
    os.symlink(REPO / "src", dst / "src")
    for name, div in SHRINK.items():
        p = dst / harness.BENCH / "configs" / f"{name}.json"
        c = json.loads(p.read_text())
        c.pop("triples")
        for s in c["sources"]:
            s["n_entities"] = max(16, s["n_entities"] // div)
        p.write_text(json.dumps(c))
    mix = dst / harness.BENCH / "traffic" / "bound_open.json"
    m = json.loads(mix.read_text())
    m["rate_qps"] = 8.0
    mix.write_text(json.dumps(m))
    return dst


@contextlib.contextmanager
def own_compile_cache():
    """Undo the persistent-cache settings a run makes, so that later tests
    in this process compile as before."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
        if saved[2] is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = saved[2]
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("checkout"))


def _config(root: Path, name: str) -> dict:
    return json.loads((root / harness.BENCH / "configs" / f"{name}.json").read_text())


def _mix(root: Path, name: str) -> dict:
    return json.loads((root / harness.BENCH / "traffic" / f"{name}.json").read_text())


# -- the copied generator and the plain reference ----------------------------

def test_generator_copy_equals_program_generator():
    import dataclasses

    from repro.rdf.generator import fedbench_like_spec, generate_federation

    spec = fedbench_like_spec(scale=0.3, seed=5)
    fed, gt = generate_federation(spec)
    data = datagen.generate({"data_seed": spec.seed, "sources": [
        {k: v for k, v in dataclasses.asdict(s).items() if v is not None}
        for s in spec.sources]})
    for src, t in zip(fed.sources, data.triples):
        assert np.array_equal(np.stack([src.table.s, src.table.p, src.table.o], 1), t)
    assert data.terms.kinds == list(fed.dictionary.kinds)
    assert data.terms.authorities == list(fed.dictionary.authorities)
    assert len(data.gt.cross_links) == len(gt.cross_links)


def _bag(rows: np.ndarray, distinct: bool) -> Counter:
    tuples = [tuple(r) for r in rows.tolist()]
    return Counter(set(tuples) if distinct else tuples)


@pytest.mark.parametrize("cell", CELLS)
def test_reference_equals_naive_evaluate(tiny_root, cell):
    from repro.engine.local import naive_evaluate

    config, mix = cell.split(".")
    data = datagen.generate(_config(tiny_root, config))
    fed = datagen.to_program(data)
    ref = Reference(data.triples)
    traffic = loadgen.Traffic(data, _mix(tiny_root, mix))
    queries = traffic.warmup() + traffic.schedule(3, 2.0)[1] if traffic.loop == "open" \
        else traffic.warmup()
    nonempty = 0
    for q in queries:
        want = naive_evaluate(fed, harness.to_program_query(q), bag=True)
        got = ref.evaluate(q)
        assert _bag(got, q.distinct) == want, q
        nonempty += len(got) > 0
    assert nonempty >= len(queries) // 2


def test_reference_bag_semantics_and_distinct():
    # (1, p, 5) is in two sources: the union holds it once
    t = [np.array([[1, 9, 5], [1, 9, 6], [2, 9, 5]], np.int32),
         np.array([[1, 9, 5], [1, 8, 7], [1, 8, 8]], np.int32)]
    ref = Reference(t)
    bag = Query("b", (("x", 9, "y"), ("x", 8, "z")), ("x",), False)
    assert ref.evaluate(bag).tolist() == [[1]] * 4
    dis = Query("d", bag.patterns, ("x",), True)
    assert ref.evaluate(dis).tolist() == [[1]]
    bound = Query("c", ((2, 9, "y"),), ("y",), False)
    assert ref.evaluate(bound).tolist() == [[5]]


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_traffic_is_deterministic_per_seed(tiny_root, cell):
    config, mix = cell.split(".")
    data = datagen.generate(_config(tiny_root, config))
    traffic = loadgen.Traffic(data, _mix(tiny_root, mix))
    if traffic.loop == "open":
        (o1, q1), (o2, q2) = traffic.schedule(2**31 + 7, 10.0), traffic.schedule(2**31 + 7, 10.0)
        o3, q3 = traffic.schedule(11, 10.0)
        assert np.array_equal(o1, o2) and q1 == q2
        # another seed binds other entities into the same trace of templates
        assert q1 != q3
        assert np.array_equal(o1, o3) and [q.name for q in q1] == [q.name for q in q3]
        assert o1[-1] == pytest.approx(10.0)
    else:
        def first(seed, n=5 * traffic.mix["cycle"]):     # whole cycles
            s = traffic.stream(seed)
            return [next(s) for _ in range(n)]
        a, b, c = first(2**31 + 7), first(2**31 + 7), first(11)
        assert a == b
        # unbound templates: every seed sends the same queries in the same order
        assert a == c
        counts = Counter(q.name for q in a)
        assert sum(counts.values()) == 5 * traffic.mix["cycle"] and len(counts) > 1


def test_fixed_counts_keep_the_total():
    shares = loadgen.popularity(18, 1.0, 13)
    for total in (1, 17, 60, 1000):
        counts = loadgen.fixed_counts(shares, total)
        assert counts.sum() == total and (counts >= 0).all()


# -- trace reduction ----------------------------------------------------------

def test_trace_reduction_by_hand():
    E = tracing.Event
    spans = [E("bench.window", 0.0, 10.0), E("bench.poll", 0.5, 6.0),
             E("bench.execute", 2.0, 5.0), E("bench.wait", 6.0, 10.0)]
    ops = [E("p/sort", 2.0, 3.0), E("p/fusion", 2.5, 3.8), E("p/sort", 4.5, 5.0),
           E("q/sort", 6.5, 7.0), E("q/sort", 9.5, 11.0)]
    s = tracing.reduce([ops], spans)
    # busy: [2, 3.8] + [4.5, 5] + [6.5, 7] + [9.5, 10]; 11.0 lies past the window
    assert s.window_s == 10.0
    assert s.busy_s == pytest.approx(3.3)
    assert s.idle_share == pytest.approx(0.67)
    assert s.device_ops == [["p/sort", pytest.approx(1.5)], ["p/fusion", pytest.approx(1.3)],
                            ["q/sort", pytest.approx(1.0)]]
    # gaps [0, 2] and [5, 6.5] in poll, [3.8, 4.5] in execute, [7, 9.5] waiting
    assert s.idle_gaps == [["wait", pytest.approx(2.5)], ["poll", pytest.approx(2.0)],
                           ["poll", pytest.approx(1.5)], ["execute", pytest.approx(0.7)]]
    assert s.idle_by_span == {"poll": pytest.approx(3.5), "execute": pytest.approx(0.7),
                              "wait": pytest.approx(2.5)}


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x) + 1)
    x = jnp.arange(1 << 14)[::-1]
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.poll"):
            with jax.profiler.TraceAnnotation("bench.execute"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    devices, spans = tracing.read(path)
    names = {s.name for s in spans}
    assert {"bench.window", "bench.poll", "bench.execute", "bench.wait"} <= names
    # the CPU has no device plane: let one operation fill the execute span
    ex = next(s for s in spans if s.name == "bench.execute")
    s = tracing.reduce([[tracing.Event("m/op", ex.start, ex.end)]], spans)
    win = next(s for s in spans if s.name == "bench.window")
    assert s.window_s == pytest.approx(win.end - win.start)
    assert s.busy_s == pytest.approx(ex.end - ex.start)
    assert s.idle_by_span["wait"] >= 0.015
    assert max(s.idle_gaps, key=lambda g: g[1])[0] == "wait"


def test_share_of_the_answer_in_service_at_the_close():
    from types import SimpleNamespace as NS

    done = lambda *ts: [(None, NS(t_done=t)) for t in ts]  # noqa: E731
    bench = NS(engine=NS(starts=[0.0, 2.0, 6.0]))
    share = harness.Bench._share_at_close
    # executions [0, 2], [2, 6], [6, 10]: the second is in service at 5
    assert share(bench, done(2.0, 6.0, 10.0), 5.0) == pytest.approx(0.75)
    # at 6.5 the third has run 0.5 of its 4 seconds
    assert share(bench, done(2.0, 6.0, 10.0), 6.5) == pytest.approx(0.125)
    # nothing in service: the last execution ended before the close
    assert share(NS(engine=NS(starts=[0.0, 2.0])), done(2.0, 6.0), 7.0) == 0.0


# -- cells by name, the result line, refusals ---------------------------------

def _run_main(root: Path, argv: list[str]) -> tuple[int, dict]:
    out = io.StringIO()
    with own_compile_cache(), contextlib.redirect_stdout(out):
        rc = harness.main(argv, root=root, platform="cpu")
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1])


def test_new_cell_found_by_name_alone(tmp_path):
    root = make_tiny_root(tmp_path / "checkout")
    bench = root / harness.BENCH
    # a new configuration, traffic mix and metric: files of their own ...
    cfg = json.loads((bench / "configs" / "fedbench9.json").read_text())
    cfg["name"] = "mini9"
    for s in cfg["sources"]:
        s["n_entities"] = max(16, s["n_entities"] // 4)
    (bench / "configs" / "mini9.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "bound_open.json").read_text())
    mix["templates"] = {"star": 2, "path": 2}
    (bench / "traffic" / "few_open.json").write_text(json.dumps(mix))
    (bench / "metrics" / "answers_in_window.py").write_text(
        "def read(window):\n    return window.completed_in_window\n")
    # ... and entries in BENCHMARK.json, no edit to an existing file
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "mini9.few_open", "config": "mini9",
                              "traffic": "few_open", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "fedbench9.bound_open" in m.get("workloads", []):
            m["workloads"].append("mini9.few_open")
    spec["per_layer"].append({"name": "answers_in_window", "unit": "queries",
                              "better": "higher", "source": "host_clock",
                              "layer": "admission", "moves": "latency_p85_ms",
                              "workloads": ["mini9.few_open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    args = ["--workload", "mini9.few_open", "--seed", str(2**31 + 3), "--seconds", "1.5"]
    rc, line = _run_main(root, args + ["--trace", "1"])
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "breakdown", "checks"]
    assert line["correct"] is True and line["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(line["device"])
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["metrics"]["answers_in_window"]["unit"] == "queries"
    assert "latency_p50_ms" not in line["metrics"]
    assert set(line["checks"]) == {"wrong", "failed", "lost", "repeated", "off_device"}

    rc, line = _run_main(root, args + ["--trace", "0"])
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    # the open cell's end-to-end metrics; its p85 needs ten answers beyond it
    assert set(line["metrics"]) <= {"latency_p85_ms", "setup_s"}
    assert "setup_s" in line["metrics"]
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def _command(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload", "fedbench9.bound_open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    proc = _command(REPO)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


def test_refuses_with_the_benchmark_alone(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / harness.BENCH,
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


# -- the comparison that decides correct --------------------------------------

def fault_classes():
    """The executor and the server with faults that can be planted
    underneath a run, and the control (capacity retries switched off)."""
    import dataclasses

    import jax.numpy as jnp

    from repro.engine import distributed as dist
    from repro.serve.query import QueryServeEngine

    class FaultEngine(control_engine_class()):
        truncate = False
        fault = None
        _last = None

        def execute(self, plan):
            if self.fault == "raise":            # every other request raises
                self.calls = getattr(self, "calls", 0) + 1
                if self.calls % 2:
                    raise dist.CapacityExceededError("planted")
            res = super().execute(plan)
            if self.fault == "stale":            # the previous answer again
                res, self._last = (self._last or res), res
            elif self.fault == "half_rows":
                n = len(next(iter(res.rows.values()), []))
                res = dataclasses.replace(res, rows={k: v[:n // 2] for k, v in res.rows.items()})
            elif self.fault == "altered":
                rows = {k: v.copy() for k, v in res.rows.items()}
                first = next(iter(rows.values()), np.zeros(0))
                if len(first):
                    first[0] += 1
                res = dataclasses.replace(res, rows=rows)
            return res

        def _join(self, left, right, join_vars, run):
            if self.fault == "exchange":        # the build side never arrives
                right = dataclasses.replace(right, valid=jnp.zeros_like(right.valid))
            return super()._join(left, right, join_vars, run)

    class FaultServer(QueryServeEngine):
        fault = None

        def _execute_batch(self, batch):
            if self.fault == "half_batch":      # half of each batch left out
                batch = batch[:len(batch) // 2]
            return super()._execute_batch(batch)

    return FaultEngine, FaultServer


WINDOW_S = 1.5


@pytest.fixture(scope="module")
def benches(tiny_root):
    engine_class, server_class = fault_classes()
    out = {}
    for name in CELLS:
        cell = harness.load_cell(tiny_root, name, trace=False)
        out[name] = harness.Bench(tiny_root, cell, time.perf_counter(), 3, WINDOW_S,
                                  platform="cpu", engine_class=engine_class,
                                  server_class=server_class)
        out[name].executed_in_setup = len(out[name].engine.starts)
        out[name].grace_s = 3.0     # a lost answer is given up on sooner here
    return out


def _measure(bench, seed, *, fault=None, truncate_at=None):
    eng, srv = bench.engine, bench.server
    cap = eng.cap
    try:
        eng.fault = srv.fault = fault
        if truncate_at is not None:
            eng.truncate, eng.cap = True, truncate_at
        window, checks, device, attempted, failed = bench.measure(seed, WINDOW_S, False)
    finally:
        eng.fault = srv.fault = None
        eng.truncate, eng.cap = False, cap
    return window, checks


@pytest.mark.parametrize("cell", CELLS)
def test_sound_runs_are_correct(benches, cell):
    for seed in (5, 2**31 + 11):
        window, checks = _measure(benches[cell], seed)
        assert checks.correct, checks
        assert checks.compared == len(window.latencies_s) > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(benches, cell):
    # starting capacity 16: nearly every answer needs more and comes back cut
    window, checks = _measure(benches[cell], 21, truncate_at=16)
    assert not checks.correct and checks.wrong > 0


@pytest.mark.parametrize("fault", ["stale", "half_rows", "altered", "exchange", "half_batch",
                                   "raise"])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(benches, cell, fault):
    window, checks = _measure(benches[cell], 31, fault=fault)
    assert not checks.correct, (fault, checks)
    if fault == "half_batch":
        assert checks.lost > 0
    elif fault == "raise":
        # the requests that raised are failed, the others still compared
        assert checks.failed > 0 and checks.compared > 0 and checks.wrong == 0
    else:
        assert checks.wrong > 0


@pytest.mark.parametrize("cell", CELLS)
def test_setup_serves_a_stretch_of_the_traffic(benches, cell):
    # every template once, then the mix's own traffic from another seed
    bench = benches[cell]
    n_templates = len(bench.traffic.templates)
    assert harness.warmup_seed(3) != 3
    if bench.traffic.loop == "open":
        # the stretch is the whole (short) window: every request it schedules
        offsets, _ = bench.traffic.schedule(harness.warmup_seed(3), WINDOW_S)
        assert bench.executed_in_setup == n_templates + len(offsets)
    else:
        # each client's first request at least
        assert bench.executed_in_setup >= n_templates + bench.traffic.mix["clients"]
