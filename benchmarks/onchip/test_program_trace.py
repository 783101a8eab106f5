"""CPU tests of what the benchmark reads from the program's own
instrumentation: the program's spans and module names in a trace
(``obench/attribution.py``) and the readers of the metrics taken from them
(star programs' device time, idle time in the executor), and the readers of
the metrics that the program counts (``retry_ms``, ``admission_wait_ms``)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

from obench import attribution, harness, tracing  # noqa: E402

E = tracing.Event

HARNESS_SPANS = [E("bench.window", 0.0, 10.0), E("bench.poll", 0.2, 6.0),
                 E("bench.execute", 1.1, 5.6), E("bench.wait", 6.0, 10.0)]
PROGRAM_SPANS = [E("repro.serve.admit", 0.6, 0.7), E("repro.serve.plan", 0.7, 1.0),
                 E("repro.serve.execute", 1.0, 5.8), E("repro.serve.run", 1.05, 5.7),
                 E("repro.engine.attempt", 1.2, 4.0), E("repro.engine.readback", 3.0, 3.6),
                 E("repro.engine.collect", 3.6, 4.0), E("repro.engine.assemble", 4.0, 5.5)]
# one answer's programs, and two more runs later in the window; the star
# program's fusion runs inside its sort loop
MODULES = [E("jit_dp_sweep(5)", 0.75, 0.95), E("jit_fed_star3(123)", 1.2, 3.0),
           E("jit_fed_collect(77)", 3.7, 3.9), E("jit_fed_star3(456)", 6.5, 7.0),
           E("jit_fed_exchange(9)", 9.5, 11.0)]
OPS = [E("jit_dp_sweep(5)/while.3", 0.75, 0.95), E("jit_fed_star3(123)/while.1", 1.2, 3.0),
       E("jit_fed_star3(123)/fusion.2", 1.5, 2.5), E("jit_fed_collect(77)/fusion.1", 3.7, 3.9),
       E("jit_fed_star3(456)/while.1", 6.5, 7.0), E("jit_fed_exchange(9)/sort.4", 9.5, 11.0)]


def test_program_spans_attribute_idle_gaps_by_hand():
    alone = tracing.reduce([OPS], HARNESS_SPANS)
    s = attribution.reduce([OPS], [MODULES], HARNESS_SPANS + PROGRAM_SPANS)
    # busy: [0.75, 0.95] + [1.2, 3] + [3.7, 3.9] + [6.5, 7] + [9.5, 10]
    assert s.busy_s == pytest.approx(3.2)
    assert (s.busy_s, s.window_s, s.idle_share, s.device_ops) == (
        alone.busy_s, alone.window_s, alone.idle_share, alone.device_ops)
    # each gap by the innermost span open at its middle, of either prefix
    assert s.idle_gaps == [["engine.assemble", pytest.approx(2.6)],
                           ["wait", pytest.approx(2.5)], ["poll", pytest.approx(0.75)],
                           ["engine.readback", pytest.approx(0.7)],
                           ["serve.run", pytest.approx(0.25)]]
    assert alone.idle_gaps == [["execute", pytest.approx(2.6)], ["wait", pytest.approx(2.5)],
                               ["poll", pytest.approx(0.75)], ["execute", pytest.approx(0.7)],
                               ["poll", pytest.approx(0.25)]]
    assert s.idle_s_in_engine == pytest.approx(3.3)
    # programs by their module runs, clipped to the window, fingerprints cut;
    # the star program's nested fusion is not counted again
    assert s.device_s_by_program == {"jit_dp_sweep": pytest.approx(0.2),
                                     "jit_fed_star3": pytest.approx(2.3),
                                     "jit_fed_collect": pytest.approx(0.2),
                                     "jit_fed_exchange": pytest.approx(0.5)}


def test_program_spans_in_a_recorded_trace(tmp_path):
    """One query served through the program's server and executor on a
    (1, 1) CPU mesh under the profiler: the trace holds the program's
    spans, named without their metadata."""
    import jax

    from repro.core.federation import build_federated_stats
    from repro.rdf.generator import fedbench_like_spec, generate_federation, generate_workload
    from repro.serve.query import QueryServeEngine

    fed, gt = generate_federation(fedbench_like_spec(scale=0.06, seed=3))
    hybrid = next(q for q in generate_workload(fed, gt, n_star=0, n_hybrid=2, n_path=0, seed=9))
    engine = harness.traced_engine_class()(fed, jax.make_mesh((1, 1), ("data", "model")), cap=8)
    server = QueryServeEngine(fed, build_federated_stats(fed), engine=engine)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        server.submit(hybrid)
        (req,) = server.drain()
    jax.profiler.stop_trace()
    assert req.metrics.capacity_retries >= 1
    devices, modules, spans = attribution.read(str(next(tmp_path.rglob("*.xplane.pb"))))
    names = {s.name for s in spans}
    assert {"repro.serve.admit", "repro.serve.plan", "repro.serve.execute", "repro.serve.run",
            "repro.engine.attempt", "repro.engine.readback", "repro.engine.collect",
            "repro.engine.assemble", "bench.window", "bench.execute"} <= names
    assert all(n.startswith(("bench.", "repro.")) and "=" not in n and "#" not in n
               for n in names)
    attempts = [s for s in spans if s.name == "repro.engine.attempt"]
    assert len(attempts) == req.metrics.capacity_retries + 1
    run = next(s for s in spans if s.name == "repro.serve.run")
    assert all(run.start <= a.start and a.end <= run.end for a in attempts)
    # the CPU has no device plane: let one operation fill the collect span
    collect = next(s for s in spans if s.name == "repro.engine.collect")
    s = attribution.reduce([[E("jit_fed_collect(1)/op", collect.start, collect.end)]],
                           [[E("jit_fed_collect(1)", collect.start, collect.end)]], spans)
    assert s.device_s_by_program == {"jit_fed_collect": pytest.approx(collect.end - collect.start)}
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s - s.busy_s)
    assert any(k.startswith(("serve.", "engine.")) for k in s.idle_by_span)


def _window(loop: str, serve: dict, trace=None, answered: float = 0.0) -> harness.Window:
    return harness.Window(loop=loop, seconds=51.0, setup_s=1.0, latencies_s=[],
                          completed_in_window=answered, serve=serve, capacity_retries=0,
                          compile_requests=0, trace=trace)


# the by-hand trace holds 2.3 s of jit_fed_star3 and 3.3 s idle in the
# executor's spans (test_program_spans_attribute_idle_gaps_by_hand); 2.5
# answers in the window
@pytest.mark.parametrize("metric, loop, value", [
    ("star_device_ms_per_query.open", "open", 2.3e3 / 2.5),
    ("star_device_ms_per_query.closed", "closed", 2.3e3 / 2.5),
    ("exec_idle_ms_per_query.closed", "closed", 3.3e3 / 2.5),
])
def test_trace_attributed_metrics_per_answer(metric, loop, value):
    read = harness._reader(HERE / "metrics" / f"{metric}.py")
    s = attribution.reduce([OPS], [MODULES], HARNESS_SPANS + PROGRAM_SPANS)
    assert read(_window(loop, {}, s, 2.5)) == pytest.approx(value)
    if metric.startswith("star"):
        assert read(_window(loop, {}, s, 2.5)) * 2.5 * 1e-3 <= s.busy_s
    # an untraced run, the other loop, a window with no answer: nothing to read
    other = "closed" if loop == "open" else "open"
    assert read(_window(loop, {}, None, 2.5)) is None
    assert read(_window(other, {}, s, 2.5)) is None
    assert read(_window(loop, {}, s, 0.0)) is None
    # the harness's own reduction has no programs or executor spans
    assert read(_window(loop, {}, tracing.reduce([OPS], HARNESS_SPANS), 2.5)) is None
    if metric.startswith("star"):
        # a window in which no star program ran
        plain = [m for m in MODULES if not m.name.startswith("jit_fed_star")]
        s = attribution.reduce([OPS], [plain], HARNESS_SPANS + PROGRAM_SPANS)
        assert read(_window(loop, {}, s, 2.5)) is None
    cell = {"open": "fedbench9.bound_open", "closed": "largerdf13.heavy_closed"}[loop]
    assert metric in [m["name"] for m, _ in harness.load_cell(REPO, cell, trace=True).metrics]
    assert metric not in [m["name"] for m, _ in harness.load_cell(REPO, cell, trace=False).metrics]


@pytest.mark.parametrize("metric, loop, key", [
    ("retry_ms_per_query.open", "open", "retry_ms"),
    ("admission_wait_ms_per_query.closed", "closed", "admission_wait_ms"),
])
def test_program_counted_metrics_per_answer(metric, loop, key):
    read = harness._reader(HERE / "metrics" / f"{metric}.py")
    assert read(_window(loop, {"n_served": 4, key: 100.0})) == pytest.approx(25.0)
    assert read(_window(loop, {"n_served": 0, key: 0.0})) is None
    # the other loop, and a program that does not count it: nothing to read
    other = "closed" if loop == "open" else "open"
    assert read(_window(other, {"n_served": 4, key: 100.0})) is None
    assert read(_window(loop, {"n_served": 4})) is None
    # a traced run of the cell that lists the metric reports it
    cell = {"open": "fedbench9.bound_open", "closed": "largerdf13.heavy_closed"}[loop]
    listed = [m["name"] for m, _ in harness.load_cell(REPO, cell, trace=True).metrics]
    assert metric in listed
