"""CPU tests of the benchmark's OPTIONAL, UNION and FILTER traffic: the plain
reference's operators by hand, the two existing mixes pinned to the queries
they drew before the new kinds existed, and runs of a small algebra mix
through ``main``: held ``off_device`` on a program that answers such plans on
the host, ``correct`` where the answers come from the device's metrics path,
and not correct with the OPTIONAL arm's unbound cells altered."""
from __future__ import annotations

import contextlib
import hashlib
import io
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

from obench import datagen, harness, loadgen  # noqa: E402
from obench.reference import UNDEF, Query, Reference, tree_query  # noqa: E402
from test_onchip_bench import (ALGEBRA_MIX, _config, _mix, make_tiny_root,  # noqa: E402
                               own_compile_cache)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("checkout"))


# -- the reference's operators, by hand ---------------------------------------

# 1 has a name and a mass; 2 a name alone; 3 a mass alone
TRIPLES = [np.array([[1, 10, 100], [1, 11, 7], [2, 10, 101]], np.int32),
           np.array([[3, 11, 5], [1, 12, 300], [1, 12, 301]], np.int32)]
NAME, MASS = ("bgp", (("x", 10, "n"),)), ("bgp", (("x", 11, "m"),))


def _rows(q: Query) -> list:
    return Reference(TRIPLES).evaluate(q).tolist()


def test_optional_keeps_an_unmatched_left_row():
    q = tree_query("o", ("optional", NAME, MASS), ("x", "n", "m"), False)
    assert _rows(q) == [[1, 100, 7], [2, 101, UNDEF]]
    # a left row with two matches is two rows; the other is padded once
    q = tree_query("o2", ("optional", NAME, ("bgp", (("x", 12, "z"),))), ("x", "z"), False)
    assert _rows(q) == [[1, 300], [1, 301], [2, UNDEF]]


def test_union_of_different_schemas():
    # the members bind different variables: each is UNDEF in the other's rows
    q = tree_query("u", ("union", (NAME, MASS)), ("x", "n", "m"), False)
    assert _rows(q) == [[1, UNDEF, 7], [1, 100, UNDEF], [2, 101, UNDEF], [3, UNDEF, 5]]
    # a bag: a solution of both members is there twice
    q = tree_query("u2", ("union", (NAME, NAME)), ("x",), False)
    assert _rows(q) == [[1], [1], [2], [2]]


def test_filter_with_an_unbound_side_is_false():
    opt = ("optional", NAME, MASS)
    # ?m is UNDEF for 2: neither m < 10 nor m != 7 holds there
    assert _rows(tree_query("f", ("filter", ("<", "m", 10), opt), ("x",), False)) == [[1]]
    assert _rows(tree_query("f", ("filter", ("!=", "m", 7), opt), ("x",), False)) == []
    # a variable the child never binds, and ordering by term id
    assert _rows(tree_query("f", ("filter", (">", "q", 0), opt), ("x",), False)) == []
    assert _rows(tree_query("f", ("filter", (">", "n", 100), NAME), ("x",), False)) == [[2]]


def test_distinct_over_undef_and_a_never_bound_projection():
    opt = ("optional", ("bgp", (("x", 10, "n"), ("x", 10, "n2"))), MASS)
    q = tree_query("d", ("union", (opt, ("bgp", (("x", 10, "n"),)))), ("m", "w"), False)
    # ?w is bound nowhere: UNDEF in every row
    assert _rows(q) == [[UNDEF, UNDEF], [UNDEF, UNDEF], [UNDEF, UNDEF], [7, UNDEF]]
    # DISTINCT holds UNDEF equal to UNDEF
    assert _rows(tree_query("d", q.tree, q.projection, True)) == [[UNDEF, UNDEF], [7, UNDEF]]


def test_a_join_key_holding_undef_is_refused():
    inner = ("optional", NAME, MASS)
    with pytest.raises(ValueError, match="UNDEF"):
        Reference(TRIPLES).evaluate(tree_query("k", ("optional", inner, MASS), ("x",), False))


def test_bound_instances_bind_the_filter_terms(tiny_root):
    data = datagen.generate(_config(tiny_root, "fedbench9"))
    mix = dict(ALGEBRA_MIX, templates={"filter": 2})
    for t in loadgen.Traffic(data, mix).templates:
        op, v, c = t.query.tree[1]
        e = int(t.pool[0])
        bound = t.instance(e)
        assert "x" not in bound.variables() and bound.patterns[0][0] == e
        assert bound.tree[1] == (op, v, c)
        # ?x in a comparison is bound as well
        q = tree_query(t.query.name, ("filter", (op, "x", c), t.query.tree[2]), (), False)
        assert loadgen.Template(q, "x", t.pool).instance(e).tree[1] == (op, e, c)


# -- the existing mixes draw what they drew -----------------------------------

def _plain(q: Query) -> tuple:
    return (q.name, q.patterns, q.projection, q.distinct)


def _digest(part) -> str:
    return hashlib.sha256(repr(part).encode()).hexdigest()[:16]


# (templates, traffic), recorded on the tests' configurations before the
# algebra kinds were added: the open schedule of 10 s, or the first 48
# closed requests, of seed 2**31 + 7
PINNED = {"fedbench9.bound_open": ("0e15c0e80681e1db", "55306d200ff66b5e"),
          "largerdf13.heavy_closed": ("63de3ebe4430f5b2", "83291faf79b6e333")}


@pytest.mark.parametrize("cell", list(PINNED))
def test_existing_mixes_draw_what_they_drew(tiny_root, cell):
    config, mix = cell.split(".")
    traffic = loadgen.Traffic(datagen.generate(_config(tiny_root, config)),
                              _mix(tiny_root, mix))
    templates = [(_plain(t.query), t.bind, None if t.pool is None else t.pool.tolist())
                 for t in traffic.templates]
    if traffic.loop == "open":
        offsets, queries = traffic.schedule(2**31 + 7, 10.0)
        sent = (offsets.tolist(), [_plain(q) for q in queries])
    else:
        stream = traffic.stream(2**31 + 7)
        sent = [_plain(next(stream)) for _ in range(48)]
    assert all(t.query.tree is None for t in traffic.templates)
    assert (_digest(templates), _digest(sent)) == PINNED[cell]


# -- an algebra mix through main ----------------------------------------------

CELL = "fedbench9.algebra_open"


@pytest.fixture(scope="module")
def algebra_root(tmp_path_factory):
    """A checkout with an algebra cell: its mix a file of the tests' own."""
    root = make_tiny_root(tmp_path_factory.mktemp("algebra"))
    (root / harness.BENCH / "traffic" / "algebra_open.json").write_text(json.dumps(ALGEBRA_MIX))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "fedbench9", "traffic": "algebra_open",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "fedbench9.bound_open" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def algebra_engine_class(undef_as=None):
    """The executor, answering OPTIONAL/UNION/FILTER plans with the host
    engine's rows and the device executor's metrics, as a program that ran
    them on the device would; ``undef_as`` plants a fault: the unbound
    cells filled with that value."""
    from repro.engine.distributed import DistMetrics
    from repro.engine.local import ExecutionResult, LocalEngine

    class AlgebraEngine(harness.traced_engine_class()):
        def execute(self, plan):
            if plan.query.root is None:
                return super().execute(plan)
            self.starts.append(time.perf_counter())
            rows = LocalEngine(self.fed).execute(plan).rows
            if undef_as is not None:
                rows = {v: np.where(c == UNDEF, undef_as, c) for v, c in rows.items()}
            return ExecutionResult(rows=rows, metrics=DistMetrics(), plan=plan,
                                   stats_epoch=plan.stats_epoch)

    return AlgebraEngine


def _run(root: Path, engine_class=None) -> dict:
    out = io.StringIO()
    argv = ["--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "2", "--trace", "0"]
    with own_compile_cache(), contextlib.redirect_stdout(out):
        rc = harness.main(argv, root=root, platform="cpu", engine_class=engine_class)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_host_fallback_is_held_off_device(algebra_root):
    line = _run(algebra_root)
    checks = {k: v["value"] for k, v in line["checks"].items()}
    # every request is an algebra query the program answers on the host; 8
    # queries/s for 2 s, the sixteenth due at the close itself
    assert line["attempted"] == 15 and line["correct"] is False
    assert checks["off_device"] == checks["failed"] == line["failed"] == line["attempted"]
    assert checks["wrong"] == checks["lost"] == 0


@pytest.mark.parametrize("undef_as, correct", [(None, True), (0, False)])
def test_algebra_answers_are_held_to_the_reference(algebra_root, undef_as, correct):
    line = _run(algebra_root, algebra_engine_class(undef_as))
    checks = {k: v["value"] for k, v in line["checks"].items()}
    assert line["attempted"] == 15
    assert checks["off_device"] == checks["failed"] == 0
    assert line["correct"] is correct
    assert (checks["wrong"] > 0) is not correct
