"""Group-tree algebra: parsing, normalization, compositional planning and
the differential suite against the ``naive_evaluate`` oracle.

The differentials deliberately pit two *different* evaluation structures
against each other: the planner normalizes (filter pushdown, union hoisting,
well-designed OPTIONAL pull-up) and reorders via the DP, while the oracle
evaluates the raw syntactic tree over the union of all sources."""
import numpy as np
import pytest

from repro.core.planner import OdysseyOptimizer, query_signature
from repro.engine.local import LocalEngine, naive_evaluate
from repro.query.algebra import (
    And,
    BGPQuery,
    Bgp,
    Comparison,
    Const,
    Filter,
    Join,
    LeftJoin,
    Not,
    Or,
    TriplePattern,
    Union,
    Var,
    certain_variables,
    from_algebra,
    group_variables,
    is_well_designed,
    normalize,
)


def _tp(s, p, o):
    def t(x):
        return Var(x) if isinstance(x, str) else Const(x)
    return TriplePattern(t(s), t(p), t(o))


def _engine_rows(fed, plan, q):
    rel = LocalEngine(fed).execute(plan).rows
    proj = q.effective_projection()
    n = len(next(iter(rel.values()))) if rel else 0
    return set(zip(*[rel[v].tolist() for v in proj])) if n else set()


# --------------------------------------------------------------------------
# Normalization
# --------------------------------------------------------------------------

def test_adjacent_bgps_merge_and_query_is_conjunctive():
    a = Bgp((_tp("x", 1, "y"),))
    b = Bgp((_tp("y", 2, "z"),))
    norm = normalize(Join((a, b)))
    assert isinstance(norm, Bgp) and len(norm.patterns) == 2
    q = from_algebra(Join((a, b)))
    assert q.root is not None and q.is_conjunctive()


def test_union_hoists_out_of_join_and_filter():
    star = Bgp((_tp("x", 1, "y"),))
    u = Union((Bgp((_tp("x", 2, "z"),)), Bgp((_tp("x", 3, "z"),))))
    norm = normalize(Join((star, u)))
    assert isinstance(norm, Union) and len(norm.members) == 2
    for m in norm.members:
        assert isinstance(m, Bgp) and len(m.patterns) == 2  # union-free branches

    norm2 = normalize(Filter(Comparison("!=", Var("x"), Var("z")), u))
    assert isinstance(norm2, Union)


def test_union_never_hoists_out_of_optional_arm():
    left = Bgp((_tp("x", 1, "y"),))
    arm = Union((Bgp((_tp("x", 2, "a"),)), Bgp((_tp("x", 3, "a"),))))
    norm = normalize(LeftJoin(left, arm))
    assert isinstance(norm, LeftJoin)            # the arm keeps its scope
    assert isinstance(norm.right, Union)


def test_well_designed_optional_pulls_above_the_join():
    L = Bgp((_tp("x", 1, "y"),))
    R = Bgp((_tp("x", 2, "o"),))                  # arm var o stays private
    S = Bgp((_tp("x", 3, "z"),))
    norm = normalize(Join((LeftJoin(L, R), S)))
    assert isinstance(norm, LeftJoin)
    assert norm.right == R
    assert isinstance(norm.left, Bgp) and len(norm.left.patterns) == 2


def test_non_well_designed_join_stays_in_syntactic_order():
    L = Bgp((_tp("x", 1, "y"),))
    R = Bgp((_tp("x", 2, "o"),))
    S = Bgp((_tp("o", 3, "z"),))                  # uses arm-only var o
    tree = Join((LeftJoin(L, R), S))
    assert not is_well_designed(tree)
    norm = normalize(tree)
    assert isinstance(norm, Join)                 # no pull-up
    assert is_well_designed(LeftJoin(L, R))


def test_filter_pushdown_reaches_certain_binder_only():
    a = Bgp((_tp("x", 1, "y"),))
    L = Bgp((_tp("x", 2, "z"),))
    R = Bgp((_tp("x", 3, "o"),))
    e = Comparison("=", Var("y"), Const(7))
    norm = normalize(Filter(e, Join((a, LeftJoin(L, R)))))
    # well-designed pull-up floats the OPTIONAL to the top, Bgp-merging fuses
    # a+L, and the filter then sinks through the LeftJoin into the certain
    # left block -- never above the LeftJoin, never into the arm
    assert isinstance(norm, LeftJoin) and norm.right == R
    assert isinstance(norm.left, Filter) and norm.left.expr == e
    assert isinstance(norm.left.child, Bgp)
    assert len(norm.left.child.patterns) == 2
    assert {"x", "y", "z"} == set(certain_variables(norm.left.child))
    assert "o" not in group_variables(norm.left)


def test_filter_never_sinks_into_optional_arm():
    left = Bgp((_tp("x", 1, "y"),))
    arm = Bgp((_tp("x", 2, "o"),))
    e = Comparison("=", Var("o"), Const(5))       # over the arm-only var
    norm = normalize(Filter(e, LeftJoin(left, arm)))
    assert isinstance(norm, Filter)               # stays above the LeftJoin
    assert isinstance(norm.child, LeftJoin)
    assert norm.child.right == arm                # arm untouched


def test_filter_distributes_over_union():
    u = Union((Bgp((_tp("x", 1, "y"),)), Bgp((_tp("x", 2, "y"),))))
    e = Comparison("<", Var("y"), Const(9))
    norm = normalize(Filter(e, u))
    assert isinstance(norm, Union)
    for m in norm.members:
        assert isinstance(m, Filter) and m.expr == e


# --------------------------------------------------------------------------
# Parser round-trips
# --------------------------------------------------------------------------

def _roundtrip(q, d):
    from repro.query.sparql import parse_sparql, serialize_sparql
    q2 = parse_sparql(serialize_sparql(q, d), d)
    assert q2.algebra() == q.algebra()
    assert q2.distinct == q.distinct
    assert q2.projection == q.projection
    return q2


def test_sparql_roundtrip_groups(tiny_fed):
    fed, _ = tiny_fed
    d = fed.dictionary
    p1, p2, p3 = 0, 1, 2                           # any dictionary ids work
    star = Bgp((TriplePattern(Var("x"), Const(p1), Var("y")),
                TriplePattern(Var("x"), Const(p2), Var("z"))))
    arm = Bgp((TriplePattern(Var("x"), Const(p3), Var("o")),))
    cases = [
        from_algebra(star, projection=["x", "y"]),
        from_algebra(LeftJoin(star, arm), projection=["x", "o"]),
        # nested OPTIONAL: arm of an arm
        from_algebra(LeftJoin(star, LeftJoin(
            arm, Bgp((TriplePattern(Var("o"), Const(p1), Var("w")),)))),
            distinct=True, projection=["x"]),
        from_algebra(Union((star, Bgp((TriplePattern(Var("x"), Const(p3),
                                                     Var("y")),)))),
                     projection=["x"]),
        # FILTER placement: inside a branch vs at group end
        from_algebra(Filter(And((Comparison("!=", Var("y"), Var("z")),
                                 Or((Comparison("<", Var("y"), Const(4)),
                                     Not(Comparison("=", Var("z"),
                                                    Const(2))))))), star),
                     projection=["x"]),
        from_algebra(LeftJoin(Filter(Comparison(">=", Var("y"), Const(1)),
                                     star), arm), projection=["x", "o"]),
    ]
    for q in cases:
        _roundtrip(q, d)


def test_sparql_unsupported_constructs_raise_named_errors(tiny_fed):
    from repro.query.sparql import parse_sparql
    fed, _ = tiny_fed
    d = fed.dictionary
    bodies = {
        "GRAPH": "GRAPH ?g { ?x ?p ?y }",
        "SERVICE": "SERVICE <http://ex.org/sparql> { ?x ?p ?y }",
        "MINUS": "?x ?p ?y MINUS { ?x ?q ?y }",
        "BIND": "BIND (?x = ?y)",
        "VALUES": "VALUES ?x { 1 }",
    }
    for kw, body in bodies.items():
        with pytest.raises(ValueError, match=kw):
            parse_sparql(f"SELECT * WHERE {{ {body} }}", d)
    with pytest.raises(ValueError, match="ASK"):
        parse_sparql("ASK WHERE { ?x ?p ?y }", d)


# --------------------------------------------------------------------------
# Plan cache: an OPTIONAL variant never aliases its plain-BGP entry
# --------------------------------------------------------------------------

def test_bgp_warmed_cache_misses_on_optional_variant(tiny_fed, tiny_stats,
                                                     tiny_workload):
    fed, _ = tiny_fed
    base = next(q for q in tiny_workload if len(q.patterns) >= 2)
    opt = OdysseyOptimizer(tiny_stats)
    p1 = opt.optimize(base)
    assert not p1.cached and opt.optimize(base).cached    # warm + sanity hit

    pred = base.patterns[0].p
    variant = from_algebra(
        LeftJoin(Bgp(tuple(base.patterns)),
                 Bgp((TriplePattern(Var("x"), pred, Var("opt0")),))),
        distinct=base.distinct, projection=base.projection)
    assert query_signature(variant)[0] != query_signature(base)[0]
    pv = opt.optimize(variant)
    assert not pv.cached                                  # MISS, not an alias
    assert opt.optimize(variant).cached                   # and its own entry


def _plan_shape(node):
    from repro.core.planner import (
        FilterPlanNode,
        JoinPlanNode,
        LeftJoinPlanNode,
        SubqueryNode,
        UnionPlanNode,
    )

    if isinstance(node, SubqueryNode):
        return ("sq", tuple(node.stars), tuple(node.sources),
                tuple((tp.s, tp.p, tp.o) for tp in node.patterns))
    if isinstance(node, (JoinPlanNode, LeftJoinPlanNode)):
        tag = "join" if isinstance(node, JoinPlanNode) else "leftjoin"
        return (tag, getattr(node, "strategy", None), tuple(node.join_vars),
                _plan_shape(node.left), _plan_shape(node.right))
    if isinstance(node, UnionPlanNode):
        return ("union", tuple(_plan_shape(c) for c in node.children))
    assert isinstance(node, FilterPlanNode)
    return ("filter", node.expr, _plan_shape(node.child))


def test_conjunctive_algebra_plans_identical_to_flat(tiny_fed, tiny_stats,
                                                     tiny_workload):
    """A group tree that *normalizes* to one Bgp routes through the legacy
    flat pipeline and produces the same plan as the flat query."""
    base = next(q for q in tiny_workload if len(q.patterns) >= 3)
    half = len(base.patterns) // 2
    wrapped = from_algebra(
        Join((Bgp(tuple(base.patterns[:half])),
              Bgp(tuple(base.patterns[half:])))),
        distinct=base.distinct, projection=base.projection)
    assert wrapped.root is not None and wrapped.is_conjunctive()
    flat = OdysseyOptimizer(tiny_stats).optimize(base)
    alg = OdysseyOptimizer(tiny_stats).optimize(wrapped)
    assert _plan_shape(flat.root) == _plan_shape(alg.root)
    assert flat.root.est_cardinality == alg.root.est_cardinality


def test_plain_bgp_planning_matches_reference_dp(tiny_stats, tiny_workload):
    """The bitmask DP the per-block pipeline runs stays bit-identical to the
    frozenset reference DP on every conjunctive workload query."""
    from repro.core.cost import CostModel
    from repro.core.decomposition import decompose
    from repro.core.join_order import dp_join_order, dp_join_order_ref
    from repro.core.source_selection import select_sources

    cm = CostModel()
    for q in tiny_workload:
        graph = decompose(q)
        sel = select_sources(graph, tiny_stats)
        new = dp_join_order(graph, tiny_stats, sel, cm, q.distinct)
        ref = dp_join_order_ref(graph, tiny_stats, sel, cm, q.distinct)
        assert new.leaf_order() == ref.leaf_order()
        np.testing.assert_allclose(new.cost, ref.cost, rtol=1e-9)
        np.testing.assert_allclose(new.cardinality, ref.cardinality, rtol=1e-9)


# --------------------------------------------------------------------------
# Differential suite: planner + engine vs the naive oracle
# --------------------------------------------------------------------------

def test_extended_workload_matches_oracle(tiny_fed, tiny_stats):
    from repro.rdf.generator import generate_extended_workload

    fed, gt = tiny_fed
    queries = generate_extended_workload(fed, gt, seed=17)
    assert len(queries) == 16
    fams = {q.name[:2] for q in queries}
    assert fams == {"OS", "UN", "FC"}              # all three families
    opt = OdysseyOptimizer(tiny_stats)
    nonempty = 0
    for q in queries:
        plan = opt.optimize(q)
        got = _engine_rows(fed, plan, q)
        want = naive_evaluate(fed, q)
        assert got == want, q.name
        nonempty += bool(want)
    assert nonempty == len(queries)                # families stay non-empty


def _random_tree(rng, leaves, depth):
    """Random group tree <= `depth` combinator levels over star leaves that
    share the center variable ``x``."""
    if depth == 0 or rng.random() < 0.3:
        return Bgp(tuple(leaves[int(rng.integers(len(leaves)))]))
    kind = rng.integers(4)
    if kind == 0:
        return Join((_random_tree(rng, leaves, depth - 1),
                     _random_tree(rng, leaves, depth - 1)))
    if kind == 1:
        return LeftJoin(_random_tree(rng, leaves, depth - 1),
                        _random_tree(rng, leaves, depth - 1))
    if kind == 2:
        return Union((_random_tree(rng, leaves, depth - 1),
                      _random_tree(rng, leaves, depth - 1)))
    child = _random_tree(rng, leaves, depth - 1)
    cvars = sorted(certain_variables(child))
    if len(cvars) < 2:
        return child
    a, b = rng.choice(cvars, size=2, replace=False).tolist()
    op = str(rng.choice(["=", "!=", "<", "<=", ">", ">="]))
    return Filter(Comparison(op, Var(a), Var(b)), child)


def _star_leaves(fed, gt, rng):
    """2-pattern star leaves sharing the center variable ``x``, satellite
    variables renamed per leaf so OPTIONAL arms bind private variables."""
    from repro.rdf.generator import _star_patterns

    leaves = []
    for src in [s.name for s in fed.sources]:
        for tmpl in range(len(gt.template_preds[src])):
            pats = _star_patterns(rng, fed, gt, src, tmpl, "x", 2,
                                  bind_obj=False)
            if pats is not None:
                i = len(leaves)
                ren = {f"x_v{j}": f"l{i}_v{j}" for j in range(2)}
                leaves.append([TriplePattern(
                    tp.s, tp.p,
                    Var(ren[tp.o.name]) if isinstance(tp.o, Var) else tp.o)
                    for tp in pats])
    return leaves


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_random_group_trees_match_oracle(tiny_fed, tiny_stats, seed):
    """Seeded randomized differential over group trees <= 3 levels: planner
    plus engine must agree with the raw-tree oracle on every draw (the
    hypothesis twin in test_property.py explores the same space)."""
    fed, gt = tiny_fed
    rng = np.random.default_rng(100 + seed)
    leaves = _star_leaves(fed, gt, rng)
    assert len(leaves) >= 2
    for _ in range(6):
        root = _random_tree(rng, leaves, depth=int(rng.integers(1, 4)))
        q = from_algebra(root, distinct=bool(rng.random() < 0.5),
                         projection=sorted(certain_variables(root)))
        plan = OdysseyOptimizer(tiny_stats).optimize(q)
        assert _engine_rows(fed, plan, q) == naive_evaluate(fed, q)


def test_optional_answers_carry_undef(tiny_fed, tiny_stats):
    """An OS-family query must actually produce UNDEF cells somewhere across
    the workload -- otherwise the OPTIONAL arms are accidentally total and
    the family tests nothing."""
    from repro.engine.local import UNDEF
    from repro.rdf.generator import generate_extended_workload

    fed, gt = tiny_fed
    queries = [q for q in generate_extended_workload(fed, gt, seed=17)
               if q.name.startswith("OS")]
    opt = OdysseyOptimizer(tiny_stats)
    seen_undef = False
    for q in queries:
        for row in _engine_rows(fed, opt.optimize(q), q):
            if UNDEF in row:
                seen_undef = True
    assert seen_undef


def test_spmd_engine_rejects_algebra_plans(tiny_fed, tiny_stats):
    from repro.engine.distributed import DistributedEngine, _Run
    from repro.rdf.generator import generate_extended_workload

    fed, gt = tiny_fed
    q = generate_extended_workload(fed, gt, n_optional=1, n_union=0,
                                   n_filtered=0, seed=17)[0]
    plan = OdysseyOptimizer(tiny_stats).optimize(q)
    # the dispatch guard fires before any mesh/device state is touched, so a
    # bare instance is enough -- no fake-device subprocess needed here
    eng = object.__new__(DistributedEngine)
    with pytest.raises(NotImplementedError, match="conjunctive"):
        eng._eval_node(plan.root, _Run(cap=2048))
