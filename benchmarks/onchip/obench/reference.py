"""Plain reference: the answer of a query over the union of a deployment's
triples, in numpy, independent of the program.

A query is a ``Query`` of the benchmark's own: patterns of (s, p, o) where
an ``int`` is a constant term id and a ``str`` a variable name.  The answer
is SPARQL's: every solution over the union of the sources (each triple
once), projected; without DISTINCT each solution is a row (bag semantics),
with DISTINCT each projected row once.  Answers are compared in one
canonical form: an ``(n, k)`` int64 array of the projected rows, sorted
lexicographically.

A conjunctive query is its basic graph pattern alone.  Evaluation starts
from the pattern with the fewest matches and adds, each time, a pattern
that shares a variable with those before it, semi-joined first on the
values bound so far: cheap where a subject is bound, and a sort-merge join
throughout.

A query may instead carry a group tree (``Query.tree``) of hashable tuples:

- ``("bgp", patterns)``: a basic graph pattern, solved as above;
- ``("optional", left, arm)``: OPTIONAL, a left join: every solution of
  ``left`` joined with each compatible solution of ``arm``, and every
  solution of ``left`` that has none kept once, with ``UNDEF`` in the
  arm's own columns;
- ``("union", members)``: UNION, the bag concatenation of the members'
  solutions, ``UNDEF`` in a column that a member does not bind;
- ``("filter", (op, lhs, rhs), child)``: FILTER on one comparison, ``op``
  one of ``= != < <= > >=``, each side a variable or a term id.

A projection variable that the tree never binds is ``UNDEF`` in every row.
These are the semantics of the program's group algebra
(``docs/algebra.md``), implemented here on their own.  Where they depart
from SPARQL 1.1: a comparison orders term ids, not the values of the terms
(a literal compares by its id); and it is two-valued, false where a side is
unbound, where SPARQL's is an error (the same for a lone comparison, not
under a negation).  A join key never holds ``UNDEF`` in the trees the
traffic draws (an arm shares only ``?x`` with its left side, which binds it
in every solution); the reference refuses one that does rather than
evaluate SPARQL's compatibility of unbound values.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


UNDEF = -1                    # an unbound cell; term ids are not negative
COMPARE = {"=": np.equal, "!=": np.not_equal, "<": np.less, "<=": np.less_equal,
           ">": np.greater, ">=": np.greater_equal}


@dataclass(frozen=True)
class Query:
    name: str
    patterns: tuple           # ((s, p, o), ...): int constant or str variable
    projection: tuple         # variable names
    distinct: bool
    tree: tuple | None = None  # group tree (module docstring); None: the BGP alone

    def variables(self) -> list[str]:
        out: list[str] = []
        for tp in self.patterns:
            for t in tp:
                if isinstance(t, str) and t not in out:
                    out.append(t)
        return out


def tree_patterns(node: tuple) -> tuple:
    """The triple patterns of a group tree, in tree order."""
    kind = node[0]
    if kind == "bgp":
        return tuple(node[1])
    if kind == "optional":
        return tree_patterns(node[1]) + tree_patterns(node[2])
    if kind == "union":
        return tuple(tp for m in node[1] for tp in tree_patterns(m))
    if kind == "filter":
        return tree_patterns(node[2])
    raise ValueError(f"unknown group node {kind!r}")


def tree_query(name: str, tree: tuple, projection, distinct: bool) -> Query:
    """A query of a group tree; its ``patterns`` are the tree's, flattened.
    A tree that is one BGP gives the conjunctive query, with no tree."""
    return Query(name, tree_patterns(tree), tuple(projection), distinct,
                 None if tree[0] == "bgp" else tree)


def bind_tree(node: tuple, var: str, term: int) -> tuple:
    """``node`` with the variable ``var`` replaced by ``term`` throughout,
    in filter comparisons as well."""
    sub = lambda t: term if t == var else t  # noqa: E731
    kind = node[0]
    if kind == "bgp":
        return ("bgp", tuple(tuple(sub(t) for t in tp) for tp in node[1]))
    if kind == "optional":
        return ("optional", bind_tree(node[1], var, term), bind_tree(node[2], var, term))
    if kind == "union":
        return ("union", tuple(bind_tree(m, var, term) for m in node[1]))
    op, lhs, rhs = node[1]
    return ("filter", (op, sub(lhs), sub(rhs)), bind_tree(node[2], var, term))


def canonical(rows: np.ndarray) -> np.ndarray:
    """``rows`` (n, k) sorted lexicographically, as int64."""
    rows = np.asarray(rows, np.int64)
    if len(rows) == 0 or rows.shape[1] == 0:
        return rows
    return rows[np.lexsort(rows.T[::-1])]


def _keys(rel: dict, names: list[str], other: dict) -> tuple[np.ndarray, np.ndarray]:
    """One int64 key per row of ``rel`` and of ``other`` on ``names``,
    equal exactly where the rows agree on all of them."""
    if len(names) == 1:
        return rel[names[0]].astype(np.int64), other[names[0]].astype(np.int64)
    a = np.stack([rel[v] for v in names], axis=1).astype(np.int64)
    b = np.stack([other[v] for v in names], axis=1).astype(np.int64)
    _, inv = np.unique(np.concatenate([a, b]), axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    return inv[:len(a)], inv[len(a):]


def _nrows(rel: dict) -> int:
    return len(next(iter(rel.values()))) if rel else 0


def _pairs(left: dict, right: dict) -> tuple[np.ndarray, np.ndarray]:
    """The row pairs of the inner join on the shared variables (every pair
    if none): left rows ``li`` matched with right rows ``ri``."""
    shared = [v for v in left if v in right]
    nl, nr = _nrows(left), _nrows(right)
    if not shared:
        return np.repeat(np.arange(nl), nr), np.tile(np.arange(nr), nl)
    lk, rk = _keys(left, shared, right)
    order = np.argsort(rk, kind="stable")
    rk_s = rk[order]
    lo = np.searchsorted(rk_s, lk, side="left")
    cnt = np.searchsorted(rk_s, lk, side="right") - lo
    li = np.repeat(np.arange(nl), cnt)
    first = np.cumsum(cnt) - cnt
    return li, order[lo[li] + (np.arange(len(li)) - first[li])]


def join(left: dict, right: dict) -> dict:
    """Inner join on the shared variables (a cross product if none)."""
    li, ri = _pairs(left, right)
    out = {v: c[li] for v, c in left.items()}
    out.update({v: c[ri] for v, c in right.items() if v not in out})
    return out


def _no_undef_keys(left: dict, right: dict):
    for v in left:
        if v in right and (np.any(left[v] == UNDEF) or np.any(right[v] == UNDEF)):
            raise ValueError(f"join key ?{v} holds UNDEF: outside what the reference evaluates")


def left_join(left: dict, arm: dict) -> dict:
    """OPTIONAL: the join, then every left row without a match once, its
    arm-only columns ``UNDEF``."""
    _no_undef_keys(left, arm)
    li, ri = _pairs(left, arm)
    alone = np.setdiff1d(np.arange(_nrows(left)), li)
    out = {v: np.concatenate([c[li], c[alone]]) for v, c in left.items()}
    for v, c in arm.items():
        if v not in out:
            out[v] = np.concatenate([c[ri], np.full(len(alone), UNDEF, c.dtype)])
    return out


def union(members: list) -> dict:
    """UNION: the members' rows one after another, ``UNDEF`` in a column
    that a member does not bind."""
    names = [v for i, m in enumerate(members) for v in m
             if all(v not in prev for prev in members[:i])]
    return {v: np.concatenate([m[v].astype(np.int64) if v in m
                               else np.full(_nrows(m), UNDEF, np.int64) for m in members])
            for v in names}


def keep_where(rel: dict, comparison: tuple) -> dict:
    """FILTER: the rows on which ``(op, lhs, rhs)`` holds, two-valued: false
    where a side is unbound (``UNDEF``, or a variable with no column)."""
    op, lhs, rhs = comparison
    n = _nrows(rel)

    def side(t):
        if isinstance(t, str):
            return rel[t].astype(np.int64) if t in rel else np.full(n, UNDEF, np.int64)
        return np.full(n, int(t), np.int64)

    a, b = side(lhs), side(rhs)
    mask = (a != UNDEF) & (b != UNDEF) & COMPARE[op](a, b)
    return {v: c[mask] for v, c in rel.items()}


class Reference:
    """The union of a deployment's sources, indexed by predicate."""

    def __init__(self, triples: list[np.ndarray]):
        t = np.concatenate(triples).astype(np.int64)
        order = np.lexsort((t[:, 2], t[:, 0], t[:, 1]))
        t = t[order]
        if len(t):
            keep = np.ones(len(t), bool)
            keep[1:] = np.any(t[1:] != t[:-1], axis=1)
            t = t[keep]
        self.t = t
        self.preds, self.starts = np.unique(t[:, 1], return_index=True)
        self.ends = np.append(self.starts[1:], len(t))

    def scan(self, tp: tuple) -> dict:
        """The bindings of one pattern's variables, one row per match."""
        s, p, o = tp
        if isinstance(p, str):
            rows = self.t
        else:
            i = np.searchsorted(self.preds, p)
            if i == len(self.preds) or self.preds[i] != p:
                rows = self.t[:0]
            else:
                rows = self.t[self.starts[i]:self.ends[i]]
        mask = np.ones(len(rows), bool)
        out: dict[str, np.ndarray] = {}
        for col, term in enumerate((s, p, o)):
            if isinstance(term, str):
                if term in out:                         # repeated variable
                    mask &= rows[:, col] == out[term]
                else:
                    out[term] = rows[:, col]
            else:
                mask &= rows[:, col] == term
        return {v: c[mask] for v, c in out.items()}

    def solve(self, patterns) -> dict:
        """Every solution of a basic graph pattern: one column per variable
        (no rows where it has none)."""
        if any(not any(isinstance(t, str) for t in tp) for tp in patterns):
            raise ValueError("a pattern without a variable")
        scans = [self.scan(tp) for tp in patterns]
        todo = list(range(len(scans)))
        first = min(todo, key=lambda i: _nrows(scans[i]))
        todo.remove(first)
        rel = scans[first]
        while todo and _nrows(rel):
            linked = [i for i in todo if set(scans[i]) & set(rel)] or todo
            nxt = min(linked, key=lambda i: _nrows(scans[i]))
            todo.remove(nxt)
            right = scans[nxt]
            for v in set(right) & set(rel):             # semi-join first
                keep = np.isin(right[v], rel[v])
                right = {k: c[keep] for k, c in right.items()}
            rel = join(rel, right)
        if todo:                    # an empty part: the pattern has no solution
            rel = {v: c[:0] for v, c in rel.items()}
            for i in todo:
                rel.update({v: c[:0] for v, c in scans[i].items() if v not in rel})
        return rel

    def group(self, node: tuple) -> dict:
        """Every solution of a group tree (module docstring)."""
        kind = node[0]
        if kind == "bgp":
            return self.solve(node[1])
        if kind == "optional":
            return left_join(self.group(node[1]), self.group(node[2]))
        if kind == "union":
            return union([self.group(m) for m in node[1]])
        if kind == "filter":
            return keep_where(self.group(node[2]), node[1])
        raise ValueError(f"unknown group node {kind!r}")

    def evaluate(self, q: Query) -> np.ndarray:
        """The canonical answer of ``q``."""
        if q.tree is not None:
            rel = self.group(q.tree)
            n = _nrows(rel)
            rows = np.stack([rel[v].astype(np.int64) if v in rel
                             else np.full(n, UNDEF, np.int64) for v in q.projection], axis=1) \
                if q.projection else np.zeros((n, 0), np.int64)
        else:
            rel = self.solve(q.patterns)
            n = _nrows(rel)
            if any(v not in rel for v in q.projection):
                n = 0
            rows = np.stack([rel[v][:n] if v in rel else np.zeros(0, np.int64)
                             for v in q.projection], axis=1) if q.projection \
                else np.zeros((n, 0), np.int64)
        if q.distinct and len(rows):
            rows = np.unique(rows, axis=0)
        return canonical(rows)
