"""Plain reference: the answer of a conjunctive query over the union of a
deployment's triples, in numpy, independent of the program.

A query is a ``Query`` of the benchmark's own: patterns of (s, p, o) where
an ``int`` is a constant term id and a ``str`` a variable name.  The answer
is SPARQL's: every solution of the basic graph pattern over the union of the
sources (each triple once), projected; without DISTINCT each solution is a
row (bag semantics), with DISTINCT each projected row once.  Answers are
compared in one canonical form: an ``(n, k)`` int64 array of the projected
rows, sorted lexicographically.

Evaluation starts from the pattern with the fewest matches and adds, each
time, a pattern that shares a variable with those before it, semi-joined
first on the values bound so far: cheap where a subject is bound, and a
sort-merge join throughout.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Query:
    name: str
    patterns: tuple           # ((s, p, o), ...): int constant or str variable
    projection: tuple         # variable names
    distinct: bool

    def variables(self) -> list[str]:
        out: list[str] = []
        for tp in self.patterns:
            for t in tp:
                if isinstance(t, str) and t not in out:
                    out.append(t)
        return out


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


def join(left: dict, right: dict) -> dict:
    """Inner join on the shared variables (a cross product if none)."""
    shared = [v for v in left if v in right]
    nl, nr = _nrows(left), _nrows(right)
    if not shared:
        li = np.repeat(np.arange(nl), nr)
        ri = np.tile(np.arange(nr), nl)
    else:
        lk, rk = _keys(left, shared, right)
        order = np.argsort(rk, kind="stable")
        rk_s = rk[order]
        lo = np.searchsorted(rk_s, lk, side="left")
        cnt = np.searchsorted(rk_s, lk, side="right") - lo
        li = np.repeat(np.arange(nl), cnt)
        first = np.cumsum(cnt) - cnt
        ri = order[lo[li] + (np.arange(len(li)) - first[li])]
    out = {v: c[li] for v, c in left.items()}
    out.update({v: c[ri] for v, c in right.items() if v not in out})
    return out


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

    def evaluate(self, q: Query) -> np.ndarray:
        """The canonical answer of ``q``."""
        if any(not any(isinstance(t, str) for t in tp) for tp in q.patterns):
            raise ValueError(f"{q.name}: a pattern without a variable")
        scans = [self.scan(tp) for tp in q.patterns]
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
        n = _nrows(rel)
        if todo or any(v not in rel for v in q.projection):
            n = 0
        rows = np.stack([rel[v][:n] if v in rel else np.zeros(0, np.int64)
                         for v in q.projection], axis=1) if q.projection \
            else np.zeros((n, 0), np.int64)
        if q.distinct and len(rows):
            rows = np.unique(rows, axis=0)
        return canonical(rows)
