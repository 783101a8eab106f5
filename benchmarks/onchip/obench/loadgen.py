"""The one traffic generator: queries drawn from a deployment, and when they
are sent.  A traffic mix is a data file (``traffic/<mix>.json``) of the
parameters read here:

- ``loop``: ``"open"`` (requests due on a Poisson schedule at ``rate_qps``,
  whatever the server does) or ``"closed"`` (``clients`` callers, each
  sending its next request as soon as its last one is answered);
- ``templates``: how many star, hybrid (two stars joined by a link) and
  path (a link followed by one pattern) query templates to draw, with
  ``star_patterns`` and ``hybrid_star_patterns`` (the ranges of patterns
  per star) and ``sources`` (the sources they may come from, all if absent),
  from ``template_seed``: the same templates for every run seed;
- ``bind``: ``"first_subject"`` binds each request's first star subject to
  an entity drawn from those that give the template an answer, so nearly
  every request is a new query; ``"none"`` sends the templates as they are;
- ``popularity_zipf_s``: template popularity, Zipf over a fixed random
  ranking; every template is sent the largest-remainder rounding of its
  share of the requests;
- ``warmup_s``: seconds of this traffic, drawn from a seed the window does
  not use, that set-up serves unmeasured (at most the window's length), so
  that every program the traffic's batches reach is compiled before the
  window opens.

The arrival times and the order of the templates are a trace fixed by
``template_seed``; the run seed draws only the entities each request binds.
So seeds change which queries are asked, not how much work arrives when.
With the order drawn per seed, a simulation from the service times measured
on the chip put the quartile spread of the open cell's p50 at 50-100%
between seeds: which heavy request lands before which cluster of arrivals decided it.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from obench.reference import Query


@dataclass(frozen=True)
class Template:
    query: Query
    bind: str | None = None            # the variable each request binds
    pool: np.ndarray | None = None     # the entities it is bound to

    def instance(self, entity: int | None) -> Query:
        if self.bind is None:
            return self.query
        sub = lambda t: entity if t == self.bind else t  # noqa: E731
        q = self.query
        return Query(q.name, tuple(tuple(sub(t) for t in tp) for tp in q.patterns),
                     q.projection, q.distinct)


def _star(rng, data, src: str, tmpl: int, var: str, k: int):
    """A k-pattern star over one template's predicates, subject ``var``:
    every entity of the template matches it."""
    preds = data.gt.template_preds[src][tmpl]
    if len(data.gt.template_entities[src][tmpl]) == 0 or len(preds) < k:
        return None
    chosen = rng.choice(preds, size=k, replace=False)
    return [(var, int(p), f"{var}_v{j}") for j, p in enumerate(chosen.tolist())]


def make_templates(data, mix: dict) -> list[Template]:
    """The mix's query templates over ``data`` (module docstring)."""
    rng = np.random.default_rng(mix["template_seed"])
    gt = data.gt
    sources = mix.get("sources") or data.names
    bound = mix["bind"] == "first_subject"
    lo, hi = mix.get("star_patterns", (2, 4))
    hlo, hhi = mix.get("hybrid_star_patterns", (1, 3))
    want = mix["templates"]
    sid = [data.source_index(s) for s in sources]
    links = gt.cross_links[np.isin(gt.cross_links[:, 0], sid)
                           & np.isin(gt.cross_links[:, 1], sid)]
    out: list[Template] = []

    def add(kind: str, n: int, patterns, proj, distinct, pool):
        q = Query(f"{kind}{n}", tuple(patterns), tuple(proj), distinct)
        if bound:
            proj = [v for v in q.variables() if v != "x"]
            out.append(Template(Query(q.name, q.patterns, tuple(proj), distinct),
                                "x", np.unique(pool)))
        else:
            out.append(Template(q))

    made = attempts = 0
    while made < want.get("star", 0) and attempts < 400:
        attempts += 1
        src = str(rng.choice(sources))
        tmpl = int(rng.integers(len(gt.template_preds[src])))
        pats = _star(rng, data, src, tmpl, "x", int(rng.integers(lo, hi + 1)))
        if pats is None:
            continue
        made += 1
        add("ST", made, pats, ["x"], bool(rng.random() < 0.5),
            gt.template_entities[src][tmpl])

    made = attempts = 0
    while made < want.get("hybrid", 0) and attempts < 400 and len(links):
        attempts += 1
        si, di, s_e, pred, o_e = links[int(rng.integers(len(links)))].tolist()
        src, dst = data.names[si], data.names[di]
        t1 = int(gt.template_of(src, s_e))
        t2 = int(gt.template_of(dst, o_e))
        p1 = _star(rng, data, src, t1, "x", int(rng.integers(hlo, hhi + 1)))
        p2 = _star(rng, data, dst, t2, "y", int(rng.integers(hlo, hhi + 1)))
        if p1 is None or p2 is None:
            continue
        same = links[(links[:, 0] == si) & (links[:, 1] == di) & (links[:, 3] == pred)]
        ok = (gt.template_of(src, same[:, 2]) == t1) & (gt.template_of(dst, same[:, 4]) == t2)
        made += 1
        add("HY", made, p1 + [("x", int(pred), "y")] + p2, ["x", "y"],
            bool(rng.random() < 0.5), same[ok, 2])

    made = attempts = 0
    while made < want.get("path", 0) and attempts < 400 and len(links):
        attempts += 1
        si, di, s_e, pred, o_e = links[int(rng.integers(len(links)))].tolist()
        src, dst = data.names[si], data.names[di]
        preds2 = gt.template_preds[dst][int(gt.template_of(dst, o_e))]
        if not preds2:
            continue
        q = int(rng.choice(preds2))
        same = links[(links[:, 0] == si) & (links[:, 1] == di) & (links[:, 3] == pred)]
        has_q = [t for t, ps in enumerate(gt.template_preds[dst]) if q in ps]
        ok = np.isin(gt.template_of(dst, same[:, 4]), has_q)
        made += 1
        add("PA", made, [("x", int(pred), "y"), ("y", q, "z")], ["x", "z"], True,
            same[ok, 2])
    return out


def popularity(n: int, s: float, rank_seed: int) -> np.ndarray:
    """Zipf shares of ``n`` templates over a fixed random ranking."""
    w = 1.0 / np.arange(1, n + 1) ** s
    rank = np.random.default_rng(rank_seed).permutation(n)
    return (w / w.sum())[rank]


def fixed_counts(shares: np.ndarray, total: int) -> np.ndarray:
    """``total`` split by ``shares``, rounded by largest remainder."""
    raw = shares * total
    counts = np.floor(raw).astype(int)
    counts[np.argsort(counts - raw, kind="stable")[:total - counts.sum()]] += 1
    return counts


def poisson_offsets(n: int, window_s: float, rng) -> np.ndarray:
    """Open-loop due times: exponential gaps scaled to span the window."""
    gaps = rng.exponential(scale=1.0, size=n)
    return np.cumsum(gaps) * (window_s / max(float(gaps.sum()), 1e-9))


def run_rng(seed: int) -> np.random.Generator:
    """The generator of one run's draws; any whole number is a seed."""
    return np.random.default_rng([abs(seed), int(seed < 0)])


class Traffic:
    """The mix over one deployment; each run draws its requests from its
    own seed."""

    def __init__(self, data, mix: dict):
        self.mix = mix
        self.loop = mix["loop"]
        self.templates = make_templates(data, mix)
        if not self.templates:
            raise ValueError("the mix draws no template from this deployment")
        self.shares = popularity(len(self.templates), mix["popularity_zipf_s"],
                                 mix["template_seed"])
        self._heaviest = self._subject_counts(data)

    def _subject_counts(self, data) -> dict:
        """Each bound template's warm-up entity: the one with most triples."""
        out = {}
        counts = np.bincount(np.concatenate([t[:, 0] for t in data.triples]))
        for t in self.templates:
            if t.bind is not None:
                out[t.query.name] = int(t.pool[np.argmax(counts[t.pool])])
        return out

    def _draw(self, rng, idx: np.ndarray) -> list[Query]:
        out = []
        for i in idx.tolist():
            t = self.templates[i]
            out.append(t.instance(None if t.bind is None else int(rng.choice(t.pool))))
        return out

    def warmup(self) -> list[Query]:
        """One instance of every template, bound to its heaviest entity."""
        return [t.instance(self._heaviest.get(t.query.name)) for t in self.templates]

    def _trace_rng(self) -> np.random.Generator:
        return np.random.default_rng([self.mix["template_seed"], 1])

    def schedule(self, seed: int, seconds: float) -> tuple[np.ndarray, list[Query]]:
        """Open loop: due offsets (seconds) and the query due at each."""
        trace = self._trace_rng()
        n = max(1, int(round(self.mix["rate_qps"] * seconds)))
        idx = trace.permutation(np.repeat(np.arange(len(self.templates)),
                                          fixed_counts(self.shares, n)))
        return poisson_offsets(n, seconds, trace), self._draw(run_rng(seed), idx)

    def stream(self, seed: int):
        """Closed loop: the queries the clients send, in order, without end;
        every cycle of ``cycle`` requests holds each template its share."""
        trace, rng = self._trace_rng(), run_rng(seed)
        counts = fixed_counts(self.shares, self.mix.get("cycle", 64))
        base = np.repeat(np.arange(len(self.templates)), counts)
        while True:
            yield from self._draw(rng, trace.permutation(base))
