"""The one traffic generator: queries drawn from a deployment, and when they
are sent.  A traffic mix is a data file (``traffic/<mix>.json``) of the
parameters read here:

- ``loop``: ``"open"`` (requests due on a Poisson schedule at ``rate_qps``,
  whatever the server does) or ``"closed"`` (``clients`` callers, each
  sending its next request as soon as its last one is answered);
- ``templates``: how many query templates of each kind to draw, from
  ``template_seed`` (the same templates for every run seed), out of the
  ``sources`` named (all if absent):

  - ``star``: one star on ``?x``, ``star_patterns`` patterns;
  - ``hybrid``: two stars joined by a link, each of ``hybrid_star_patterns``
    patterns;
  - ``path``: a link followed by one pattern;
  - ``optional`` (FedBench LS7's OPTIONAL): a star or a hybrid, and an
    OPTIONAL arm of ``arm_patterns`` patterns on ``?x`` (1-2 if absent)
    whose predicates come from another template of ``?x``'s source; an arm
    is kept only where the share of the left side's solutions that match it
    lies within ``ARM_SHARE``, so that answers hold both matched and padded
    rows;
  - ``union`` (LS1): a UNION of two stars on ``?x`` from two sources, each
    of ``union_patterns`` patterns (1-3 if absent), each binding ``?v`` as
    its first object, projected on ``(?x, ?v)``;
  - ``filter`` (LS7's FILTER): a star or a hybrid, and one comparison
    ``?v op c`` on an object of ``?x``'s star, ``op`` one of ``<``, ``>``,
    ``!=`` and ``c`` a term id among ``?v``'s values; it is kept only where
    it drops some solutions and keeps some.

  A mix that names none of the last three draws exactly what it drew before
  they existed: the same draws, in the same order;
- ``bind``: ``"first_subject"`` binds each request's ``?x`` (everywhere in
  the query, filter comparisons included) to an entity drawn from those that
  give the template's required part (all but an OPTIONAL arm) an answer, so
  nearly every request is a new query; ``"none"`` sends the templates as
  they are;
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

from obench.reference import Query, Reference, bind_tree, keep_where, tree_query

ARM_SHARE = (0.1, 0.9)   # the share of an OPTIONAL's left solutions its arm matches


@dataclass(frozen=True)
class Template:
    query: Query
    bind: str | None = None            # the variable each request binds
    pool: np.ndarray | None = None     # the entities it is bound to

    def instance(self, entity: int | None) -> Query:
        if self.bind is None:
            return self.query
        q = self.query
        tree = bind_tree(q.tree or ("bgp", q.patterns), self.bind, entity)
        return tree_query(q.name, tree, q.projection, q.distinct)


def _star(rng, data, src: str, tmpl: int, var: str, k: int):
    """A k-pattern star over one template's predicates, subject ``var``:
    every entity of the template matches it."""
    preds = data.gt.template_preds[src][tmpl]
    if len(data.gt.template_entities[src][tmpl]) == 0 or len(preds) < k:
        return None
    chosen = rng.choice(preds, size=k, replace=False)
    return [(var, int(p), f"{var}_v{j}") for j, p in enumerate(chosen.tolist())]


def _draw_star(rng, data, sources, lo: int, hi: int):
    """A star on ``?x`` over one template of a source drawn from
    ``sources``: its patterns, source, template and entities; ``None``
    where the template cannot give it."""
    src = str(rng.choice(sources))
    tmpl = int(rng.integers(len(data.gt.template_preds[src])))
    pats = _star(rng, data, src, tmpl, "x", int(rng.integers(lo, hi + 1)))
    if pats is None:
        return None
    return pats, src, tmpl, data.gt.template_entities[src][tmpl]


def _draw_hybrid(rng, data, links: np.ndarray, lo: int, hi: int):
    """Two stars, on ``?x`` and ``?y``, joined by a link ``?x pred ?y``
    drawn from ``links``: its patterns, ``?x``'s source and template, and
    the subjects of links between the two templates; ``None`` where a
    template cannot give its star."""
    gt = data.gt
    si, di, s_e, pred, o_e = links[int(rng.integers(len(links)))].tolist()
    src, dst = data.names[si], data.names[di]
    t1 = int(gt.template_of(src, s_e))
    t2 = int(gt.template_of(dst, o_e))
    p1 = _star(rng, data, src, t1, "x", int(rng.integers(lo, hi + 1)))
    p2 = _star(rng, data, dst, t2, "y", int(rng.integers(lo, hi + 1)))
    if p1 is None or p2 is None:
        return None
    same = links[(links[:, 0] == si) & (links[:, 1] == di) & (links[:, 3] == pred)]
    ok = (gt.template_of(src, same[:, 2]) == t1) & (gt.template_of(dst, same[:, 4]) == t2)
    return p1 + [("x", int(pred), "y")] + p2, src, t1, same[ok, 2]


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
        got = _draw_star(rng, data, sources, lo, hi)
        if got is None:
            continue
        made += 1
        add("ST", made, got[0], ["x"], bool(rng.random() < 0.5), got[3])

    made = attempts = 0
    while made < want.get("hybrid", 0) and attempts < 400 and len(links):
        attempts += 1
        got = _draw_hybrid(rng, data, links, hlo, hhi)
        if got is None:
            continue
        made += 1
        add("HY", made, got[0], ["x", "y"], bool(rng.random() < 0.5), got[3])

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

    if any(want.get(k, 0) for k in ("optional", "union", "filter")):
        out += _algebra_templates(rng, data, mix, sources, links, bound)
    return out


def _algebra_templates(rng, data, mix: dict, sources, links, bound: bool) -> list[Template]:
    """The mix's OPTIONAL, UNION and FILTER templates (module docstring),
    each kept only where the data gives it the answers it is drawn for."""
    gt = data.gt
    ref = Reference(data.triples)
    want = mix["templates"]
    lo, hi = mix.get("star_patterns", (2, 4))
    hlo, hhi = mix.get("hybrid_star_patterns", (1, 3))
    alo, ahi = mix.get("arm_patterns", (1, 2))
    ulo, uhi = mix.get("union_patterns", (1, 3))
    out: list[Template] = []

    def add(name: str, tree: tuple, distinct: bool, solutions: dict, proj=None):
        """A template of ``tree``, projected on ``proj`` (every variable if
        ``None``); the ``?x`` of ``solutions`` are the entities it binds."""
        proj = tree_query(name, tree, (), distinct).variables() if proj is None else proj
        if bound:
            out.append(Template(tree_query(name, tree, [v for v in proj if v != "x"], distinct),
                                "x", np.unique(solutions["x"])))
        else:
            out.append(Template(tree_query(name, tree, proj, distinct)))

    def left_side():
        if len(links) and rng.random() < 0.5:
            return _draw_hybrid(rng, data, links, hlo, hhi)
        return _draw_star(rng, data, sources, lo, hi)

    made = attempts = 0
    while made < want.get("optional", 0) and attempts < 400:
        attempts += 1
        got = left_side()
        if got is None:
            continue
        pats, src, tmpl, _ = got
        used = {tp[1] for tp in pats if tp[0] == "x"}
        others = [t for t in range(len(gt.template_preds[src])) if t != tmpl]
        if not others:
            continue
        other = gt.template_preds[src][int(rng.choice(others))]
        preds = [p for p in other if p not in used]
        k = int(rng.integers(alo, ahi + 1))
        if len(preds) < k:
            continue
        arm = [("x", int(p), f"a{j}") for j, p in enumerate(rng.choice(preds, k, replace=False))]
        left = ref.solve(pats)
        if not len(left["x"]):
            continue
        share = float(np.mean(np.isin(left["x"], ref.solve(arm)["x"])))
        if not ARM_SHARE[0] <= share <= ARM_SHARE[1]:
            continue
        made += 1
        add(f"OP{made}", ("optional", ("bgp", tuple(pats)), ("bgp", tuple(arm))),
            bool(rng.random() < 0.5), left)

    made = attempts = 0
    while made < want.get("union", 0) and attempts < 400 and len(sources) > 1:
        attempts += 1
        members = []
        for m, src in enumerate(rng.choice(sources, size=2, replace=False).tolist()):
            tmpl = int(rng.integers(len(gt.template_preds[src])))
            pats = _star(rng, data, src, tmpl, "x", int(rng.integers(ulo, uhi + 1)))
            if pats is None:
                break
            names = {tp[2]: ("v" if j == 0 else f"u{m}_{tp[2]}") for j, tp in enumerate(pats)}
            members.append(("bgp", tuple((s, p, names[o]) for s, p, o in pats)))
        if len(members) < 2:
            continue
        tree = ("union", tuple(members))
        solutions = ref.group(tree)
        if not len(solutions["x"]):
            continue
        made += 1
        add(f"UN{made}", tree, bool(rng.random() < 0.5), solutions, ["x", "v"])

    made = attempts = 0
    while made < want.get("filter", 0) and attempts < 400:
        attempts += 1
        got = left_side()
        if got is None:
            continue
        objs = [tp[2] for tp in got[0] if tp[0] == "x" and tp[2] != "y"]
        chosen = objs[int(rng.integers(len(objs)))]
        pats = [tuple("v" if t == chosen else t for t in tp) for tp in got[0]]
        solutions = ref.solve(pats)
        if not len(solutions["v"]):
            continue
        comparison = (str(rng.choice(["<", ">", "!="])), "v", int(rng.choice(solutions["v"])))
        kept = keep_where(solutions, comparison)
        if not 0 < len(kept["v"]) < len(solutions["v"]):
            continue
        made += 1
        add(f"FI{made}", ("filter", comparison, ("bgp", tuple(pats))), bool(rng.random() < 0.5), kept)
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
