"""The benchmark's own copy of the synthetic federation generator.

A deployment's data is made here from its configuration file, so that a
change to the program cannot change the data it is judged on.  The
generator follows ``fedbench_like_spec``/``generate_federation`` of the
program step for step (same random draws in the same order, same term ids),
but it returns plain numpy arrays: one ``(n, 3)`` int32 triple array per
source, sorted by (s, p, o) and without duplicates, plus the per-term kind
and authority the planner's statistics read, and the ground truth the
traffic generator draws its queries from.

Each source has a population of characteristic-set templates (Zipf entity
counts), predicates drawn from shared and source-local pools, per-(entity,
predicate) multiplicities of 1 to 4 (so DISTINCT and bag answers differ),
and link predicates whose objects are entities of another source: the
federated joins.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

SHARED_PREDS = ["rdf:type", "rdfs:label", "foaf:name", "owl:sameAs", "skos:subject"]
IRI, LITERAL = 0, 1


@dataclass
class Terms:
    """Term ids in order of first use, with each term's kind and authority
    (what the planner's entity summaries partition by)."""

    terms: list = field(default_factory=list)
    kinds: list = field(default_factory=list)
    authorities: list = field(default_factory=list)
    index: dict = field(default_factory=dict)
    auth_index: dict = field(default_factory=dict)
    auth_names: list = field(default_factory=list)

    def add(self, term: str, kind: int = IRI, authority: str | None = None) -> int:
        tid = self.index.get(term)
        if tid is not None:
            return tid
        if authority is None:
            authority = _authority_of(term, kind)
        aid = self.auth_index.get(authority)
        if aid is None:
            aid = self.auth_index[authority] = len(self.auth_names)
            self.auth_names.append(authority)
        tid = self.index[term] = len(self.terms)
        self.terms.append(term)
        self.kinds.append(kind)
        self.authorities.append(aid)
        return tid


def _authority_of(term: str, kind: int) -> str:
    if kind == LITERAL:
        return "literal:plain"
    if "://" in term:
        scheme, rest = term.split("://", 1)
        return scheme + "://" + rest.split("/", 1)[0]
    if ":" in term:
        return term.split(":", 1)[0] + ":"
    return "urn:"


@dataclass
class GroundTruth:
    """Per-source template structure, for drawing queries with answers."""

    first_entity: dict         # source -> id of its first entity (ids are contiguous)
    assign: dict               # source -> template index of each entity
    template_preds: dict       # source -> [sorted predicate ids per template]
    template_entities: dict    # source -> [entity ids per template]
    cross_links: np.ndarray    # (n, 5) int64: src index, dst index, s, pred, o

    def template_of(self, source: str, entities) -> np.ndarray:
        """The template index of each of ``source``'s ``entities``."""
        return self.assign[source][np.asarray(entities) - self.first_entity[source]]


@dataclass
class Data:
    """One generated deployment."""

    names: list[str]
    triples: list[np.ndarray]  # per source, (n, 3) int32, sorted, unique
    terms: Terms
    gt: GroundTruth

    @property
    def n_triples(self) -> int:
        return sum(len(t) for t in self.triples)

    def source_index(self, name: str) -> int:
        return self.names.index(name)


def _unique_sorted(s, p, o) -> np.ndarray:
    s, p, o = (np.asarray(a, np.int32) for a in (s, p, o))
    order = np.lexsort((o, p, s))
    t = np.stack([s[order], p[order], o[order]], axis=1)
    if len(t):
        keep = np.ones(len(t), bool)
        keep[1:] = np.any(t[1:] != t[:-1], axis=1)
        t = t[keep]
    return t


def generate(config: dict) -> Data:
    """The deployment that ``config`` (a configuration file's contents)
    describes: its ``sources`` (each a ``SourceSpec`` of the program's
    generator, with ``n_entities`` already scaled) from ``data_seed``."""
    specs = config["sources"]
    rng = np.random.default_rng(config["data_seed"])
    d = Terms()
    shared = [d.add(p, IRI) for p in SHARED_PREDS]
    rdf_type = shared[0]
    names = [ss["name"] for ss in specs]

    entity_ids: dict[str, np.ndarray] = {}
    for ss in specs:
        auth = ss.get("authority") or f"http://{ss['name'].lower()}.org"
        low = ss["name"].lower()
        entity_ids[ss["name"]] = np.array(
            [d.add(f"{auth}/{low}/e{i}", IRI, authority=auth)
             for i in range(ss["n_entities"])], dtype=np.int32)

    gt = GroundTruth({}, {}, {}, {}, np.zeros((0, 5), np.int64))
    links_out: list[np.ndarray] = []
    triples: list[np.ndarray] = []
    for si, ss in enumerate(specs):
        name, low = ss["name"], ss["name"].lower()
        n_templates = ss.get("n_templates", 12)
        lo_size, hi_size = ss.get("template_size", (3, 7))
        mult_p = ss.get("multiplicity_p", 0.35)
        links = ss.get("links", [])
        local = [d.add(f"{low}:p{i}", IRI) for i in range(ss.get("n_local_preds", 20))]
        link_ids = {lk["pred"]: d.add(lk["pred"], IRI) for lk in links}
        classes = [d.add(f"{low}:Class{i}", IRI) for i in range(ss.get("n_classes", 6))]

        templates: list[list[int]] = []
        template_link: list[list[tuple[int, str]]] = []
        for _ in range(n_templates):
            size = int(rng.integers(lo_size, hi_size + 1))
            pool = local + shared[:3]
            preds = list(rng.choice(pool, size=min(size, len(pool)), replace=False))
            if rdf_type not in preds:
                preds.append(rdf_type)
            here: list[tuple[int, str]] = []
            for lk in links:
                if rng.random() < lk.get("density", 0.3):
                    pid = link_ids[lk["pred"]]
                    if pid not in preds:
                        preds.append(pid)
                    here.append((pid, lk["target"]))
            templates.append(sorted(set(int(p) for p in preds)))
            template_link.append(here)

        w = 1.0 / np.arange(1, n_templates + 1) ** ss.get("zipf_a", 1.4)
        w /= w.sum()
        ents = entity_ids[name]
        assign = rng.choice(n_templates, size=len(ents), p=w)
        tmpl_entities = [ents[assign == t] for t in range(n_templates)]

        lit_pool: dict[int, np.ndarray] = {}

        def literals_for(pred: int) -> np.ndarray:
            if pred not in lit_pool:
                lit_pool[pred] = np.array(
                    [d.add(f"lit:{name}:{pred}:{i}", LITERAL)
                     for i in range(ss.get("literal_pool", 64))], dtype=np.int32)
            return lit_pool[pred]

        S, P, O = [], [], []
        for t, preds in enumerate(templates):
            es = tmpl_entities[t]
            if len(es) == 0:
                continue
            link_map = dict(template_link[t])
            for pred in preds:
                mult = np.clip(rng.geometric(1.0 - mult_p, size=len(es)), 1, 4)
                subs = np.repeat(es, mult)
                k = len(subs)
                if pred == rdf_type:
                    objs = rng.choice(classes, size=k)
                elif pred in link_map:
                    target = link_map[pred]
                    objs = rng.choice(entity_ids[target], size=k)
                    if target != name:
                        links_out.append(np.stack([
                            np.full(k, si), np.full(k, names.index(target)),
                            subs, np.full(k, pred), objs], axis=1).astype(np.int64))
                else:
                    objs = rng.choice(literals_for(pred), size=k)
                S.append(subs)
                P.append(np.full(k, pred, dtype=np.int32))
                O.append(np.asarray(objs, dtype=np.int32))

        triples.append(_unique_sorted(np.concatenate(S), np.concatenate(P),
                                      np.concatenate(O)))
        gt.first_entity[name] = int(ents[0]) if len(ents) else 0
        gt.assign[name] = assign
        gt.template_preds[name] = templates
        gt.template_entities[name] = tmpl_entities

    if links_out:
        gt.cross_links = np.concatenate(links_out)
    return Data(names=names, triples=triples, terms=d, gt=gt)


def to_program(data: Data):
    """The system under test's view of the deployment: a
    ``repro.rdf.dataset.Federation`` over the same triples and term ids."""
    from repro.rdf.dataset import Federation, Source, TripleTable
    from repro.rdf.dictionary import TermDict

    t = data.terms
    dictionary = TermDict(terms=t.terms, kinds=t.kinds, authorities=t.authorities,
                          _index=t.index, _auth_index=t.auth_index,
                          _auth_names=t.auth_names)
    sources = [Source(name=n, table=TripleTable.from_triples(a[:, 0], a[:, 1], a[:, 2]))
               for n, a in zip(data.names, data.triples)]
    return Federation(sources=sources, dictionary=dictionary)
