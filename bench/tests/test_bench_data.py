"""The generator against UBA's published ranges, the rewritten templates
against the answers with inference, and the benchmark's reference against
the repository's ``evaluate_bgp``."""
from collections import Counter

import numpy as np
import pytest

from bench import lubm, reference
from reference import evaluate_bgp
from repro.core.query import Const, Query, TriplePattern, Var

from tiny import TINY, UBA


def _query(instance, name="q") -> Query:
    term = {"var": Var, "const": Const}
    return Query([TriplePattern(*(term[k](v) for k, v in p))
                  for p in instance], name=name)


class _Graph:
    """Lookups over a generated graph for the range checks."""

    def __init__(self, g):
        self.g, self.t = g, g.triples.astype(np.int64)

    def pairs(self, p):
        m = self.t[:, 1] == self.g.names[p]
        return self.t[m, 0], self.t[m, 2]

    def of(self, cls):
        first, n = self.g.ranges[cls]
        return np.arange(first, first + n)

    def one(self, p, subjects):
        """Each subject's one object of ``p`` (asserted to be one)."""
        s, o = self.pairs(p)
        m = np.isin(s, subjects)
        assert Counter(s[m].tolist()) == Counter(subjects.tolist()), p
        return dict(zip(s[m].tolist(), o[m].tolist()))


def _within(counts, lo_hi, what):
    lo, hi = lo_hi
    counts = np.asarray(list(counts))
    assert counts.min() >= lo and counts.max() <= hi, (what, counts)


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_generator_follows_uba_ranges(seed):
    g = lubm.generate(3, UBA, seed)
    G = _Graph(g)
    t = G.t
    assert len(np.unique(t, axis=0)) == len(t)
    dept_univ = G.one("ub:subOrganizationOf", G.of("dept"))
    _within(Counter(dept_univ.values()).values(), UBA["departments"],
            "departments")
    _within(Counter(G.one("ub:subOrganizationOf", G.of("rgroup")).values())
            .values(), UBA["research_groups"], "research groups")
    works = {}
    per_dept = {}
    for r in lubm.FACULTY:
        w = G.one("ub:worksFor", G.of(r))
        works.update(w)
        per_dept[r] = Counter(w.values())
        assert set(per_dept[r]) == set(dept_univ)
        _within(per_dept[r].values(), UBA[r], r)
    faculty = Counter(works.values())
    member = {}
    for cls, key in (("undergrad", "undergraduates_per_faculty"),
                     ("grad", "graduates_per_faculty")):
        m = G.one("ub:memberOf", G.of(cls))
        member.update(m)
        n = Counter(m.values())
        assert all(n[d] % faculty[d] == 0 for d in faculty), cls
        _within([n[d] // faculty[d] for d in faculty], UBA[key], cls)
    # each course is taught by one member of the faculty, who teaches
    # 1-2 courses and 1-2 graduate courses
    s, o = G.pairs("ub:teacherOf")
    teacher = dict(zip(o.tolist(), s.tolist()))
    assert len(teacher) == len(o)
    for cls, key in (("course", "courses_per_faculty"),
                     ("gradcourse", "graduate_courses_per_faculty")):
        assert set(G.of(cls).tolist()) <= set(teacher)
        _within(Counter(teacher[c] for c in G.of(cls).tolist()).values(),
                UBA[key], cls)
    # students take distinct courses of their department
    s, o = G.pairs("ub:takesCourse")
    taken = Counter(s.tolist())
    assert all(member[x] == works[teacher[c]]
               for x, c in zip(s.tolist(), o.tolist()))
    _within([taken[x] for x in G.of("undergrad").tolist()],
            UBA["courses_per_undergraduate"], "undergraduate courses")
    _within([taken[x] for x in G.of("grad").tolist()],
            UBA["courses_per_graduate"], "graduate courses")
    # advisors: every graduate student, one undergraduate in five; a
    # professor of the student's department
    professors = set(np.concatenate([G.of(r) for r in lubm.PROFESSORS])
                     .tolist())
    s, o = G.pairs("ub:advisor")
    adv = dict(zip(s.tolist(), o.tolist()))
    assert len(adv) == len(s) and set(G.of("grad").tolist()) <= set(adv)
    assert all(a in professors and works[a] == member[x]
               for x, a in adv.items())
    ug_adv = Counter(member[x] for x in adv if x < G.of("grad")[0])
    ug_n = Counter(member[x] for x in G.of("undergrad").tolist())
    assert all(ug_adv[d] == ug_n[d] // UBA["undergraduates_per_advisor"]
               for d in ug_n)
    # one head per department, a full professor who works for it
    head = G.one("ub:headOf", G.pairs("ub:headOf")[0])
    assert sorted(head.values()) == G.of("dept").tolist()
    assert all(works[h] == d and h in set(G.of("fullprof").tolist())
               for h, d in head.items())
    # publications: per rank in its range; graduate co-authors publish
    # with their advisor
    s, o = G.pairs("ub:publicationAuthor")
    fac_pubs = Counter(o[np.isin(o, list(works))].tolist())
    for r in lubm.FACULTY:
        _within([fac_pubs[f] for f in G.of(r).tolist()],
                UBA["publications"][r], r + " publications")
    first_author = {p: a for p, a in zip(s.tolist(), o.tolist())
                    if a in works}
    grad_pubs = [(p, a) for p, a in zip(s.tolist(), o.tolist())
                 if a not in works]
    assert all(first_author[p] == adv[a] for p, a in grad_pubs)
    _within(Counter(a for _, a in grad_pubs).values(),
            UBA["publications"]["graduate"], "graduate publications")
    # every person one e-mail address of their own and the one telephone
    people = np.concatenate([G.of(r) for r in lubm.FACULTY]
                            + [G.of("undergrad"), G.of("grad")])
    mail = G.one("ub:emailAddress", people)
    assert len(set(mail.values())) == len(people)
    assert set(G.one("ub:telephone", people).values()) == {
        g.ranges["telephone"][0]}


def test_triples_per_university_near_lubm():
    """LUBM-10240 is about 1.36 billion triples, about 133,000 a
    university: ten universities here land within a tenth of that."""
    n = len(lubm.generate(10, UBA, 0).triples) / 10
    assert abs(n / 133_000 - 1) < 0.1, n


def test_generator_is_a_function_of_the_seed():
    a = lubm.generate(2, TINY, 2**33 + 1).triples
    b = lubm.generate(2, TINY, 2**33 + 1).triples
    c = lubm.generate(2, TINY, 2**33 + 2).triples
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def _answers(index, graph, patterns, rng, n):
    vars_ = lubm.template_vars(patterns)
    out = []
    for _ in range(n):
        inst = lubm.instantiate(patterns, graph, rng)
        out.append((inst, reference.evaluate(index, inst, vars_)))
    return out


def test_rewritten_templates_give_the_answers_with_inference():
    """q7 and q12 drop or replace the type patterns that need inference;
    with the inference spelled out (each subclass, and a Chair as the
    head of a department) the answers are the same."""
    g = lubm.generate(2, UBA, 5)
    index = reference.Index(g.triples)
    tpl = lubm.load_templates("lubm")
    rng = np.random.default_rng(3)
    for inst, got in _answers(index, g, tpl["q7"], rng, 20):
        prof = inst[1][0]
        want = []
        for student in ("ub:UndergraduateStudent", "ub:GraduateStudent"):
            for course in ("ub:Course", "ub:GraduateCourse"):
                pats = [(("var", "x"), ("const", g.names["rdf:type"]),
                         ("const", g.names[student])),
                        (("var", "y"), ("const", g.names["rdf:type"]),
                         ("const", g.names[course])),
                        (("var", "x"), ("const", g.names["ub:takesCourse"]),
                         ("var", "y")),
                        (prof, ("const", g.names["ub:teacherOf"]),
                         ("var", "y"))]
                want.append(reference.evaluate(index, pats, ["x", "y"]))
        want = reference.sorted_rows(np.concatenate(want), distinct=True)
        assert len(got) > 0 and np.array_equal(got, want)
    for inst, got in _answers(index, g, tpl["q12"], rng, 10):
        univ = inst[3][2]
        c = {k: ("const", g.names[k]) for k in (
            "rdf:type", "ub:headOf", "ub:Department", "ub:worksFor",
            "ub:subOrganizationOf")}
        pats = [(("var", "x"), c["ub:headOf"], ("var", "z")),
                (("var", "z"), c["rdf:type"], c["ub:Department"]),
                (("var", "y"), c["rdf:type"], c["ub:Department"]),
                (("var", "x"), c["ub:worksFor"], ("var", "y")),
                (("var", "y"), c["ub:subOrganizationOf"], univ)]
        want = reference.evaluate(index, pats, ["x", "y"])
        assert len(got) > 0 and np.array_equal(got, want)


@pytest.mark.parametrize("name", ["q1", "q2", "q3", "q7", "q12", "q14"])
def test_reference_equals_evaluate_bgp(name):
    graph = lubm.generate(2, TINY, 7)
    index = reference.Index(graph.triples)
    patterns = lubm.load_templates("lubm")[name]
    vars_ = lubm.template_vars(patterns)
    rng = np.random.default_rng(1)
    for _ in range(4):
        inst = lubm.instantiate(patterns, graph, rng)
        want = evaluate_bgp(graph.triples, _query(inst))
        # evaluate_bgp's columns follow the program's Query.vars
        order = [v.name for v in _query(inst).vars]
        got = reference.evaluate(index, inst, order)
        assert np.array_equal(got, want)
        assert len(reference.evaluate(index, inst, vars_)) == len(want)


def test_request_stream_and_arrivals_keep_the_work_fixed():
    mix = {"q1": 0.4, "q7": 0.3, "q12": 0.3}
    a = lubm.request_stream(mix, 10, 40, lubm.rng(5, 3))
    b = lubm.request_stream(mix, 10, 40, lubm.rng(6, 3))
    assert a != b and Counter(a) == Counter(b) == Counter(
        {"q1": 16, "q7": 12, "q12": 12})
    x = lubm.arrival_offsets(20.0, 10.0)
    assert np.array_equal(x, lubm.arrival_offsets(20.0, 10.0))
    assert len(x) == 200 and x[0] == 0.0 and x[-1] < 10.0
    gaps = np.diff(np.append(x, 10.0))
    # exponential quantiles: mean 1/rate, median about ln 2 of it
    assert np.isclose(gaps.mean(), 0.05)
    assert np.isclose(np.median(gaps), 0.05 * np.log(2), rtol=0.02)
    assert not np.all(np.diff(gaps) >= 0)  # shuffled, not sorted
