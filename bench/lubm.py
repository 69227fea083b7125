"""LUBM data after the UBA generator, query templates and request streams.

``generate`` writes a university data set the way LUBM's data generator
(UBA; Guo, Pan, Heflin, J. Web Sem. 2005) describes it: per university a
number of departments, per department faculty of four ranks, students in a
ratio to the faculty, courses per teacher, research groups and
publications, each count drawn from its published range, which the
configuration file holds under ``cardinalities``.  It draws from numpy's
generator, not UBA's Java one, so the triples are not UBA's files: the
same classes, properties and ranges, other draws.  It emits integer ids
directly (no strings, no dictionary), one contiguous range per class

    predicates | classes | literals | universities | departments |
    research groups | full, associate, assistant professors | lecturers |
    courses | graduate courses | undergraduates | graduates | publications

so a template's placeholder (``$gradcourse``) is drawn from one range.
Literals (names, e-mail addresses, the one telephone value, research
interests) are ids too: a name such as ``FullProfessor3`` is one literal
shared by every department's FullProfessor3, as in UBA's files.

Templates live in ``queries/<name>.json`` as plain triple patterns: a term
is a variable (``?x``), a named constant (``rdf:type``) or a placeholder
(``$gradcourse``) drawn uniformly per instance.  A traffic file's mix
names templates and weights; ``request_stream`` turns it into a sequence
whose every block of ``block`` requests holds each template its share of
times, in an order drawn from the seed.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

PREDICATES = (
    "rdf:type", "ub:name", "ub:takesCourse", "ub:teacherOf",
    "ub:undergraduateDegreeFrom", "ub:mastersDegreeFrom",
    "ub:doctoralDegreeFrom", "ub:advisor", "ub:memberOf",
    "ub:publicationAuthor", "ub:headOf", "ub:teachingAssistantOf",
    "ub:emailAddress", "ub:telephone", "ub:subOrganizationOf",
    "ub:worksFor", "ub:researchInterest",
)
CLASSES = (
    "ub:University", "ub:Department", "ub:ResearchGroup",
    "ub:FullProfessor", "ub:AssociateProfessor", "ub:AssistantProfessor",
    "ub:Lecturer", "ub:Course", "ub:GraduateCourse",
    "ub:UndergraduateStudent", "ub:GraduateStudent",
    "ub:TeachingAssistant", "ub:ResearchAssistant", "ub:Publication",
)
# entity ranges, in id order; the faculty ranks in the order of FACULTY
FACULTY = ("fullprof", "assocprof", "asstprof", "lecturer")
FACULTY_CLASS = dict(zip(FACULTY, CLASSES[3:7]))
PROFESSORS = FACULTY[:3]


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of a run's randomness."""
    return np.random.default_rng([seed % 2**64, stream])


@dataclass(frozen=True)
class Graph:
    triples: np.ndarray            # (n, 3) int32 (s, p, o)
    names: dict                    # constant name -> id
    ranges: dict                   # entity class -> (first id, count)


class _Ids:
    """Hands out contiguous id ranges."""

    def __init__(self, first: int):
        self.next = first
        self.ranges: dict[str, tuple[int, int]] = {}

    def take(self, name: str, count: int) -> np.ndarray:
        self.ranges[name] = (self.next, int(count))
        self.next += int(count)
        return np.arange(self.ranges[name][0], self.next, dtype=np.int64)


def _between(gen, lo_hi, size) -> np.ndarray:
    lo, hi = lo_hi
    return gen.integers(lo, hi + 1, size=size)


def _starts(counts: np.ndarray) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int64)


def _local(owner: np.ndarray, n_owners: int) -> np.ndarray:
    """Each item's index among the items of its owner (``owner`` sorted)."""
    counts = np.bincount(owner, minlength=n_owners)
    return np.arange(len(owner)) - np.repeat(_starts(counts), counts)


def _distinct_picks(gen, k: np.ndarray, n: np.ndarray) -> np.ndarray:
    """For row i, ``min(k[i], n[i])`` distinct values of ``[0, n[i])``, as
    an (m, kmax) array with -1 where a row has fewer."""
    k = np.minimum(k, n)
    kmax = int(k.max()) if len(k) else 0
    pick = np.full((len(k), kmax), -1, np.int64)
    for j in range(kmax):
        rows = np.flatnonzero(k > j)
        while len(rows):
            cand = gen.integers(0, n[rows])
            clash = np.any(pick[rows, :j] == cand[:, None], axis=1)
            pick[rows[~clash], j] = cand[~clash]
            rows = rows[clash]
    return pick


def _shuffled_rank(gen, owner: np.ndarray, n_owners: int) -> np.ndarray:
    """A random order of each owner's items: each item's rank in it."""
    order = np.lexsort((gen.random(len(owner)), owner))
    rank = np.empty(len(owner), np.int64)
    rank[order] = _local(owner[order], n_owners)
    return rank


def generate(n_universities: int, cardinalities: dict, seed: int) -> Graph:
    """LUBM(``n_universities``, ``seed``) after UBA's published ranges."""
    card = cardinalities
    gen = rng(seed, 0)
    n_deg = int(card["degree_universities"])
    if n_universities > n_deg:
        raise ValueError("more universities than degrees are drawn from")
    names = {t: i for i, t in enumerate(PREDICATES + CLASSES)}
    pid = {p: names[p] for p in PREDICATES}
    cid = {c: names[c] for c in CLASSES}

    # ---- how many of each, per department
    U = n_universities
    dept_univ = np.repeat(np.arange(U), _between(
        gen, card["departments"], U))
    D = len(dept_univ)
    fac_n = {r: _between(gen, card[r], D) for r in FACULTY}
    faculty = sum(fac_n.values())
    ug_n = faculty * _between(gen, card["undergraduates_per_faculty"], D)
    gr_n = faculty * _between(gen, card["graduates_per_faculty"], D)
    rg_n = _between(gen, card["research_groups"], D)
    ta_n = gr_n // _between(gen, card["graduates_per_teaching_assistant"], D)
    ra_n = gr_n // _between(gen, card["graduates_per_research_assistant"], D)

    def owners(counts):
        return np.repeat(np.arange(len(counts)), counts)

    fac_dept = {r: owners(fac_n[r]) for r in FACULTY}
    fac_all_dept = np.concatenate([fac_dept[r] for r in FACULTY])
    n_fac = len(fac_all_dept)
    c_per = _between(gen, card["courses_per_faculty"], n_fac)
    g_per = _between(gen, card["graduate_courses_per_faculty"], n_fac)
    pubs_per = np.concatenate([_between(gen, card["publications"][r],
                                        len(fac_dept[r])) for r in FACULTY])

    # ---- literal pools, then the entities' ids
    ids = _Ids(len(names))
    longest = {
        "University": n_deg, "Department": int(np.bincount(dept_univ).max()),
        **{FACULTY_CLASS[r][3:]: int(fac_n[r].max()) for r in FACULTY},
        "Course": int(np.bincount(np.repeat(fac_all_dept, c_per),
                                  minlength=D).max()),
        "GraduateCourse": int(np.bincount(np.repeat(fac_all_dept, g_per),
                                          minlength=D).max()),
        "UndergraduateStudent": int(ug_n.max()),
        "GraduateStudent": int(gr_n.max()),
        "Publication": int(pubs_per.max()),
    }
    name_of = {k: ids.take("name:" + k, n)[0] for k, n in longest.items()}
    telephone = ids.take("telephone", 1)[0]
    interests = ids.take("research_interest", card["research_interests"])
    univ = ids.take("univ", U)
    univ_all = np.concatenate([univ, ids.take("univ_other", n_deg - U)])
    dept = ids.take("dept", D)
    rgroup = ids.take("rgroup", int(rg_n.sum()))
    fac = {r: ids.take(r, len(fac_dept[r])) for r in FACULTY}
    fac_all = np.concatenate([fac[r] for r in FACULTY])
    course_teacher = np.repeat(np.arange(n_fac), c_per)
    course_dept = fac_all_dept[course_teacher]
    order = np.argsort(course_dept, kind="stable")
    course_teacher, course_dept = course_teacher[order], course_dept[order]
    course = ids.take("course", len(course_teacher))
    gcourse_teacher = np.repeat(np.arange(n_fac), g_per)
    gcourse_dept = fac_all_dept[gcourse_teacher]
    order = np.argsort(gcourse_dept, kind="stable")
    gcourse_teacher, gcourse_dept = gcourse_teacher[order], gcourse_dept[order]
    gcourse = ids.take("gradcourse", len(gcourse_teacher))
    ug_dept, gr_dept = owners(ug_n), owners(gr_n)
    ug = ids.take("undergrad", len(ug_dept))
    gr = ids.take("grad", len(gr_dept))
    pub_author = np.repeat(np.arange(n_fac), pubs_per)
    pub = ids.take("publication", len(pub_author))
    people = np.concatenate([fac_all, ug, gr])
    email = ids.take("email", len(people))
    if ids.next >= 2**31:
        raise ValueError("ids do not fit int32")

    parts = []

    def rows(s, p, o):
        s = np.asarray(s, np.int64)
        parts.append(np.stack([s, np.broadcast_to(np.int64(p), s.shape),
                               np.broadcast_to(np.asarray(o, np.int64),
                                               s.shape)], axis=1))

    def named(s, cls_name, local):
        rows(s, pid["ub:name"], name_of[cls_name] + local)

    # universities: the generated ones, and every other one a degree names
    rows(univ, pid["rdf:type"], cid["ub:University"])
    named(univ, "University", np.arange(U))
    # departments and research groups
    rows(dept, pid["rdf:type"], cid["ub:Department"])
    named(dept, "Department", _local(dept_univ, U))
    rows(dept, pid["ub:subOrganizationOf"], univ[dept_univ])
    rows(rgroup, pid["rdf:type"], cid["ub:ResearchGroup"])
    rows(rgroup, pid["ub:subOrganizationOf"], dept[owners(rg_n)])
    # faculty
    degrees = []
    for r in FACULTY:
        rows(fac[r], pid["rdf:type"], cid[FACULTY_CLASS[r]])
        named(fac[r], FACULTY_CLASS[r][3:], _local(fac_dept[r], D))
        rows(fac[r], pid["ub:worksFor"], dept[fac_dept[r]])
        if r in PROFESSORS:
            rows(fac[r], pid["ub:researchInterest"], interests[
                gen.integers(len(interests), size=len(fac[r]))])
    for p in ("ub:undergraduateDegreeFrom", "ub:mastersDegreeFrom",
              "ub:doctoralDegreeFrom"):
        d = gen.integers(n_deg, size=n_fac)
        degrees.append(d)
        rows(fac_all, pid[p], univ_all[d])
    head = np.flatnonzero(_shuffled_rank(gen, fac_dept["fullprof"], D) == 0)
    rows(fac["fullprof"][head], pid["ub:headOf"],
         dept[fac_dept["fullprof"][head]])
    # courses, each taught by one member of its department's faculty
    rows(course, pid["rdf:type"], cid["ub:Course"])
    named(course, "Course", _local(course_dept, D))
    rows(fac_all[course_teacher], pid["ub:teacherOf"], course)
    rows(gcourse, pid["rdf:type"], cid["ub:GraduateCourse"])
    named(gcourse, "GraduateCourse", _local(gcourse_dept, D))
    rows(fac_all[gcourse_teacher], pid["ub:teacherOf"], gcourse)
    # students
    c_n = np.bincount(course_dept, minlength=D)
    g_n = np.bincount(gcourse_dept, minlength=D)

    def takes(stud, sdept, per, first_of_dept, n_of_dept, pool):
        pick = _distinct_picks(gen, _between(gen, per, len(stud)),
                               n_of_dept[sdept])
        who, j = np.nonzero(pick >= 0)
        rows(stud[who], pid["ub:takesCourse"],
             pool[first_of_dept[sdept[who]] + pick[who, j]])

    prof_dept = np.concatenate([fac_dept[r] for r in PROFESSORS])
    prof_ids = np.concatenate([fac[r] for r in PROFESSORS])
    order = np.argsort(prof_dept, kind="stable")
    prof_ids, prof_dept = prof_ids[order], prof_dept[order]
    p_n = np.bincount(prof_dept, minlength=D)

    def advisor(sdept):
        return prof_ids[_starts(p_n)[sdept] + gen.integers(0, p_n[sdept])]

    for stud, sdept, cls_name in ((ug, ug_dept, "UndergraduateStudent"),
                                  (gr, gr_dept, "GraduateStudent")):
        rows(stud, pid["rdf:type"], cid["ub:" + cls_name])
        named(stud, cls_name, _local(sdept, D))
        rows(stud, pid["ub:memberOf"], dept[sdept])
    takes(ug, ug_dept, card["courses_per_undergraduate"], _starts(c_n), c_n,
          course)
    takes(gr, gr_dept, card["courses_per_graduate"], _starts(g_n), g_n,
          gcourse)
    ug_adv = np.flatnonzero(_shuffled_rank(gen, ug_dept, D) < ug_n[ug_dept]
                            // card["undergraduates_per_advisor"])
    rows(ug[ug_adv], pid["ub:advisor"], advisor(ug_dept[ug_adv]))
    gr_advisor = advisor(gr_dept)
    rows(gr, pid["ub:advisor"], gr_advisor)
    gr_deg = gen.integers(n_deg, size=len(gr))
    degrees.append(gr_deg)
    rows(gr, pid["ub:undergraduateDegreeFrom"], univ_all[gr_deg])
    # teaching assistants (each of one course of the department) and
    # research assistants, among the graduate students
    ta_rank = _shuffled_rank(gen, gr_dept, D)
    ta = np.flatnonzero(ta_rank < np.minimum(ta_n, c_n)[gr_dept])
    course_rank = _shuffled_rank(gen, course_dept, D)
    course_by_rank = np.empty(len(course), np.int64)
    course_by_rank[_starts(c_n)[course_dept] + course_rank] = course
    rows(gr[ta], pid["rdf:type"], cid["ub:TeachingAssistant"])
    rows(gr[ta], pid["ub:teachingAssistantOf"],
         course_by_rank[_starts(c_n)[gr_dept[ta]] + ta_rank[ta]])
    ra = np.flatnonzero(_shuffled_rank(gen, gr_dept, D) < ra_n[gr_dept])
    rows(gr[ra], pid["rdf:type"], cid["ub:ResearchAssistant"])
    # publications: by a faculty member, some with graduate co-authors
    rows(pub, pid["rdf:type"], cid["ub:Publication"])
    named(pub, "Publication", _local(pub_author, n_fac))
    rows(pub, pid["ub:publicationAuthor"], fac_all[pub_author])
    adv_index = np.searchsorted(fac_all, gr_advisor)  # fac_all is sorted
    pick = _distinct_picks(
        gen, _between(gen, card["publications"]["graduate"], len(gr)),
        pubs_per[adv_index])
    who, j = np.nonzero(pick >= 0)
    rows(pub[_starts(pubs_per)[adv_index[who]] + pick[who, j]],
         pid["ub:publicationAuthor"], gr[who])
    # every person's e-mail address and telephone
    rows(people, pid["ub:emailAddress"], email)
    rows(people, pid["ub:telephone"], telephone)
    # a degree's university is declared where it is named (UBA nests it)
    other = np.unique(np.concatenate(degrees))
    other = other[other >= U]
    rows(univ_all[other], pid["rdf:type"], cid["ub:University"])
    return Graph(np.concatenate(parts).astype(np.int32), names, ids.ranges)


# ------------------------------------------------------------------ queries
def load_templates(name: str) -> dict:
    """``queries/<name>.json``'s ``templates``: name -> [s, p, o] terms."""
    return json.loads(
        (HERE / "queries" / f"{name}.json").read_text())["templates"]


def template_vars(patterns) -> list[str]:
    """Variables in order of first appearance (the answer's columns)."""
    out = []
    for pat in patterns:
        for t in pat:
            if t.startswith("?") and t[1:] not in out:
                out.append(t[1:])
    return out


def instantiate(patterns, graph: Graph, rng) -> list[tuple]:
    """One instance: placeholders drawn uniformly from their class; returns
    patterns of ``("var", name)`` / ``("const", id)`` terms."""
    drawn: dict[str, int] = {}
    out = []
    for pat in patterns:
        terms = []
        for t in pat:
            if t.startswith("?"):
                terms.append(("var", t[1:]))
            elif t.startswith("$"):
                if t not in drawn:
                    first, count = graph.ranges[t[1:]]
                    drawn[t] = first + int(rng.integers(count))
                terms.append(("const", drawn[t]))
            else:
                terms.append(("const", graph.names[t]))
        out.append(tuple(terms))
    return out


def request_stream(mix: dict, block: int, n: int, rng) -> list[str]:
    """``n`` template names; each block of ``block`` holds every template
    ``round(weight * block)`` times, shuffled."""
    counts = {t: round(w * block) for t, w in mix.items()}
    if sum(counts.values()) != block:
        raise ValueError(f"mix {mix} does not divide a block of {block}")
    one = [t for t, c in counts.items() for _ in range(c)]
    out: list[str] = []
    while len(out) < n:
        out.extend(one[i] for i in rng.permutation(block))
    return out[:n]


def arrival_offsets(rate_qps: float, seconds: float) -> np.ndarray:
    """Open-loop due times in [0, seconds): ``rate * seconds`` exponential
    gaps taken at evenly spaced quantiles, in one fixed shuffled order.
    Every seed offers this same schedule: the order of the gaps sets how
    requests bunch up, and so the tail, which the seed must not move; the
    seed draws what the requests ask."""
    n = max(1, int(round(rate_qps * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps = gaps[rng(0, 4).permutation(n)] * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
