"""Univ-Bench-shaped facts (LUBM's UBA profile) for the RDFS-Plus subset.

Counts per department come from a fixed stream, so every seed makes the
same number of facts of each kind; the seed draws the links: which
courses a student takes, who advises whom, where degrees come from.
``enrolments`` makes the writes of the serving and streaming mixes.
"""

from __future__ import annotations

import numpy as np

from bench.dataset import Dataset, Vocab, cat, triples

SHAPE_STREAM = 0x5EED  # the counts; the same for every seed


def _between(rng, lohi, size=None):
    lo, hi = lohi
    return rng.integers(lo, hi + 1, size=size)


def _pick_distinct(rng, n_rows: int, pool: int, counts: np.ndarray):
    """Per row, ``counts[row]`` distinct indices into ``range(pool)``."""
    order = np.argsort(rng.random((n_rows, pool)), axis=1)
    rows = np.repeat(np.arange(n_rows), counts)
    cols = order[rows, np.concatenate([np.arange(c) for c in counts])
                 if n_rows else np.zeros(0, np.int64)]
    return rows, cols


def generate(config: dict, seed: int) -> Dataset:
    sc = config["scale"]
    shape = np.random.default_rng(SHAPE_STREAM)
    rng = np.random.default_rng([abs(int(seed)), 1])
    vocab = Vocab()
    parts: dict = {"Schema": [], "Data": []}
    parts["Schema"].append(triples(vocab, config["schema"]))
    pool = [f"u{k}" for k in range(sc["degree_university_pool"])]
    depts = []
    data: list = []
    for u in range(sc["universities"]):
        uni = f"u{u}"
        data.append((uni, "type", "University"))
        for d in range(_between(shape, sc["departments_per_university"])):
            dept = f"{uni}.d{d}"
            data += [(dept, "type", "Department"),
                     (dept, "subOrganizationOf", uni)]
            for g in range(_between(shape, sc["research_groups"])):
                grp = f"{dept}.rg{g}"
                data += [(grp, "type", "ResearchGroup"),
                         (grp, "subOrganizationOf", dept)]
            ranks = (("FullProfessor", "fp", sc["full_professors"]),
                     ("AssociateProfessor", "ap", sc["associate_professors"]),
                     ("AssistantProfessor", "sp", sc["assistant_professors"]),
                     ("Lecturer", "lec", sc["lecturers"]))
            faculty, profs = [], []
            for cls, tag, lohi in ranks:
                for i in range(_between(shape, lohi)):
                    who = f"{dept}.{tag}{i}"
                    faculty.append(who)
                    data.append((who, "type", cls))
                    if cls != "Lecturer":
                        profs.append(who)
            n_fac = len(faculty)
            deg = rng.integers(0, len(pool), (n_fac, 3))
            for i, who in enumerate(faculty):
                data += [(who, "headOf" if i == 0 else "worksFor", dept),
                         (who, "undergraduateDegreeFrom", pool[deg[i, 0]]),
                         (who, "mastersDegreeFrom", pool[deg[i, 1]]),
                         (who, "doctoralDegreeFrom", pool[deg[i, 2]])]
            courses, gcourses = [], []
            n_c = _between(shape, sc["courses_per_faculty"], n_fac)
            n_gc = _between(shape, sc["graduate_courses_per_faculty"], n_fac)
            teach = rng.permutation(n_fac)  # who teaches which block
            for i in range(n_fac):
                who = faculty[teach[i]]
                for _ in range(n_c[i]):
                    c = f"{dept}.c{len(courses)}"
                    courses.append(c)
                    data += [(who, "teacherOf", c), (c, "type", "Course")]
                for _ in range(n_gc[i]):
                    c = f"{dept}.gc{len(gcourses)}"
                    gcourses.append(c)
                    data += [(who, "teacherOf", c),
                             (c, "type", "GraduateCourse")]
            n_ug = n_fac * _between(shape, sc["undergraduates_per_faculty"])
            n_gs = n_fac * _between(shape, sc["graduates_per_faculty"])
            ug_nc = _between(shape, sc["courses_per_undergraduate"], n_ug)
            gs_nc = _between(shape, sc["courses_per_graduate"], n_gs)
            ug_adv = shape.permutation(n_ug)[
                :n_ug // sc["undergraduate_advisor_one_in"]]
            ugs = [f"{dept}.ug{i}" for i in range(n_ug)]
            gss = [f"{dept}.gs{i}" for i in range(n_gs)]
            data += [(s, "type", "UndergraduateStudent") for s in ugs]
            data += [(s, "memberOf", dept) for s in ugs]
            data += [(s, "type", "GraduateStudent") for s in gss]
            data += [(s, "memberOf", dept) for s in gss]
            r, c = _pick_distinct(rng, n_ug, len(courses), ug_nc)
            data += [(ugs[i], "takesCourse", courses[j])
                     for i, j in zip(r.tolist(), c.tolist())]
            r, c = _pick_distinct(rng, n_gs, len(gcourses), gs_nc)
            data += [(gss[i], "takesCourse", gcourses[j])
                     for i, j in zip(r.tolist(), c.tolist())]
            adv = rng.integers(0, len(profs), len(ug_adv))
            data += [(ugs[i], "advisor", profs[a])
                     for i, a in zip(ug_adv.tolist(), adv.tolist())]
            adv = rng.integers(0, len(profs), n_gs)
            deg = rng.integers(0, len(pool), n_gs)
            for i, s in enumerate(gss):
                data += [(s, "advisor", profs[adv[i]]),
                         (s, "undergraduateDegreeFrom", pool[deg[i]])]
            depts.append({"name": dept, "courses": courses,
                          "gcourses": gcourses, "profs": profs,
                          "people": faculty + ugs + gss})
    parts["Data"].append(triples(vocab, data))
    persons = [p for d in depts for p in d["people"]]
    return Dataset(vocab, cat(parts), {
        "depts": depts, "persons": persons, "pool": pool,
        "enrolled": 0})


def enrolments(ds: Dataset, dept_idx, rng) -> list:
    """One enrolment per entry of ``dept_idx``: a new student of that
    department with the profile's facts.  Entry ``k`` of the run's
    enrolments is a graduate when ``k % 4 == 3``; its course count and
    advisor follow ``k`` too, so every seed writes the same number of
    facts.  Returns one ``{"Data": (n, 3) ids}`` per enrolment."""
    out = []
    for d in np.asarray(dept_idx).tolist():
        k = ds.extra["enrolled"]
        ds.extra["enrolled"] = k + 1
        dept = ds.extra["depts"][d]
        grad = k % 4 == 3
        who = f"{dept['name']}.new{k}"
        rows = [(who, "type", "GraduateStudent" if grad
                 else "UndergraduateStudent"),
                (who, "memberOf", dept["name"])]
        pool = dept["gcourses"] if grad else dept["courses"]
        n_c = (1 if grad else 2) + k % 3
        for j in rng.permutation(len(pool))[:n_c].tolist():
            rows.append((who, "takesCourse", pool[j]))
        if grad or k % 5 == 0:
            rows.append((who, "advisor",
                         dept["profs"][rng.integers(len(dept["profs"]))]))
        if grad:
            uni = ds.extra["pool"][rng.integers(len(ds.extra["pool"]))]
            rows.append((who, "undergraduateDegreeFrom", uni))
        out.append({"Data": triples(ds.vocab, rows)})
    return out
