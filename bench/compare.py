"""The comparisons that decide ``correct``, kept apart from the program's own
so that no change to the program can loosen them."""
from __future__ import annotations

import itertools

import numpy as np


def forecast_numbers(pairs) -> dict:
    """Numbers of a forecast check over ``(program, reference)`` replica
    pairs, each a dict of ``node_idx``, ``finish_order``, ``start_t``,
    ``end_t`` and ``makespan``; a program replica of ``None`` is one the
    program never returned.

    ``decision_mismatches``: replicas whose node assignment or finish order
    differs from the reference's anywhere; ``time_rel_err``: the largest
    relative error of a start time, end time or makespan (an exact zero is
    compared absolutely)."""
    mismatches, err = 0, 0.0
    for prog, ref in pairs:
        if prog is None:
            mismatches, err = mismatches + 1, float("inf")
            continue
        same = all(np.array_equal(prog[k], ref[k])
                   for k in ("node_idx", "finish_order"))
        mismatches += not same
        for k in ("start_t", "end_t", "makespan"):
            a = np.asarray(prog[k], np.float64)
            b = np.asarray(ref[k], np.float64)
            if a.shape != b.shape:
                err = float("inf")
                continue
            rel = np.abs(a - b) / np.where(b == 0.0, 1.0, np.abs(b))
            err = max(err, float(np.max(rel)) if rel.size else 0.0)
    return {"decision_mismatches": mismatches, "time_rel_err": err}


def label_mismatch_share(a, b) -> float:
    """Share of points whose group differs between two labelings under the
    best one-to-one map of group ids; labelings of different length share
    nothing."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.size == 0:
        return 1.0
    ia, a = np.unique(a, return_inverse=True)
    ib, b = np.unique(b, return_inverse=True)
    m = np.zeros((ia.size, ib.size), np.int64)
    np.add.at(m, (a, b), 1)
    if m.shape[0] > m.shape[1]:
        m = m.T
    rows = np.arange(m.shape[0])
    best = max(m[rows, list(p)].sum()
               for p in itertools.permutations(range(m.shape[1]), m.shape[0]))
    return 1.0 - best / a.size


def grouping_numbers(pairs) -> dict:
    """Numbers of a grouping check over ``(program, reference)`` results,
    each a dict with ``k`` and ``labels``.

    ``k_mismatches``: calls whose chosen k differs from the reference's;
    ``label_mismatch_share``: the largest share of points grouped
    differently from the reference, up to a renaming of the groups."""
    k_bad, share = 0, 0.0
    for prog, ref in pairs:
        k_bad += prog["k"] != ref["k"]
        share = max(share, label_mismatch_share(prog["labels"], ref["labels"]))
    return {"k_mismatches": k_bad, "label_mismatch_share": share}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Whether every number is at or under its limit, and the table
    ``{name: {"value", "limit"}}`` that a run prints.  A number without a
    limit, or a limit without a number, fails."""
    table = {k: {"value": numbers.get(k), "limit": limits.get(k)}
             for k in sorted(set(numbers) | set(limits))}
    ok = all(v["value"] is not None and v["limit"] is not None
             and v["value"] <= v["limit"] for v in table.values())
    return ok, table
