"""Decide ``correct``: the answers the window returned, compared request by
request with the plain reference (``reference.py``).

The configurations promise exact answers, so every limit but one is 0:

    failed_requests      requests that raised or never resolved;
    overflowed_calls     engine calls of the window whose
                         ``Counters.overflow`` was set: a frontier, beam
                         or result cap cut the answer short;
    degraded_dispatches  batches the queue served on a fallback engine;
    mismatched_rows      compared rows whose answer differs from the
                         reference: for select, the sorted id set; for
                         the nearest point, the squared distance (bit for
                         bit) and an id at that distance;
    compared_rows        rows compared, at least 1.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from chipbench.reference import Reference


def mismatched_rows(op: str, rows: Sequence[np.ndarray],
                    answers: Sequence, ref: Reference) -> Tuple[int, int]:
    """(mismatched rows, compared rows) over requests ``rows`` with the
    program's ``answers`` (select: a list of id arrays per request; knn at
    k=1: (ids (m, 1), squared distances (m, 1), overflow)).  The nearest-point
    reference answers k=1 only: a wider answer is refused, not half read."""
    bad = total = 0
    for q, ans in zip(rows, answers):
        total += len(q)
        if op == "select":
            want = ref.select(q)
            bad += sum(not np.array_equal(np.asarray(a), w)
                       for a, w in zip(ans, want))
            bad += abs(len(ans) - len(want))
        elif op == "knn":
            ids, dists = np.asarray(ans[0]), np.asarray(ans[1])
            if ids.ndim != 2 or ids.shape[1] != 1:
                raise ValueError(f"the reference compares k=1 answers only, "
                                 f"got ids of shape {ids.shape}")
            want_d, tied = ref.nearest(q[:, :2])
            for i in range(len(q)):
                ok = (len(ids) > i and np.float32(dists[i, 0]) == want_d[i]
                      and int(ids[i, 0]) in tied[i])
                bad += not ok
        else:
            raise ValueError(f"no reference for operator {op!r}")
    return bad, total


def checks(values: Dict[str, int]) -> Dict[str, Dict]:
    """Each number compared beside its limit."""
    out = {}
    for name, v in values.items():
        out[name] = ({"value": v, "min": 1} if name == "compared_rows"
                     else {"value": v, "max": 0})
    return out


def passed(chk: Dict[str, Dict]) -> bool:
    return all(("max" not in c or c["value"] <= c["max"])
               and ("min" not in c or c["value"] >= c["min"])
               for c in chk.values())


def lines(chk: Dict[str, Dict]) -> List[str]:
    return [f"check {n}: {c['value']} "
            + (f"<= {c['max']}" if "max" in c else f">= {c['min']}")
            for n, c in chk.items()]
