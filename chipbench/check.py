"""The comparison that decides ``correct``.

Every output the window produced is compared with the reference's
answer for the same input, row for row. The number compared is the
count of rows in one but not the other (a row the program repeats
counts too), summed over all outputs. The configurations' guarantees
are exact fixpoints and exact views, so its limit is 0: the readings it
was set from are in PERF.md.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"mismatched_rows": 0}


def mismatched_rows(got: np.ndarray, want: np.ndarray) -> int:
    got = np.asarray(got, np.int64).reshape(len(got), -1)
    want = np.asarray(want, np.int64).reshape(len(want), -1)
    g = set(map(tuple, got.tolist()))
    w = set(map(tuple, want.tolist()))
    return len(g ^ w) + (len(got) - len(g)) + (len(want) - len(w))


def compare(outputs: list, expected: list) -> dict:
    """{"mismatched_rows": rows wrong over all outputs, "failed":
    outputs with any row wrong, "compared": outputs compared}."""
    if len(outputs) != len(expected):
        raise ValueError(f"{len(outputs)} outputs, {len(expected)} answers")
    wrong = [mismatched_rows(g, w) for g, w in zip(outputs, expected)]
    return {"mismatched_rows": sum(wrong),
            "failed": sum(1 for x in wrong if x),
            "compared": len(wrong)}


def checks(result: dict) -> dict:
    """Each number compared, beside its limit."""
    return {name: {"value": result[name], "limit": limit}
            for name, limit in LIMITS.items()}


def correct(result: dict) -> bool:
    return result["compared"] > 0 and all(
        result[name] <= limit for name, limit in LIMITS.items())
