"""The comparison that decides `correct`.

Each number compared has a limit of its own, kept in the cell's traffic file
and set from readings on the chip (PERF.md gives them). A run prints every
number beside its limit, as its last lines on standard error and under the
result line's last key, whether or not it passed.
"""

from __future__ import annotations

import math
import sys
from typing import Dict, Iterable, Tuple

import numpy as np


UNREADABLE = 1e300  # stands for an infinite or undefined reading


def _difference(answer, reference):
    """(answer - reference, reference) in float64, or None for an answer
    that is not finite or has another shape: it reads infinite."""
    a = np.asarray(answer, np.float64)
    r = np.asarray(reference, np.float64)
    if a.shape != r.shape or not np.all(np.isfinite(a)):
        return None
    return a - r, r


def coefficient_gap(answer, reference) -> float:
    """Norm of the difference over the norm of the reference."""
    pair = _difference(answer, reference)
    if pair is None:
        return math.inf
    return float(np.linalg.norm(pair[0]) / max(np.linalg.norm(pair[1]), 1e-300))


def largest_miss(answer, reference) -> float:
    """The largest difference of one coefficient over the reference's largest
    coefficient: it sees one altered element that the norm of a million
    would hide."""
    pair = _difference(answer, reference)
    if pair is None:
        return math.inf
    return float(np.max(np.abs(pair[0])) / max(np.max(np.abs(pair[1])), 1e-300))


def worst(measure, answers: Iterable[Tuple[int, np.ndarray]], references: Dict[int, np.ndarray]) -> float:
    """The widest `measure` of any answer from the reference of the table it
    was fitted on; `answers` are (table index, coefficient). No answer at
    all reads infinite."""
    widest = -math.inf
    for index, coeff in answers:
        widest = max(widest, measure(coeff, references[index]))
    return widest if widest >= 0 else math.inf


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """`correct`, and each number beside its limit. Every limit has to be
    met by a number that was read; a number without a limit is an error."""
    compared = {}
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for the compared number {name!r}")
        limit = float(limits[name])
        if not math.isfinite(value):  # the result line has to stay plain JSON
            value = UNREADABLE
        passed = bool(value <= limit)
        ok = ok and passed
        compared[name] = {"value": value, "limit": limit, "ok": passed}
    for name in limits:
        if name not in numbers:
            raise KeyError(f"the limit {name!r} has no number read against it")
    return ok, compared


def report(compared: Dict[str, dict], correct: bool) -> None:
    """Last lines of standard error: each number beside its limit."""
    for name, entry in compared.items():
        print(
            f"compared {name} = {entry['value']!r} limit {entry['limit']!r} "
            f"{'ok' if entry['ok'] else 'FAILED'}",
            file=sys.stderr,
        )
    print(f"correct = {correct}", file=sys.stderr, flush=True)
