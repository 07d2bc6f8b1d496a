"""The gap arithmetic every correctness check shares."""
from __future__ import annotations

import numpy as np


def widest_gap(program, reference):
    """Widest |program - reference| / (|reference| + 1) over all elements;
    a NaN or a missing value reads as infinitely far."""
    a = np.asarray(program, np.float64)
    b = np.asarray(reference, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    d = np.abs(a - b) / (np.abs(b) + 1.0)
    return float(np.max(np.where(np.isnan(d), np.inf, d)))


def rel_gap(program, reference):
    """|program - reference| / |reference|; a NaN reads as infinitely
    far."""
    d = abs(program - reference) / abs(reference) if reference else abs(
        program - reference)
    return float("inf") if d != d else float(d)
