"""Arithmetic the metric readers share.  A reader is ``read(record)``:
it returns the metric's value (or a dict with ``value`` and notes), or None
when the run has nothing for it to read."""

from __future__ import annotations

import numpy as np


def percentile(xs, q: float):
    """The q-th percentile over every sample (linear interpolation)."""
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def idle_share(record) -> float | None:
    tr = record.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
