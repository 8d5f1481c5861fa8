"""The one traffic generator.  A mix is a JSON file under ``bench/traffic``
holding parameters only; this module turns it and ``--seed`` into requests.

A prompt's ``buckets`` are the lengths the engine compiles its prefill for;
a prompt shorter than its bucket is padded by the engine.  A mix with
``"on_buckets": true`` rounds every drawn length up to its bucket instead,
so every prompt is a whole bucket of real tokens and the engine pads
nothing.

Every seed gets the same multiset of sizes and gaps: lengths are the
distribution's quantiles at (i + 0.5) / n, gaps the exponential's, and the
seed only permutes them and draws the token ids.  So the work of a run is
fixed and the seed changes its order, not its amount.

Kinds:
  * ``open_poisson``: an open loop.  ``rate_per_s`` arrivals for
    ``lead_s`` seconds before the window and for the whole window.
  * ``backlog``: an offline batch.  The queue is topped up to
    ``queue_depth`` before every engine step, from an endless sequence of
    cycles of ``cycle`` requests.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Req:
    due: float           # seconds after the schedule's start; inf: backlog
    prompt: np.ndarray   # (n,) int32
    max_new: int
    phase: str           # "lead" | "window" | "backlog"


def load_mix(name: str, root: Path = TRAFFIC_DIR) -> dict:
    return json.loads((root / f"{name}.json").read_text())


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths: quantiles of the distribution, clipped, rounded up to a
    whole token (and to the next bucket where ``on_buckets`` is set)."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + np.floor((spec["max"] - spec["min"] + 1) * q)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    if spec.get("on_buckets"):
        b = np.asarray(spec["buckets"], np.int64)
        x = b[np.searchsorted(b, x)]
    return x


def lengths_of(mix: dict) -> list[int]:
    """The engine's prefill buckets for the mix's prompts."""
    return [int(b) for b in mix["prompt"]["buckets"]]


def max_len(mix: dict) -> int:
    """Cache positions a request can need: longest prompt + output."""
    return lengths_of(mix)[-1] + int(mix["output"]["max"])


def _gaps(n: int, span: float, rng) -> np.ndarray:
    """n exponential inter-arrival quantiles, permuted, summing to span."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return rng.permutation(g * (span / g.sum()))


def _requests(mix, n, rng, vocab, phase):
    pl = rng.permutation(quantile_lengths(mix["prompt"], n))
    ol = rng.permutation(quantile_lengths(mix["output"], n))
    return [Req(math.inf, rng.integers(1, vocab, int(a), dtype=np.int32),
                int(b), phase) for a, b in zip(pl, ol)]


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """open_poisson: every request of the lead-in and of the window, with
    due times in seconds from the start of the lead-in."""
    if mix["kind"] != "open_poisson":
        raise ValueError("schedule() is for open_poisson mixes")
    rng = np.random.default_rng(seed)
    rate, lead = mix["rate_per_s"], mix["lead_s"]
    out = []
    for phase, t0, span in (("lead", 0.0, lead), ("window", lead, seconds)):
        n = max(1, round(rate * span))
        reqs = _requests(mix, n, rng, vocab, phase)
        g = _gaps(n, span, rng)
        due = t0 + np.cumsum(g) - g          # the first arrival opens the phase
        for r, d in zip(reqs, due):
            r.due = float(d)
        out.extend(reqs)
    return out


def backlog(mix: dict, seed: int, vocab: int):
    """backlog: an endless iterator of requests, cycle by cycle."""
    if mix["kind"] != "backlog":
        raise ValueError("backlog() is for backlog mixes")
    rng = np.random.default_rng(seed)
    while True:
        yield from _requests(mix, mix["cycle"], rng, vocab, "backlog")
