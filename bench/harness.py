"""What every cell shares: finding its files by name, refusing a device
that is not the cell's, the metric readers, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
configuration's file (``bench/configs/<name>.json``) names its driver
(``bench/drivers/<driver>.py``), and every metric is read by
``bench/metrics/<metric>.py``.  Nothing here knows a cell, a model or a
metric by name, so a new cell, configuration, mix or metric is new files
and entries only.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(RuntimeError):
    """The run cannot be made here (device, files): exit nonzero, no line."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise Refused(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell, its configuration's file, and the metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())

    def mine(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "config": config, "root": root,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def check_device(chips: int, peaks: dict) -> dict:
    """The platform must be a TPU, with exactly the cell's chip count, of a
    kind the peak table has."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise Refused(f"no TPU: JAX found {d0.platform} devices")
    if len(devs) != chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if d0.device_kind not in peaks:
        raise Refused(f"device kind {d0.device_kind!r} has no entry in "
                      "bench/peaks.json")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def host_device(peaks: dict) -> dict:
    """The devices as they are, for runs that skip the chip check (tests of
    the harness on the CPU).  Their kind gets no peaks: every share of a
    peak then reads as not measured."""
    import jax
    d0 = jax.devices()[0]
    nan = float("nan")
    peaks.setdefault(d0.device_kind, {"bf16_flops": nan, "hbm_bw": nan})
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices())}


def load_peaks(root: Path = BENCH) -> dict:
    table = json.loads((root / "peaks.json").read_text())
    return {k: v for k, v in table.items() if not k.startswith("_")}


def read_metrics(entries: list[dict], record: dict,
                 root: Path = BENCH) -> dict:
    """Each metric from its own reader; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in entries:
        mod = load_module(root / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name'].replace('.', '_')}")
        v = mod.read(record)
        extra = {}
        if isinstance(v, dict):
            extra = {k: x for k, x in v.items() if k != "value"}
            v = v["value"]
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"], **extra}
    return out


def emit(result: dict, checks: list[tuple[str, float, float]]) -> None:
    """Checked numbers as the last lines of stderr, and the result as the
    last line of stdout, with the checks under the key that comes last."""
    for name, value, limit in checks:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    print(json.dumps(result), flush=True)
