#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``, whose ``driver`` names
``bench/drivers/<driver>.py``) and a traffic mix (``bench/traffic/<mix>``).
With ``--trace 0`` the result reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of a short
part of the window.  The last stdout line is one JSON object; the numbers
that decide ``correct`` are the last lines of stderr.  Without a TPU, with
another chip count than the cell's, or with a device kind that
``bench/peaks.json`` lacks, it exits 2 and prints no result.

``--control fp8`` (or ``int8``) makes the run the control of ``correct``:
the plain reference computed in that precision is put in the program's
place for the comparison, so the run has to report ``correct: false``.
The benchmark's own runs never pass it.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8", "int8"), default=None)
    return ap.parse_args(argv)


def main(argv=None, *, require_tpu: bool = True, root: Path = ROOT) -> int:
    args = parse(argv)
    try:
        spec = harness.load_cell(args.workload, root)
        peaks = harness.load_peaks(root / "bench")
        driver = harness.load_module(
            root / "bench" / "drivers" / f"{spec['config']['driver']}.py",
            "bench_driver")
        if require_tpu:
            device = harness.check_device(spec["cell"]["chips"], peaks)
        else:
            device = harness.host_device(peaks)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    trace_dir = root / "bench_out" / "trace" / args.workload
    rec, dev, correct, checks, _ = driver.run(
        spec, args.seed, args.seconds, bool(args.trace), T0, device, peaks,
        trace_dir=trace_dir, control=args.control, cache=require_tpu)
    entries = spec["per_layer"] if args.trace else spec["end_to_end"]
    result = {"correct": correct, "attempted": int(rec["attempted"]),
              "failed": int(rec.get("failed", 0)),
              "metrics": harness.read_metrics(entries, rec, root / "bench"),
              "device": dev}
    if args.trace and rec.get("trace", {}).get("busy_s") is not None:
        result["device"]["busy_s"] = rec["trace"]["busy_s"]
        result["device"]["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = rec["trace"]["breakdown"]
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
