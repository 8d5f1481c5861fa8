#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate that the
system sustains without a growing backlog.

    python3 bench/sweep.py --workload qwen3-chat-bucketed --rates 4,6,8,10 \\
        --seconds 20 --seed 1

Runs the cell once per rate in one process, with the mix's rate replaced,
and prints one JSON line per rate: requests offered and finished per
second, requests still queued at the close, and the TTFT / ITL tails.  A
rate is sustained when at most two requests wait for a slot at the
window's close: the backlog did not grow.  (Requests finished per second
trail the offered rate by the requests still running at the close, so
they are printed but not judged.)  The knee
found is written into the cell's traffic file by hand, as a number.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import harness, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    try:
        spec = harness.load_cell(args.workload)
        peaks = harness.load_peaks()
        device = harness.check_device(spec["cell"]["chips"], peaks)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    driver = harness.load_module(
        ROOT / "bench/drivers" / f"{spec['config']['driver']}.py", "drv")
    real = traffic.load_mix
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(real(spec["cell"]["traffic"]), rate_per_s=rate)
        driver.traffic.load_mix = lambda name, root=None, m=mix: m
        rec, *_ = driver.run(spec, args.seed, args.seconds, False,
                             time.perf_counter(), device, peaks,
                             check=False)
        done = rec["finished_in_window"] / args.seconds
        row = {"rate": rate, "offered": len(rec["ttft_s"]) / args.seconds,
               "finished_per_s": done, "queued_at_close":
               rec["queued_at_close"],
               "slots_occupied_at_open": rec["occupied_at_open"],
               "ttft_p50_ms": np.percentile(rec["ttft_s"], 50) * 1e3,
               "ttft_p95_ms": np.percentile(rec["ttft_s"], 95) * 1e3,
               "itl_p95_ms": np.percentile(rec["itl_s"], 95) * 1e3,
               "decode_step_ms": rec["decode_s"] / max(1, rec["decode_steps"])
               * 1e3}
        row["sustained"] = bool(rec["queued_at_close"] <= 2)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
