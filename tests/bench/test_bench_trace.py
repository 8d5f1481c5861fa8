"""The reduction from trace events to device time."""

import pytest

from bench import trace as T

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def ev(plane, name, t, d, line=T.OPS_LINE, meta=""):
    return {"plane": plane, "line": line, "name": name, "t": t, "d": d,
            "meta": meta}


def synthetic():
    host = "/host:CPU"
    return [
        ev(host, "bench.engine_step", 0, 1000, line="python"),
        ev(host, "bench.wait_arrival", 1000, 200, line="python"),
        ev(host, "ExecuteOnLocalDevices", 650, 100, line="python"),
        ev(DEV0, "fused_swiglu_pallas.3", 0, 300),
        ev(DEV0, "fusion.1", 250, 150,                  # overlaps the kernel
           meta="bf16[8] fusion(%fused_swiglu_pallas.3)"),
        ev(DEV0, "all-to-all.2", 600, 200, meta="hlo_op=all-to-all.2"),
        ev(DEV0, "fusion.4", 700, 50),                  # hides 50 ns of it
        ev(DEV1, "fused_swiglu_pallas.3", 0, 500),
        ev(DEV1, "all-to-all.2", 900, 100),
        ev(DEV0, "not an op", 0, 1200, line="XLA Modules"),
    ]


def test_window_is_the_benchmark_spans():
    assert T.window_ns(synthetic()) == (0, 1200)


def test_busy_is_the_union_per_chip_averaged():
    r = T.reduce(synthetic())
    # chip 0: [0,400) + [600,800) = 600; chip 1: [0,500) + [900,1000) = 600
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["window_s"] == pytest.approx(1200e-9)
    assert r["chips"] == 2


def test_op_seconds_match_name_or_hlo_text_and_average_chips():
    evs = synthetic()
    assert T.op_seconds(evs, r"^fused_swiglu_pallas\b") == pytest.approx(
        400e-9)
    assert T.op_seconds(evs, r"all-to-all") == pytest.approx(150e-9)
    assert T.op_seconds(evs, r"hlo_op=all-to-all") == pytest.approx(100e-9)
    assert T.op_seconds(evs, r"no-such-op") is None


def test_breakdown_names_ops_and_idle_gaps_by_host_span():
    r = T.reduce(synthetic())["breakdown"]
    assert r["device_ops"][0][0] == "fused_swiglu_pallas.3"
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    gaps = dict(r["idle_gaps"])
    # chip 0 idles [400,600) inside the step, [800,1200) across the wait
    assert gaps["bench.engine_step"] == pytest.approx(200e-9)
    assert gaps["bench.wait_arrival"] == pytest.approx(400e-9)


def test_union_merges_touching_intervals():
    assert T.union([(5, 7), (0, 2), (2, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert T.covered([(0, 10), (5, 15)]) == 15


def test_nested_ops_count_once_in_the_breakdown():
    evs = [ev(DEV0, "while.3", 0, 1000), ev(DEV0, "fused_swiglu_pallas.7",
                                            100, 600),
           ev(DEV0, "fusion.2", 800, 100), ev(DEV0, "copy.1", 1000, 50)]
    st = dict(T.self_times(evs))
    assert st == {"while.3": 300, "fused_swiglu_pallas.7": 600,
                  "fusion.2": 100, "copy.1": 50}


def test_recorded_chip_trace_slice():
    """40 ms of a traced qwen3-chat-poisson window on a TPU v5e: device ops
    and the Python thread's spans (tests/bench/data)."""
    from pathlib import Path
    evs = T.read_saved(Path(__file__).parent / "data" /
                       "chat_trace_slice.json.gz")
    r = T.reduce(evs)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    ops = T.device_ops(evs)["/device:TPU:0"]
    # ops nest (a layer scan's while holds its body): self times add up to
    # the busy time, not to the sum of durations
    assert sum(ns for _, ns in T.self_times(ops)) == pytest.approx(
        r["busy_s"] * 1e9, rel=1e-3)
    assert sum(e["d"] for e in ops) > 1.5 * r["busy_s"] * 1e9
    kern = T.op_seconds(evs, r"^fused_swiglu_pallas\b")
    assert kern is not None and 0 < kern < r["busy_s"]
    # an op that only takes the kernel's output is not the kernel
    fed = [e for e in evs if "%fused_swiglu_pallas" in e["meta"]
           and not e["name"].startswith("fused_swiglu")]
    assert all(not T.matches(e, __import__("re").compile(
        r"^fused_swiglu_pallas\b")) for e in fed)
    assert r["breakdown"]["idle_gaps"]
