"""Metric arithmetic: percentiles over every request, censored requests
counted, rates over the whole window, readers that find nothing say so."""

import math

import numpy as np
import pytest

from bench import flops, harness
from bench.drivers import serve


def _entry(name, unit="x"):
    return {"name": name, "unit": unit}


def _read(name, record):
    return harness.read_metrics([_entry(name)], record).get(name, {}).get(
        "value")


def _run(times, dues, sched_phase="window", t_open=10.0, t_close=20.0):
    """A fake run record: request i due at dues[i], tokens at times[i]."""
    clock = serve.Clock()
    sched, rid_of = [], {}

    class Q:
        def __init__(self, rid, n):
            self.rid, self.prompt = rid, np.zeros(n, np.int32)
            self.submitted_at, self.done = 0.0, True
    for i, (due, ts) in enumerate(zip(dues, times)):
        clock.times[i] = list(ts)
        clock.due[i] = due
        clock.req[i] = Q(i, 100)
        rid_of[i] = i
        r = type("R", (), {})()
        r.due = due
        r.phase = sched_phase if t_open <= due < t_close else "lead"
        sched.append(r)

    class Eng:
        occupancy = [0.5, 1.0, 1.0, 0.5]
        queue = []
    run = dict(clock=clock, sched=sched, rid_of=rid_of, start=0.0,
               t_open=t_open, t_close=t_close, snap=dict(
                   compiles=3, decode_s=1.0, decode_steps=10, occ=1,
                   occupied=2),
               snap_close=dict(compiles=3, decode_s=3.0, decode_steps=110,
                               occ=4, queued=0))
    model = {"hidden_size": 8, "head_dim": 2, "num_attention_heads": 2,
             "num_key_value_heads": 1, "num_experts": 4,
             "num_experts_per_tok": 2, "moe_intermediate_size": 4,
             "vocab_size": 16, "num_hidden_layers": 1}
    return serve.summarize(run, Eng(), model, t_close - t_open)


def test_ttft_percentile_is_over_all_due_requests_with_censoring():
    dues = [10.0 + i * 0.1 for i in range(40)]
    times = [[d + 0.05, d + 0.1] for d in dues[:34]] + [[]] * 5 + [[25.0]]
    rec = _run(times, dues)
    assert rec["censored"] == 6 and len(rec["ttft_s"]) == 40
    # the censored requests count at their elapsed time to the close
    assert sorted(rec["ttft_s"])[-6:] == pytest.approx(
        sorted(20.0 - d for d in dues[34:]))
    # without the censored requests the 95th percentile would be 50 ms
    assert np.percentile(rec["ttft_s"], 95) > 6.0


def test_requests_due_outside_window_are_not_timed():
    rec = _run([[5.1], [15.1], [21.0]], [5.0, 15.0, 20.5])
    assert rec["ttft_s"] == pytest.approx([0.1])


def test_itl_counts_every_gap_whose_later_token_is_in_window():
    rec = _run([[9.0, 9.5, 10.5, 11.0, 21.0]], [9.0], sched_phase="lead")
    assert rec["itl_s"] == pytest.approx([1.0, 0.5])
    assert _read("itl_p95_ms", rec) == pytest.approx(
        np.percentile([1.0, 0.5], 95) * 1e3)


def test_rates_are_over_the_whole_window():
    rec = _run([[10.5, 11.0, 12.0], [19.0, 19.5, 22.0]], [10.0, 18.0])
    # tokens at 10.5..19.5 inside [10, 20): 5 output, 2 prompts of 100
    assert rec["output_tokens"] == 5 and rec["prompt_tokens"] == 200
    assert _read("serve_tokens_per_s", rec) == pytest.approx(205 / 10.0)


def test_engine_counters_are_window_deltas():
    rec = _run([[10.5]], [10.0])
    assert _read("engine.decode_step_ms.chat", rec) == pytest.approx(
        2.0 / 100 * 1e3)
    assert _read("engine.compiles_in_window.chat", rec) == 0
    assert _read("engine.slot_occupancy.docs", rec) == pytest.approx(
        100 * np.mean([1.0, 1.0, 0.5]))


def test_readers_without_data_leave_the_metric_out():
    rec = {"open_loop": False, "itl_s": [], "prompt_tokens": 0,
           "output_tokens": 0, "window_s": 10.0, "trace": {},
           "decode_steps": 0, "model_flops": 0.0}
    for name in ("itl_p95_ms", "serve_tokens_per_s",
                 "device.idle_share.chat", "engine.decode_step_ms.chat",
                 "kernel.expert_ffn_roofline.docs", "serve_mfu.docs"):
        assert _read(name, rec) is None, name


def test_shares_of_a_peak_are_left_out_without_a_peak():
    nan = float("nan")
    rec = {"model_flops": 1e12, "window_s": 1.0, "chips": 1,
           "peak": {"bf16_flops": nan, "hbm_bw": nan}}
    assert _read("serve_mfu.docs", rec) is None
    rec["peak"] = {"bf16_flops": 1e14, "hbm_bw": 1e12}
    assert _read("serve_mfu.docs", rec) == pytest.approx(1.0)


def test_expert_ffn_work_counts_routed_rows_and_hit_experts():
    a = {"hidden_size": 2048, "moe_intermediate_size": 768,
         "num_experts": 128, "num_experts_per_tok": 8}
    ops, by = flops.expert_ffn_work(a, 1)
    assert ops == 8 * 3 * 2 * 2048 * 768
    assert by == pytest.approx(8 * 3 * 2048 * 768 * 2 + 2 * 8 * 2048 * 2)
    _, big = flops.expert_ffn_work(a, 100000)
    assert big == pytest.approx(128 * 3 * 2048 * 768 * 2 +
                                2 * 800000 * 2048 * 2)
    t, bound = flops.least_time(ops, by, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(by / 819e9)


def test_prefill_flops_grow_with_the_prompt():
    a = {"hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
         "num_key_value_heads": 4, "num_experts": 128,
         "num_experts_per_tok": 8, "moe_intermediate_size": 768,
         "vocab_size": 151936, "num_hidden_layers": 8}
    one = flops.prefill_flops(a, 1)
    assert one == pytest.approx(8 * (flops.layer_linear_flops(a) +
                                     4 * 32 * 128) + 2 * 2048 * 151936)
    assert flops.prefill_flops(a, 1024) > 1000 * flops.layer_linear_flops(a)
    assert math.isclose(flops.decode_flops(a, 10),
                        8 * (flops.layer_linear_flops(a) + 4 * 32 * 128 * 10)
                        + 2 * 2048 * 151936)
