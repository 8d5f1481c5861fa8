"""The command: refuses what is not the cell's device, names the device it
ran on, and finds new cells, configurations, mixes and metrics by name."""

import json
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import harness, run
from bench_tiny import ROOT, last_json, make_root


def test_refuses_a_device_that_is_not_a_tpu(capsys):
    rc = run.main(["--workload", "qwen3-chat-bucketed", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "no TPU" in out.err


def test_command_from_a_checkout_of_bench_files_only_prints_nothing(
        tmp_path):
    """A directory holding BENCHMARK.json and the benchmark's own files but
    not the program exits nonzero without a result line."""
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-chat-bucketed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0 and r.stdout.strip() == ""


def _fake_jax(monkeypatch, platform, kind, n):
    import jax
    devs = [SimpleNamespace(platform=platform, device_kind=kind)] * n
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_refuses_an_unknown_device_kind(monkeypatch):
    _fake_jax(monkeypatch, "tpu", "TPU v9 imaginary", 1)
    with pytest.raises(harness.Refused, match="peaks.json"):
        harness.check_device(1, harness.load_peaks())


def test_refuses_another_chip_count(monkeypatch):
    _fake_jax(monkeypatch, "tpu", "TPU v5 lite", 4)
    with pytest.raises(harness.Refused, match="needs 1 chips"):
        harness.check_device(1, harness.load_peaks())
    dev = harness.check_device(4, harness.load_peaks())
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_unknown_workload_is_refused():
    with pytest.raises(harness.Refused, match="unknown workload"):
        harness.load_cell("no-such-cell")


def test_every_cell_finds_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        spec = harness.load_cell(w["name"])
        assert (ROOT / "bench/drivers" /
                f"{spec['config']['driver']}.py").exists()
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").exists()
        for m in spec["end_to_end"] + spec["per_layer"]:
            assert (ROOT / "bench/metrics" / f"{m['name']}.py").exists()
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]


def test_new_config_mix_and_metric_by_adding_files(tmp_path, capsys):
    """A cell, configuration, mix and per-layer metric that did not exist
    run by files added next to the existing ones."""
    extra = {"name": "engine.decode_steps_in_window.tiny", "unit": "count",
             "better": "higher", "source": "program_counter",
             "layer": "serving engine", "moves": "itl_p95_ms",
             "workloads": ["tiny-chat"]}
    root = make_root(tmp_path, extra_metrics=[extra])
    (root / "bench/metrics" / f"{extra['name']}.py").write_text(
        "def read(record):\n    return record['decode_steps']\n")
    rc = run.main(["--workload", "tiny-chat", "--seed", str(2**33 + 5),
                   "--seconds", "2", "--trace", "1"],
                  require_tpu=False, root=root)
    res = last_json(capsys.readouterr().out)
    assert rc == 0 and isinstance(res["correct"], bool)
    assert res["metrics"][extra["name"]]["value"] > 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"served.mean"}
