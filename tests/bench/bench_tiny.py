"""A checkout in a temporary directory with one tiny serving cell added
(the program's reduced qwen3-moe widths, CPU-sized), made only by adding
files: nothing of ``bench/`` is edited."""

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


def make_root(tmp: Path, *, extra_metrics=()) -> Path:
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(DATA / "tiny-moe.json", tmp / "bench/configs/tiny-moe.json")
    for mix in ("tiny_chat", "tiny_backlog"):
        shutil.copy(DATA / f"{mix}.json", tmp / f"bench/traffic/{mix}.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny-moe", "source": "reduced qwen3-moe-30b-a3b",
         "file": "bench/configs/tiny-moe.json", "reduced": [],
         "why": "CPU-sized"})
    bench["workloads"] += [
        {"name": "tiny-chat", "config": "tiny-moe", "traffic": "tiny_chat",
         "chips": 1, "why": "CPU-sized chat"},
        {"name": "tiny-docs", "config": "tiny-moe",
         "traffic": "tiny_backlog", "chips": 1, "why": "CPU-sized backlog"}]
    swap = {"qwen3-chat-bucketed": "tiny-chat",
            "qwen3-docs-backlog": "tiny-docs"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [
                swap[w] for w in m["workloads"] if w in swap]
    bench["per_layer"] += list(extra_metrics)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
