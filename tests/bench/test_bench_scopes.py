"""The program's names in a trace (bench/scopes.py): op names from the HLO
of the trace's metadata plane, self time by named scope within one
executable, idle time by the engine's spans, and the metric readers that
read them."""

import glob
import json
from pathlib import Path

import pytest

from bench import harness
from bench import scopes as S
from bench import trace as T

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
HOST = "/host:CPU"
DATA = Path(__file__).parent / "data"
NEW = ("model.decode_device_ms.chat", "model.decode_scan_io_share.chat",
       "shuffle.staging_share.docs", "engine.host_ms_per_step.chat",
       "device.idle_outside_wait_share.chat")


def ev(plane, name, t, d, line=T.OPS_LINE, meta=""):
    return {"plane": plane, "line": line, "name": name, "t": t, "d": d,
            "meta": meta}


def _read(name, record):
    return harness.read_metrics([{"name": name, "unit": "x"}],
                                record).get(name)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(serve_decode)/layers/while/body/dynamic_slice", S.SCAN_IO),
    ("jit(serve_decode)/layers/while", S.SCAN_IO),
    ("jit(serve_decode)/layers/while/body/attention/dot_general",
     "attention"),
    ("jit(serve_decode)/layers/while/body/moe.decode/shard_map/moe.route/"
     "top_k", "moe.route"),
    ("jit(serve_decode)/layers/while/body/moe.decode/shard_map/"
     "moe.expert_ffn/jit(fused_swiglu_pallas)/fused_swiglu_pallas/"
     "pallas_call", "moe.expert_ffn"),
    ("jit(serve_decode)/layers/while/body/moe.decode/shard_map/mul",
     "moe.decode"),
    ("jit(serve_prefill)/layers/while/body/closed_call/checkpoint/"
     "shard_map/moe.dispatch/jit(segment_gather)/segment_gather/pallas_call",
     "moe.dispatch"),
    ("jit(serve_prefill)/lm_head/argmax", "lm_head"),
    ("jit(serve_prefill)/jit(_take)/gather", S.UNSCOPED),
    ("", S.UNSCOPED),
])
def test_scope_is_the_innermost_named_scope(op_name, scope):
    assert S.scope_of(op_name) == scope


@pytest.mark.parametrize("program,module", [
    ("jit_serve_decode(8902609927363826918)", "jit_serve_decode"),
    ("jit_serve_prefill(12)", "jit_serve_prefill"),
    ("jit__lambda", "jit__lambda"),
])
def test_module_name_drops_the_program_id(program, module):
    assert S.module_name(program) == module


DEC, PRE = "jit_serve_decode(11)", "jit_serve_prefill(12)"
NAMES = {
    DEC: {"while.1": "jit(serve_decode)/layers/while",
          "dynamic-slice_bitcast_fusion.2":
              "jit(serve_decode)/layers/while/body/dynamic_slice",
          "fusion.3": "jit(serve_decode)/layers/while/body/attention/dot",
          "fused_swiglu_pallas.4": "jit(serve_decode)/layers/while/body/"
                                   "moe.decode/moe.expert_ffn/pallas_call",
          "fusion.5": "jit(serve_decode)/lm_head/argmax"},
    PRE: {"segment_gather.6": "jit(serve_prefill)/layers/while/body/"
                              "moe.dispatch/pallas_call",
          "fused_swiglu_pallas.7": "jit(serve_prefill)/layers/while/body/"
                                   "moe.expert_ffn/pallas_call",
          "segment_scatter_add.8": "jit(serve_prefill)/layers/while/body/"
                                   "moe.combine/pallas_call"},
}


def scoped():
    """A decode run (a layer scan holding a parameter slice, attention and
    the expert kernel, then the head) and a prefill run on one chip, with
    the engine's spans on the host; :data:`NAMES` holds their op names."""
    mods = S.MODULES_LINE
    return [
        ev(HOST, "bench.engine_step", 0, 1200, line="python"),
        ev(HOST, "engine.step", 0, 1000, line="python"),
        ev(HOST, "engine.decode", 100, 700, line="python"),
        ev(HOST, "engine.wait", 300, 500, line="python"),
        ev(HOST, "engine.retire", 800, 100, line="python"),
        ev(DEV0, DEC, 100, 600, line=mods),
        ev(DEV0, "while.1", 100, 550),
        ev(DEV0, "dynamic-slice_bitcast_fusion.2", 100, 200),
        ev(DEV0, "fusion.3", 350, 50),
        ev(DEV0, "fused_swiglu_pallas.4", 400, 200),
        ev(DEV0, "fusion.5", 650, 50),
        ev(DEV0, PRE, 1100, 90, line=mods),
        ev(DEV0, "segment_gather.6", 1100, 20),
        ev(DEV0, "fused_swiglu_pallas.7", 1120, 50),
        ev(DEV0, "segment_scatter_add.8", 1170, 20),
    ]


def test_self_time_by_scope_within_one_module():
    evs = scoped()
    dec = S.scope_seconds(evs, "jit_serve_decode", NAMES)
    # the loop's own 100 ns outside its body ops count as scan plumbing
    assert dec == pytest.approx({S.SCAN_IO: 300e-9, "moe.expert_ffn": 200e-9,
                                 "attention": 50e-9, "lm_head": 50e-9})
    assert list(dec)[0] == S.SCAN_IO
    pre = S.scope_seconds(evs, "jit_serve_prefill", NAMES)
    assert pre == pytest.approx({"moe.expert_ffn": 50e-9,
                                 "moe.dispatch": 20e-9,
                                 "moe.combine": 20e-9})
    assert S.scope_seconds(evs, "jit_serve_insert", NAMES) == {}
    # a run of a program the names do not hold is not counted
    assert S.scope_seconds(evs, "jit_serve_decode", {PRE: NAMES[PRE]}) == {}
    # an op the program's names lack is unscoped
    part = {DEC: {k: v for k, v in NAMES[DEC].items() if k != "fusion.3"}}
    assert S.scope_seconds(evs, "jit_serve_decode", part)[S.UNSCOPED] == \
        pytest.approx(50e-9)
    assert S.module_seconds(evs, "jit_serve_decode") == [600e-9]


def test_ops_are_grouped_by_the_run_around_them_on_their_chip():
    m = S.MODULES_LINE
    evs = [ev(DEV0, "jit_serve_decode(1)", 100, 100, line=m),
           ev(DEV0, "jit_serve_prefill(2)", 300, 100, line=m),
           ev(DEV1, "jit_serve_decode(1)", 0, 1000, line=m),
           ev(DEV0, "fusion.1", 150, 10), ev(DEV0, "fusion.2", 250, 10),
           ev(DEV0, "fusion.3", 399, 10), ev(DEV1, "fusion.4", 250, 10),
           ev(DEV0, "fusion.0", 50, 10)]
    got = {k: [e["name"] for e in v] for k, v in S.runs(evs).items()}
    assert got == {(DEV0, 100, "jit_serve_decode(1)"): ["fusion.1"],
                   (DEV0, 300, "jit_serve_prefill(2)"): ["fusion.3"],
                   (DEV1, 0, "jit_serve_decode(1)"): ["fusion.4"]}


def test_idle_by_innermost_engine_span():
    by = S.idle_by_span(scoped())
    # busy [100,700) (the loop covers its body's gap) and [1100,1190) in
    # the window [0,1200): idle [0,100) step, [700,800) wait, [800,900)
    # retire, [900,1000) step, [1000,1100) and [1190,1200) outside
    assert by == pytest.approx({"engine.step": 200e-9, "engine.wait": 100e-9,
                                "engine.retire": 100e-9,
                                S.OUTSIDE: 110e-9})
    r = T.reduce(scoped())
    assert sum(by.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_host_step_seconds_count_whole_steps_in_the_window():
    evs = scoped() + [
        ev(HOST, "engine.step", 1100, 200, line="python"),   # cut by the end
        ev(HOST, "engine.wait", 1150, 50, line="python")]
    assert S.host_step_seconds(evs) == (1, pytest.approx(1000e-9),
                                        pytest.approx(500e-9))
    assert S.host_step_seconds([e for e in evs if e["name"] !=
                                "engine.step"])[0] == 0


def _message(*fields) -> bytes:
    """A protobuf message of (field, value) pairs: an int is a varint,
    bytes or str a length-delimited field."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def _xspace(programs: dict[str, dict[str, str]]) -> bytes:
    """An XSpace whose metadata plane holds an HloProto per program."""
    stat_meta = _message((1, 7), (2, S.HLO_PROTO))
    plane = [(1, 3), (2, S.METADATA_PLANE), (5, _message((1, 7),
                                                         (2, stat_meta)))]
    for i, (prog, ops) in enumerate(programs.items(), 1):
        comp = _message((1, "main"), *[
            (2, _message((1, op), (2, "fusion"),
                         *([(7, _message((1, "mul"), (2, n)))] if n else [])))
            for op, n in ops.items()])
        hlo = _message((1, _message((1, "m"), (3, comp))))
        em = _message((1, i), (2, prog),
                      (5, _message((1, 7), (6, hlo))))
        plane.append((4, _message((1, i), (2, em))))
    return _message((1, _message((2, "/host:CPU"))), (1, _message(*plane)),
                    (4, "host"))


def test_op_names_read_the_metadata_plane():
    raw = _xspace({DEC: NAMES[DEC], PRE: dict(NAMES[PRE], copy="")})
    got = S.op_names(raw)
    assert got == {DEC: NAMES[DEC], PRE: dict(NAMES[PRE], copy="")}
    assert S.op_names(_message((1, _message((2, "/host:CPU"))))) == {}


def test_a_malformed_message_is_an_error():
    with pytest.raises(ValueError, match="wire type 3"):
        list(S._fields(bytes([1 << 3 | 3])))


def test_find_op_names_takes_the_newest_trace_holding_the_programs(
        tmp_path, monkeypatch):
    import os
    monkeypatch.setattr(S, "TRACES", tmp_path)
    old, new = tmp_path / "a" / "old.xplane.pb", tmp_path / "b" / "x.xplane.pb"
    for p in (old, new):
        p.parent.mkdir()
    old.write_bytes(_xspace({DEC: {"fusion.3": "jit(f)/lm_head/x"}}))
    new.write_bytes(_xspace(NAMES))
    os.utime(old, (1, 1))
    (tmp_path / "c").mkdir()
    (tmp_path / "c" / "bad.xplane.pb").write_bytes(bytes([1 << 3 | 3]))
    evs = scoped()
    assert S.find_op_names(evs, "jit_serve_decode") == {
        DEC: NAMES[DEC]}
    assert S.find_op_names(evs, "jit_serve_prefill") == {
        PRE: NAMES[PRE]}
    new.unlink()
    assert S.find_op_names(evs, "jit_serve_decode") == {
        DEC: {"fusion.3": "jit(f)/lm_head/x"}}
    assert S.find_op_names(evs, "jit_serve_prefill") == {}
    assert S.find_op_names(evs, "jit_serve_insert") == {}


def test_op_names_of_a_profile_tell_two_buckets_apart(tmp_path):
    """A CPU profile of one function compiled at two shapes (two prefill
    buckets): the metadata plane holds each executable under its own
    program id, with the named scopes in its op names."""
    import jax
    import jax.numpy as jnp

    def serve_prefill(x):
        with jax.named_scope("layers"):
            with jax.named_scope("attention"):
                y = jnp.sin(x) @ x
        with jax.named_scope("lm_head"):
            return jnp.argmax(y, -1)
    f = jax.jit(serve_prefill)
    small, big = jnp.ones((8, 8)), jnp.ones((16, 16))
    f(small).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(big).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = S.op_names(Path(path).read_bytes())
    progs = [p for p in names if S.module_name(p) == "jit_serve_prefill"]
    assert len(progs) == 2 and len(set(progs)) == 2
    for p in progs:
        scopes = {S.scope_of(n) for n in names[p].values()}
        assert {"attention", "lm_head"} <= scopes, p


def test_existing_readings_of_the_recorded_slice_are_unchanged():
    """The values the accepted trace metrics read from the recorded chat
    slice, pinned; the new readers find nothing there (a program of before
    the named executables and spans), and leave their metrics out."""
    root = Path(__file__).resolve().parents[2]
    evs = T.read_saved(DATA / "chat_trace_slice.json.gz")
    r = T.reduce(evs)
    assert (r["busy_s"], r["window_s"], r["chips"]) == (0.073876628,
                                                        0.13776181, 1)
    assert r["breakdown"]["device_ops"][:2] == [
        ["while.42", 0.033225678], ["fused_swiglu_pallas.8", 0.014380166]]
    assert r["breakdown"]["idle_gaps"][0] == ["$engine.py:605 step",
                                              0.063879913]
    assert T.op_seconds(evs, r"^fused_swiglu_pallas\b") == 0.014380166
    model = json.loads((root / "bench/configs/qwen3-30b-a3b-serve1.json")
                       .read_text())["model"]
    rec = {"trace": dict(r, events=evs), "trace_calls": [("decode", 32)],
           "model": model, "peak": harness.load_peaks()["TPU v5 lite"]}
    names = ("device.idle_share.chat", "device.idle_share.docs",
             "kernel.expert_ffn_roofline.docs") + NEW
    got = harness.read_metrics([{"name": n, "unit": "%"} for n in names],
                               rec)
    assert set(got) == set(names[:3])
    assert got["device.idle_share.chat"]["value"] == pytest.approx(
        46.37365173991254, rel=1e-12)
    assert got["device.idle_share.docs"]["value"] == pytest.approx(
        46.37365173991254, rel=1e-12)
    assert got["kernel.expert_ffn_roofline.docs"]["value"] == pytest.approx(
        71.7920716695638, rel=1e-12)


def test_trace_metrics_read_scopes_modules_and_spans(monkeypatch):
    evs = scoped()
    monkeypatch.setattr(S, "find_op_names", lambda events, module: {
        p: v for p, v in NAMES.items() if S.module_name(p) == module})
    rec = {"trace": dict(T.reduce(evs), events=evs)}
    got = harness.read_metrics([{"name": n, "unit": "x"} for n in NEW], rec)
    assert got["model.decode_device_ms.chat"]["value"] == pytest.approx(
        600e-9 * 1e3)
    assert got["model.decode_device_ms.chat"]["runs"] == 1
    io = got["model.decode_scan_io_share.chat"]
    assert io["value"] == pytest.approx(100 * 300 / 600)
    assert io["by_scope"][S.SCAN_IO] == pytest.approx(300e-9)
    st = got["shuffle.staging_share.docs"]
    assert st["value"] == pytest.approx(100 * 40 / 90)
    assert set(st["by_scope"]) == {"moe.dispatch", "moe.expert_ffn",
                                   "moe.combine"}
    host = got["engine.host_ms_per_step.chat"]
    assert host["value"] == pytest.approx((1000 - 500) * 1e-9 * 1e3)
    assert host["steps"] == 1
    idle = got["device.idle_outside_wait_share.chat"]
    assert idle["value"] == pytest.approx(100 * (510 - 100) / 510)
    assert idle["by_span"]["engine.wait"] == pytest.approx(100e-9)


def test_trace_metrics_are_left_out_for_a_program_without_scopes():
    """A trace of a program whose executables are unnamed and whose engine
    writes no spans (the program before them) leaves every new metric out
    without looking for a trace on disk."""
    evs = [dict(e, name=e["name"].replace("jit_serve_decode", "jit__lambda")
                .replace("jit_serve_prefill", "jit__lambda"))
           for e in scoped() if not e["name"].startswith("engine.")]
    rec = {"trace": dict(T.reduce(evs), events=evs)}
    for name in NEW:
        assert _read(name, rec) is None, name
    assert _read("model.decode_device_ms.chat", {"trace": {}}) is None


def test_every_new_metric_has_an_entry_and_a_reader():
    root = Path(__file__).resolve().parents[2]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert m["workloads"] and m["moves"] in {
            e["name"] for e in bench["end_to_end"]}
        mod = harness.load_module(root / "bench" / "metrics" / f"{name}.py",
                                  "m")
        assert mod.__doc__ and callable(mod.read)


def test_recorded_v5e_decode_step_by_scope(monkeypatch):
    """One decode-only engine step of the docs cell on a TPU v5e: the
    engine's spans, the ``jit_serve_decode`` run and its 725 ops, and the
    op names the trace's metadata plane gives them (tests/bench/data; the
    ``Async XLA Ops`` line, which no reduction reads, left out)."""
    import gzip
    with gzip.open(DATA / "docs_decode_step.json.gz", "rt") as f:
        rec = json.load(f)
    evs, names = rec["events"], rec["op_names"]
    (prog,) = names
    assert all(e["name"] in names[prog] for e in evs
               if e["line"] == T.OPS_LINE)
    r = T.reduce(evs)
    by = S.scope_seconds(evs, "jit_serve_decode", names)
    total = sum(by.values())
    # every op of the step belongs to the one decode run
    assert total == pytest.approx(r["busy_s"], rel=1e-9)
    assert S.module_seconds(evs, "jit_serve_decode") == pytest.approx(
        [0.050383469])
    # the layer scan's weight copies lead, then the expert kernel; the
    # named scopes claim all but a few percent
    assert list(by)[:2] == [S.SCAN_IO, "moe.expert_ffn"]
    assert by[S.SCAN_IO] / total == pytest.approx(0.6778, abs=1e-4)
    assert 1 - by[S.UNSCOPED] / total > 0.95
    assert set(by) == {S.SCAN_IO, "moe.expert_ffn", S.UNSCOPED, "attention",
                       "lm_head", "moe.decode", "moe.route"}
    idle = S.idle_by_span(evs)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert list(idle)[:2] == ["engine.wait", "engine.decode"]
    steps, step_s, wait_s = S.host_step_seconds(evs)
    assert steps == 1 and 0 < wait_s < step_s < r["window_s"]
    spans = {e["name"] for e in evs if e["plane"].startswith("/host")}
    assert spans == {"bench.engine_step", "engine.step", "engine.admit",
                     "engine.decode", "engine.wait", "engine.retire"}
    monkeypatch.setattr(S, "find_op_names", lambda events, module: names)
    got = _read("model.decode_scan_io_share.chat",
                {"trace": dict(r, events=evs)})
    assert got["value"] == pytest.approx(100 * by[S.SCAN_IO] / total)
