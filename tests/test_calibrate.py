"""Runtime pipe-constant calibration: sane rates, end-to-end consumption."""

import dataclasses

import jax
import pytest

from repro.core import calibrate, commplan, pipesim
from repro.core.dcomm import DcommConfig


@pytest.fixture(scope="module")
def table():
    return calibrate.calibrate(payload_bytes=1 << 19, repeats=2)


def test_rates_positive_and_finite(table):
    assert calibrate._MIN_BW <= table.stage_bw <= calibrate._MAX_BW
    assert calibrate._MIN_BW <= table.wire_bw <= calibrate._MAX_BW
    assert calibrate._MIN_OVH <= table.overhead_s <= calibrate._MAX_OVH
    assert table.platform and table.payload_bytes > 0
    d = table.as_dict()
    assert set(d) == {"stage_bw", "wire_bw", "overhead_s", "platform",
                      "payload_bytes", "wire_measured"}
    # one device has no wire to time: the table must say so
    assert table.wire_measured == (len(jax.devices()) > 1)


def test_apply_threads_into_linkcosts_and_pipesim(table):
    cfg = calibrate.apply(table, DcommConfig(engine="fused_pipe",
                                             ep_axis="model"))
    assert cfg.pipe_stage_bw == table.stage_bw
    assert cfg.pipe_wire_bw == table.wire_bw
    assert cfg.pipe_overhead_s == table.overhead_s
    lc = commplan.LinkCosts.from_dcomm(cfg)
    assert (lc.intra_bw, lc.inter_bw, lc.hop_overhead_s) == (
        table.stage_bw, table.wire_bw, table.overhead_s)
    p = pipesim.params_from_dcomm(1 << 22, cfg)
    assert (p.stage_bw, p.wire_bw, p.per_slice_overhead_s) == (
        table.stage_bw, table.wire_bw, table.overhead_s)
    plan = pipesim.plan_slices(p)
    assert plan["n_slices"] >= 1 and plan["total_s"] > 0


def test_clamp_refuses_degenerate_rates():
    assert calibrate._clamp(0.0, 1.0, 10.0) == 1.0
    assert calibrate._clamp(-5.0, 1.0, 10.0) == 1.0
    assert calibrate._clamp(float("nan"), 1.0, 10.0) == 1.0
    assert calibrate._clamp(float("inf"), 1.0, 10.0) == 10.0
    assert calibrate._clamp(3.0, 1.0, 10.0) == 3.0


def test_make_context_accepts_calibration(table):
    from repro.configs import get_arch
    from repro.launch.mesh import make_host_mesh
    from repro.models.lm import make_context

    cfg = get_arch("qwen3-moe-30b-a3b").reduced()
    mesh = make_host_mesh()
    ctx = make_context(cfg, mesh, multi_pod=False, engine="fused_pipe",
                       calibration=table)
    assert ctx.dcfg.pipe_stage_bw == table.stage_bw
    assert ctx.dcfg.pipe_wire_bw == table.wire_bw
    base = make_context(cfg, mesh, multi_pod=False, engine="fused_pipe")
    assert base.dcfg.pipe_stage_bw == 819e9       # defaults untouched
    assert dataclasses.replace(
        ctx.dcfg, pipe_stage_bw=819e9, pipe_wire_bw=50e9,
        pipe_overhead_s=2e-6) == base.dcfg        # only the 3 constants moved
