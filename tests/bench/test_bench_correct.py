"""``correct`` against the plain reference, on the CPU at a tiny size: the
program's tokens lie within the limit, and a run whose timed path is broken
underneath, or the reference in a lower precision put in the program's
place (``--control``), does not.

The readings here are of a fixed set of requests served to completion, so
they do not depend on how many requests a timed window finishes on a busy
machine.  The tiny configuration's limit (tests/bench/data/tiny-moe.json)
lies between what the program read on seeds 1-8 (mean gap at most 0.00043)
and what the float8 control read on them (at least 0.00085)."""

import json

import jax.numpy as jnp
import pytest

from bench import harness, run, traffic
from bench_tiny import DATA, last_json, make_root

CONFIG = json.loads((DATA / "tiny-moe.json").read_text())
MIX = json.loads((DATA / "tiny_chat.json").read_text())
NUMBER, LIMIT = CONFIG["check"]["number"], CONFIG["check"]["limit"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


def _served(root, seed, control=None):
    """Serve the mix's first requests to completion and read the gaps."""
    driver = harness.load_module(root / "bench/drivers/serve.py", "drv")
    mesh, params, eng = driver.build(CONFIG, MIX, seed)
    vocab = CONFIG["model"]["vocab_size"]
    with mesh:
        eng.warmup(params)
        for r in traffic.schedule(MIX, seed, 2.0, vocab)[:6]:
            eng.submit(r.prompt, max_new=r.max_new)
        eng.run(params)
    return driver.reference_gaps(CONFIG, seed, eng.finished,
                                 traffic.max_len(MIX),
                                 MIX["output"]["max"], control=control)


def _altered_token(monkeypatch):
    """A token altered where it is produced: the sampler's pick moves to
    the next id."""
    from repro.serving import engine

    def greedy(out):
        logits, *rest = out
        tok = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
        return (tok.astype(jnp.int32), *rest)
    monkeypatch.setattr(engine, "greedy", greedy)


def _stale_state(monkeypatch):
    """A decode step that returns its state unchanged."""
    from repro.models import lm
    real = lm.decode_step

    def decode_step(params, state, inputs, ctx, max_len):
        logits, _ = real(params, state, inputs, ctx, max_len)
        return logits, state
    monkeypatch.setattr(lm, "decode_step", decode_step)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_program_within_limit_and_float8_control_beyond_it(root, seed):
    got = _served(root, seed, control="fp8")
    assert got["program." + NUMBER.split(".")[1]] <= LIMIT
    assert got[NUMBER] > LIMIT
    assert _served(root, seed)[NUMBER] == got["program.mean"]


def test_float8_control_run_is_not_correct(root, capsys):
    """A whole run (chip check skipped) with the float8 reference put in
    the program's place reports ``correct: false``, its number beside the
    limit; the same run without the control is correct."""
    argv = ["--workload", "tiny-chat", "--seed", "7", "--seconds", "2",
            "--trace", "0"]
    rc = run.main(argv + ["--control", "fp8"], require_tpu=False, root=root)
    res = last_json(capsys.readouterr().out)
    assert rc == 0 and res["correct"] is False
    assert res["checks"][NUMBER]["value"] > LIMIT
    assert res["checks"][NUMBER]["limit"] == LIMIT
    rc = run.main(argv, require_tpu=False, root=root)
    res = last_json(capsys.readouterr().out)
    assert rc == 0 and res["correct"] is True


@pytest.mark.parametrize("fault", [_altered_token, _stale_state])
def test_broken_timed_path_run_is_not_correct(root, capsys, monkeypatch,
                                              fault):
    """A whole run (chip check skipped) with the program broken
    underneath reports ``correct: false`` and the number beside its
    limit."""
    fault(monkeypatch)
    rc = run.main(["--workload", "tiny-chat", "--seed", "5", "--seconds",
                   "2", "--trace", "0"], require_tpu=False, root=root)
    res = last_json(capsys.readouterr().out)
    assert rc == 0 and res["correct"] is False
    assert res["checks"][NUMBER]["value"] > 10 * LIMIT
    assert res["checks"][NUMBER]["limit"] == LIMIT
