"""Fault-tolerant training runtime: checkpoint/restart, failure injection,
straggler mitigation.

``run_training`` wraps the step function with:
  * periodic step-atomic checkpoints (async),
  * automatic restart from the last committed step on any step failure
    (bounded retries) — the deterministic data pipeline replays the stream,
  * a straggler monitor: when a step exceeds ``straggler_factor`` × the
    rolling median, the Online Load Balancer input is perturbed to demote the
    slow lane from forwarder duty (lane-level mitigation, DESIGN.md §2) and
    the event is logged.  On a real pod the demotion feeds the next step's
    balancer assignment; here the hook is observable state + logs.
  * optional failure injection (probability per step) to exercise the path.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint import checkpointer


class InjectedFailure(RuntimeError):
    pass


@dataclasses.dataclass
class RunConfig:
    total_steps: int
    ckpt_dir: str
    ckpt_every: int = 50                   # 0: never checkpoint
    max_restarts: int = 3
    straggler_factor: float = 2.0
    straggler_window: int = 16
    inject_failure_at: int | None = None   # deterministic injection (tests)
    # called as on_restart(step, restored) after every rewind: ``restored``
    # is True when (params, opt) were reloaded from a committed checkpoint
    # (the step function must re-base any state keyed to the step index or
    # to the parameter layout — e.g. the adaptive expert placement, whose
    # table must match the restored weights' layout), False when the run
    # restarts from scratch with the in-memory params kept.
    on_restart: Callable[[int, bool], None] | None = None


@dataclasses.dataclass
class RunState:
    restarts: int = 0
    straggler_events: int = 0
    demoted_lanes: tuple = ()
    steps_run: int = 0


def run_training(step_fn: Callable, init_state: tuple, batch_at: Callable,
                 cfg: RunConfig, log: Callable = print) -> tuple:
    """step_fn(params, opt, batch) -> (params, opt, metrics).

    Returns ((params, opt), RunState).  Restarts re-load the latest committed
    checkpoint and replay the deterministic stream from that step.
    """
    params, opt = init_state
    run = RunState()
    start = checkpointer.latest_step(cfg.ckpt_dir)
    step = 0
    if start is not None:
        (params, opt), _ = _restore(cfg.ckpt_dir, (params, opt))
        step = start
        log(f"[ft] resumed from committed step {step}")
        if cfg.on_restart is not None:
            cfg.on_restart(step, True)
    pending = None
    times: deque = deque(maxlen=cfg.straggler_window)
    injected = False

    while step < cfg.total_steps:
        try:
            if cfg.inject_failure_at is not None and step == cfg.inject_failure_at \
                    and not injected and run.restarts == 0:
                injected = True
                raise InjectedFailure(f"injected failure at step {step}")
            t0 = time.perf_counter()
            params, opt, metrics = step_fn(params, opt, batch_at(step))
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            # ---- straggler monitor ----------------------------------------
            # a step may declare itself a timing fence (e.g. the first step
            # after an adaptive-placement re-jit): its dt is compile time,
            # not lane health — skip the check and restart the window
            if metrics.pop("straggler_fence", False):
                times.clear()
            else:
                if len(times) >= max(4, cfg.straggler_window // 2):
                    med = float(np.median(times))
                    if dt > cfg.straggler_factor * med:
                        run.straggler_events += 1
                        lane = run.straggler_events % 16
                        run.demoted_lanes = tuple(set(run.demoted_lanes) | {lane})
                        log(f"[ft] straggler: step {step} took {dt:.3f}s "
                            f"(median {med:.3f}s) — demoting lane {lane} from "
                            f"forwarder duty for the next plan")
                times.append(dt)
            step += 1
            run.steps_run += 1
            if cfg.ckpt_every and (step % cfg.ckpt_every == 0
                                   or step == cfg.total_steps):
                checkpointer.wait(pending)
                pending = checkpointer.save(cfg.ckpt_dir, (params, opt), step)
        except Exception as e:  # noqa: BLE001 — restart on ANY step failure
            if run.restarts >= cfg.max_restarts:
                raise
            run.restarts += 1
            log(f"[ft] step {step} failed ({type(e).__name__}: {e}); "
                f"restart {run.restarts}/{cfg.max_restarts}")
            checkpointer.wait(pending)
            pending = None
            committed = checkpointer.latest_step(cfg.ckpt_dir)
            if committed is None:
                step = 0
                log("[ft] no committed checkpoint — restarting from scratch")
                if cfg.on_restart is not None:
                    cfg.on_restart(0, False)
            else:
                (params, opt), _ = _restore(cfg.ckpt_dir, (params, opt))
                step = committed
                log(f"[ft] restored step {step}")
                if cfg.on_restart is not None:
                    cfg.on_restart(step, True)
    checkpointer.wait(pending)
    return (params, opt), run


def _restore(path, like):
    return checkpointer.restore(path, like)
