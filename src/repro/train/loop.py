"""Fault-tolerant training loop.

Restart semantics: on entry the loop restores the newest committed checkpoint
(if any) and resumes from its step; the data pipeline is stateless-indexable
so the token stream realigns exactly. SIGTERM (preemption) triggers a final
synchronous checkpoint before exit. Straggler steps are flagged by the
StepMonitor; the hook logs (in a fleet deployment it would drain the host).

The host pulls batch i+1 while the device runs step i (a one-batch lookahead
on the calling thread), so a step takes about max(device step, pull) rather
than their sum. A batch is pulled only for a step that will run.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint import CheckpointManager
from repro.runtime import PreemptionGuard, StepMonitor


def train_loop(
    *,
    step_fn: Callable,
    state,
    batches: Iterable[Dict[str, np.ndarray]],
    total_steps: int,
    ckpt: Optional[CheckpointManager] = None,
    ckpt_every: int = 100,
    log_every: int = 10,
    monitor: Optional[StepMonitor] = None,
    guard: Optional[PreemptionGuard] = None,
    log_fn: Callable[[str], None] = print,
):
    """Runs to total_steps (resuming if a checkpoint exists). Returns
    (state, number of steps completed, the restored ones included).

    Each step dispatches ``step_fn`` on the batch in hand, then pulls the
    next batch while the device runs (if another step will run), then waits
    on the step's loss. The step's checkpoint and the guard check follow the
    wait, before the next dispatch. An error raised by that pull
    (``StopIteration`` included) is held and raised after them; a guard exit
    drops it. ``StepMonitor`` times a step from its dispatch to the wait's
    return and counts the pulls that finished while the device still ran.

    Host spans, which a profiler session records (a no-op otherwise):
    ``repro.train.step`` encloses a step's call, the pull and the wait;
    ``repro.train.wait`` the wait alone."""
    start_step = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        state, start_step = ckpt.restore(state)
        log_fn(f"[resume] restored checkpoint at step {start_step}")

    monitor = monitor or StepMonitor()
    it = iter(batches)
    # fast-forward the (stateless) stream
    for _ in range(start_step):
        next(it)

    done = start_step
    batch = next(it) if start_step < total_steps else None
    for step in range(start_step, total_steps):
        pull_error = None
        t0 = time.perf_counter()
        with TraceAnnotation("repro.train.step"):
            state, metrics = step_fn(state, batch)
            leaf = (metrics["total_loss"] if "total_loss" in metrics
                    else jax.tree.leaves(metrics)[0])
            if step + 1 < total_steps:
                try:
                    batch = next(it)
                except Exception as e:  # raised after this step's checks
                    pull_error = e
                is_ready = getattr(leaf, "is_ready", None)
                monitor.record_lookahead(
                    hidden=is_ready is not None and not is_ready())
            with TraceAnnotation("repro.train.wait"):
                jax.block_until_ready(leaf)
        dt = time.perf_counter() - t0
        straggler = monitor.record(step, dt)
        if straggler:
            log_fn(f"[straggler] step {step} took {dt * 1e3:.1f} ms "
                   f"(ewma {monitor.snapshot()['ewma_s'] * 1e3:.1f} ms)")
        if log_every and step % log_every == 0:
            loss = float(metrics.get("total_loss", metrics.get("loss", np.nan)))
            hidden = monitor.snapshot()["lookahead_hidden_share"]
            log_fn(f"step {step:5d} loss {loss:8.4f} dt {dt * 1e3:7.1f} ms "
                   f"pull hidden {hidden:6.1%}")
        done = step + 1
        if ckpt is not None and (done % ckpt_every == 0 or done == total_steps):
            ckpt.save_async(state, done)
        if guard is not None and guard.should_exit:
            log_fn(f"[preempt] SIGTERM at step {done}; checkpointing and exiting")
            if ckpt is not None:
                ckpt.wait()
                ckpt.save(state, done)
            return state, done
        if pull_error is not None:
            raise pull_error
    if ckpt is not None:
        ckpt.wait()
    return state, done
