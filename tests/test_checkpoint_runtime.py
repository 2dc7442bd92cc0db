"""Checkpointing (atomic/async/retention/restore) + runtime health machinery
+ fault-tolerant loop semantics (resume, preemption)."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.runtime import Heartbeat, PreemptionGuard, StepMonitor
from repro.train import train_loop


def _state(x=0.0):
    return {"params": {"w": jnp.full(4, x)}, "step": jnp.asarray(0, jnp.int32),
            "nested": {"a": jnp.arange(6).reshape(2, 3)}}


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    st = _state(3.5)
    mgr.save(st, 10)
    restored, step = mgr.restore(st)
    assert step == 10
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 3.5)
    np.testing.assert_array_equal(np.asarray(restored["nested"]["a"]),
                                  np.arange(6).reshape(2, 3))


def test_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(_state(float(s)), s)
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4
    restored, _ = mgr.restore(_state())
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 4.0)


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_async(_state(1.0), 5)
    mgr.wait()
    assert mgr.latest_step() == 5


def test_atomicity_tmp_dirs_ignored(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(_state(1.0), 1)
    os.makedirs(tmp_path / ".tmp_step_2")          # simulated crashed save
    (tmp_path / ".tmp_step_2" / "garbage").write_text("x")
    os.makedirs(tmp_path / "step_3")               # no manifest → incomplete
    assert mgr.steps() == [1]


def test_step_monitor_flags_stragglers():
    mon = StepMonitor(z_threshold=4.0)
    for i in range(20):
        assert not mon.record(i, 0.100 + 0.001 * (i % 3))
    assert mon.record(20, 1.0)      # 10× outlier
    assert mon.flagged == 1
    assert not mon.record(21, 0.101)


def test_step_monitor_constant_stream_tolerates_jitter():
    """MAD = 0 degeneracy: a window of IDENTICAL step times used to floor
    sigma at 1e-6, so a nanosecond of jitter z-scored in the thousands and
    flagged a straggler. With the median-fraction floor, sub-5%-of-median
    jitter must flag nothing."""
    mon = StepMonitor(z_threshold=4.0)
    for i in range(32):
        assert not mon.record(i, 0.100)          # perfectly constant window
    # nanosecond-to-microsecond jitter: well inside 5% of the median
    for i, jit in enumerate((1e-9, 5e-8, 1e-6, 2e-4)):
        assert not mon.record(32 + i, 0.100 + jit), f"flagged jitter {jit}"
    assert mon.flagged == 0
    # a REAL straggler on the constant stream still flags: 4·(0.05·med) above
    assert mon.record(100, 0.100 + 4.5 * 0.05 * 0.100)
    assert mon.flagged == 1


def test_heartbeat(tmp_path):
    path = str(tmp_path / "hb")
    hb = Heartbeat(path, interval_s=0.05)
    hb.start()
    time.sleep(0.12)
    hb.stop()
    assert Heartbeat.is_alive(path, stale_after_s=5.0)
    assert not Heartbeat.is_alive(str(tmp_path / "missing"))


def _quadratic_step(state, batch):
    w = state["params"]["w"]
    g = 2 * (w - batch["target"])
    w = w - 0.2 * g
    loss = jnp.sum((w - batch["target"]) ** 2)
    return ({"params": {"w": w}, "step": state["step"] + 1},
            {"total_loss": loss})


class _Batches:
    def __iter__(self):
        while True:
            yield {"target": jnp.asarray([1.0, 2.0])}


def test_train_loop_resume_exactness(tmp_path):
    """Interrupted run + resumed run == uninterrupted run (restart semantics)."""
    ck1 = CheckpointManager(str(tmp_path / "a"))
    st0 = {"params": {"w": jnp.zeros(2)}, "step": jnp.asarray(0, jnp.int32)}
    # uninterrupted 20 steps
    full, _ = train_loop(step_fn=_quadratic_step, state=st0, batches=_Batches(),
                         total_steps=20, ckpt=None, log_every=0)
    # interrupted at 10 (ckpt every 5), then resumed to 20
    part, n = train_loop(step_fn=_quadratic_step, state=st0, batches=_Batches(),
                         total_steps=10, ckpt=ck1, ckpt_every=5, log_every=0)
    resumed, n2 = train_loop(step_fn=_quadratic_step, state=st0, batches=_Batches(),
                             total_steps=20, ckpt=ck1, ckpt_every=5, log_every=0)
    assert n == 10 and n2 == 20
    np.testing.assert_allclose(np.asarray(resumed["params"]["w"]),
                               np.asarray(full["params"]["w"]), atol=1e-6)


def test_train_loop_resumed_at_its_end_returns_steps_done(tmp_path):
    """Resumed at or past total_steps, the loop runs no step and returns the
    number of steps completed, not one more."""
    ck = CheckpointManager(str(tmp_path))
    st0 = {"params": {"w": jnp.zeros(2)}, "step": jnp.asarray(0, jnp.int32)}
    _, n = train_loop(step_fn=_quadratic_step, state=st0, batches=_Batches(),
                      total_steps=4, ckpt=ck, ckpt_every=2, log_every=0)
    assert n == 4

    def never(state, batch):
        raise AssertionError("no step is left to run")

    for total in (4, 3):
        _, n = train_loop(step_fn=never, state=st0, batches=_Batches(),
                          total_steps=total, ckpt=ck, log_every=0)
        assert n == 4


def test_train_loop_preemption_checkpoints(tmp_path):
    ck = CheckpointManager(str(tmp_path))
    guard = PreemptionGuard(install=False)
    st0 = {"params": {"w": jnp.zeros(2)}, "step": jnp.asarray(0, jnp.int32)}

    calls = {"n": 0}
    def step(state, batch):
        calls["n"] += 1
        if calls["n"] == 3:
            guard.trigger()          # simulated SIGTERM mid-run
        return _quadratic_step(state, batch)

    _, n = train_loop(step_fn=step, state=st0, batches=_Batches(),
                      total_steps=100, ckpt=ck, ckpt_every=1000,
                      guard=guard, log_every=0)
    assert n == 3
    assert ck.latest_step() == 3     # preemption forced a final checkpoint
