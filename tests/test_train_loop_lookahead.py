"""``train_loop``'s one-batch lookahead: the host pulls batch i+1 between
step i's dispatch and its wait.

The tests log events instead of timing them: the batch iterator logs
``pull k``, the step function ``dispatch k``, and the loss leaf it returns
logs ``wait k`` when the loop blocks on it. A step's checkpoint logs
``ckpt n`` with n the steps completed.
"""

from __future__ import annotations

import itertools

import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager
from repro.runtime import PreemptionGuard, StepMonitor
from repro.train import train_loop


class _Leaf:
    """A loss leaf: ``is_ready`` is False until the loop waits on it, unless
    it starts ready."""

    def __init__(self, log, k, ready=False):
        self.log, self.k, self.ready = log, k, ready

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.log.append(f"wait {self.k}")
        self.ready = True
        return self

    def __float__(self):
        return float(self.k)


class _LoggedCkpt(CheckpointManager):
    def __init__(self, path, log):
        super().__init__(path)
        self.log = log

    def save_async(self, state, step):
        self.log.append(f"ckpt {step}")
        super().save_async(state, step)


def _pulls(log, fail_at=None, error=None):
    """Batch k is k; pull number ``fail_at`` (counting from 1) raises
    ``error``, or ends the stream when ``error`` is StopIteration."""
    for k in itertools.count():
        if fail_at is not None and k + 1 == fail_at:
            if error is StopIteration:
                return
            raise error("pull failed")
        log.append(f"pull {k}")
        yield k


def _step(log, received, guard=None, fire_at=None):
    def step_fn(state, batch):
        n = len(received)
        log.append(f"dispatch {n}")
        received.append(batch)
        if guard is not None and n + 1 == fire_at:
            guard.trigger()
        return {"w": state["w"] + 1.0}, {"total_loss": _Leaf(log, n)}
    return step_fn


def _state():
    return {"w": jnp.zeros(2)}


def test_pull_overlaps_the_step_in_order():
    log, received = [], []
    _, done = train_loop(step_fn=_step(log, received), state=_state(),
                         batches=_pulls(log), total_steps=3, log_every=0)
    assert done == 3
    assert log == ["pull 0", "dispatch 0", "pull 1", "wait 0",
                   "dispatch 1", "pull 2", "wait 1", "dispatch 2", "wait 2"]
    assert received == [0, 1, 2]


def test_resumed_run_pulls_only_its_steps(tmp_path):
    ck = CheckpointManager(str(tmp_path))
    log, received = [], []
    train_loop(step_fn=_step(log, received), state=_state(),
               batches=_pulls(log), total_steps=4, ckpt=ck, ckpt_every=2,
               log_every=0)
    assert [e for e in log if e.startswith("pull")] == [
        f"pull {k}" for k in range(4)]
    log.clear()
    received.clear()
    _, done = train_loop(step_fn=_step(log, received), state=_state(),
                         batches=_pulls(log), total_steps=7, ckpt=ck,
                         ckpt_every=2, log_every=0)
    assert done == 7
    # the fast-forward draws batches 0-3, then exactly one pull a step
    assert [e for e in log if e.startswith("pull")] == [
        f"pull {k}" for k in range(7)]
    assert received == [4, 5, 6]


@pytest.mark.parametrize("n", [1, 3])
def test_guard_exit_pulls_at_most_one_batch_past(tmp_path, n):
    ck = CheckpointManager(str(tmp_path))
    guard = PreemptionGuard(install=False)
    log, received = [], []
    _, done = train_loop(step_fn=_step(log, received, guard, fire_at=n),
                         state=_state(), batches=_pulls(log),
                         total_steps=100, ckpt=ck, ckpt_every=1000,
                         guard=guard, log_every=0)
    assert done == n and ck.latest_step() == n
    assert sum(e.startswith("pull") for e in log) <= n + 1
    assert received == list(range(n))


@pytest.mark.parametrize("error", [ValueError, StopIteration])
def test_pull_error_raised_after_the_steps_checkpoint(tmp_path, error):
    k = 3
    log, received = [], []
    ck = _LoggedCkpt(str(tmp_path), log)
    with pytest.raises(error):
        train_loop(step_fn=_step(log, received), state=_state(),
                   batches=_pulls(log, fail_at=k, error=error),
                   total_steps=10, ckpt=ck, ckpt_every=1, log_every=0)
    ck.wait()
    assert ck.latest_step() == k - 1
    # pull k fails while step k-1 runs; the error waits for its checkpoint
    assert log[-3:] == [f"dispatch {k - 2}", f"wait {k - 2}", f"ckpt {k - 1}"]
    assert received == list(range(k - 1))


def test_guard_exit_drops_the_pull_error(tmp_path):
    k = 3
    guard = PreemptionGuard(install=False)
    log, received = [], []
    ck = CheckpointManager(str(tmp_path))
    _, done = train_loop(
        step_fn=_step(log, received, guard, fire_at=k - 1), state=_state(),
        batches=_pulls(log, fail_at=k, error=ValueError),
        total_steps=10, ckpt=ck, ckpt_every=1000, guard=guard, log_every=0)
    assert done == k - 1 and ck.latest_step() == k - 1


@pytest.mark.parametrize("leaf,hidden", [
    (lambda log, n: _Leaf(log, n), 3),              # running at the pull's end
    (lambda log, n: _Leaf(log, n, ready=True), 0),  # done before it
    (lambda log, n: float(n), 0),                   # no is_ready
], ids=["running", "ready", "float"])
def test_monitor_counts_hidden_pulls(leaf, hidden):
    log, lines = [], []

    def step_fn(state, batch):
        return state, {"total_loss": leaf(log, batch)}

    monitor = StepMonitor()
    _, done = train_loop(step_fn=step_fn, state=_state(), batches=_pulls(log),
                         total_steps=4, monitor=monitor, log_every=1,
                         log_fn=lines.append)
    snap = monitor.snapshot()
    assert done == 4 and snap["steps"] == 4
    # no pull after the last step
    assert snap["lookahead_pulls"] == 3
    assert snap["lookahead_hidden"] == hidden
    assert snap["lookahead_hidden_share"] == pytest.approx(hidden / 3)
    assert f"pull hidden {hidden / 3:6.1%}" in lines[-1]


def test_monitor_share_is_zero_without_pulls():
    snap = StepMonitor().snapshot()
    assert (snap["lookahead_pulls"], snap["lookahead_hidden"],
            snap["lookahead_hidden_share"]) == (0, 0, 0.0)
