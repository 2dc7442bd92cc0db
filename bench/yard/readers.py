"""Shared arithmetic of the per-layer metric readers in ``bench/metrics/``.

Each reader takes the run's context (the trace summary, the window's step
count and seconds, the cell's required work, the chip's peaks and the
sampler's host times) and returns its number, or ``None`` where the run
holds nothing to read: a share of a roofline or a peak is never 0 for want
of a reading.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, Optional

from yard import work


def sampler_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Median host time of one batch pull and its host-to-device put."""
    s = ctx["sample_s"]
    return statistics.median(s) * 1e3 if s else None


def idle_share(ctx: Dict[str, Any]) -> Optional[float]:
    """Per cent of the traced window in which no operation ran on the device
    (averaged over the chips)."""
    t = ctx["summary"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def gas_roofline(ctx: Dict[str, Any]) -> Optional[float]:
    """Least time of the FAST-GAS reduce's required bytes at peak HBM
    bandwidth, over the kernel's measured device time, in per cent."""
    t, w = ctx["summary"], ctx["window"]
    if t["kernel_s"] <= 0:
        return None
    least = (w["steps"] * ctx["cell"].work["gas_bytes"]
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / t["kernel_s"]


def mfu(ctx: Dict[str, Any]) -> Optional[float]:
    """Least time of a step's required work (operations at peak FLOP/s or
    bytes at peak HBM bandwidth, whichever is longer) over the measured
    time per step, in per cent."""
    w = ctx["window"]
    if not w["steps"]:
        return None
    least, _ = work.least_time(ctx["cell"].work, ctx["peaks"])
    return 100.0 * least / (w["elapsed_s"] / w["steps"])
