"""Arithmetic shared by the metric readers in bench/metrics/. Each reader
takes the run record a driver returns and gives a number, or None where
the record holds nothing to read (the metric is then left out)."""
from __future__ import annotations

from typing import Dict, Optional

from . import peaks, work
from .stats import MISSING, percentile


def kernel_roofline_pct(rec: Dict) -> Optional[float]:
    """Least time for the window's useful work over the summed device time
    of the kernel's events, in percent."""
    t = rec.get("trace")
    if not t or t["kernel_s"] <= 0 or not rec.get("symbols"):
        return None
    pk = peaks.peaks(rec["device"]["kind"])
    least, _ = work.least_time_s(
        rec["topology"], rec["backend"], rec["symbols"],
        rec["launch_rows"], peaks.compute_peak(rec["device"]["kind"],
                                               rec["backend"]),
        pk["hbm_bytes_per_s"])
    return 100.0 * least / t["kernel_s"]


def mfu_pct(rec: Dict) -> Optional[float]:
    """Useful operations per second of the whole window over the peak of
    the chips it used, in percent."""
    if not rec.get("symbols") or not rec.get("window_s"):
        return None
    peak = peaks.compute_peak(rec["device"]["kind"], rec["backend"])
    rate = rec["symbols"] / rec["window_s"]
    return (100.0 * rate * work.flop_per_symbol(rec["topology"])
            / (peak * rec["device"]["count"]))


def device_idle_pct(rec: Dict) -> Optional[float]:
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def glue_share_pct(rec: Dict) -> Optional[float]:
    t = rec.get("trace")
    if not t or t["kernel_s"] + t["glue_s"] <= 0:
        return None
    return 100.0 * t["glue_s"] / (t["glue_s"] + t["kernel_s"])


def latency_ms(rec: Dict, key: str, q: float) -> Optional[float]:
    """q-th percentile of rec[key] (seconds; MISSING counts as later than
    all), in ms. A percentile that lands on a missing answer reads as the
    time from its due time to the end of the wait, `rec['missing_s']`."""
    v = rec.get(key)
    if not v:
        return None
    p = percentile(v, q)
    if p == MISSING:
        p = rec["missing_s"]
    return 1e3 * p
