"""Arithmetic on samples: percentiles, rates, spreads. No I/O."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between closest
    ranks; an instance that never completed is passed in as ``math.inf``
    and so sits in the tail."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or math.isinf(xs[hi]):
        return float(xs[hi] if k > lo else xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver takes it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def end_to_end(gen: dict) -> dict:
    """Every end-to-end reading the generator's report supports, over all
    the work of the whole window. ``rows`` carry monotonic seconds."""
    start, end = gen["window_start"], gen["window_end"]
    seconds = end - start
    rows = gen["rows"]
    out = {}
    done_in_window = [r for r in rows if "done" in r and start <= r["done"] < end]
    out["instances_per_s"] = len(done_in_window) / seconds
    due = [r for r in rows if "due" in r and start <= r["due"] < end]
    if due:
        # an instance that is not complete when the grace period ends has
        # waited at least that long (and fails the run); a made-up report
        # without the stamp keeps it at infinity
        grace_end = gen.get("grace_end", math.inf)
        complete = [(r.get("done", grace_end) - r["due"]) * 1e3 for r in due]
        ack = [(r.get("acked", grace_end) - r["due"]) * 1e3 for r in due]
        out["complete_p95_ms"] = percentile(complete, 95)
        out["complete_p50_ms"] = percentile(complete, 50)
        out["create_ack_p95_ms"] = percentile(ack, 95)
        late = [(r["sent"] - r["due"]) * 1e3 for r in due]
        out["gen_late_p95_ms"] = percentile(late, 95)
        out["gen_late_p50_ms"] = percentile(late, 50)
    for name, t in (("in_flight_at_start", start), ("in_flight_at_end", end)):
        out[name] = sum(1 for r in rows if r["sent"] < t and not r.get("done", math.inf) < t)
    counted = due if due else [r for r in rows if start <= r["sent"] < end]
    out["attempted"] = len(counted)
    out["failed"] = sum(1 for r in counted if "done" not in r)
    return out
