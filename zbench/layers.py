"""Per-layer metrics: each is a data file ``layer_metrics/<name>.json``
that declares its source. The kinds a file can name:

  span_pair      median (or mean, p95) of stage ``to`` - stage ``from`` over
                 the RecordTracer spans of client commands in the window
  counter_ratio  sum of counters ``num`` over sum of ``den`` (window deltas;
                 a name starting with ``@`` is a count of the window that the
                 harness takes from the re-read log or the generator's
                 report: ``@committed_records``, ``@instances_completed``,
                 ``@log.<value type>.<record type>.<intent>``)
  value          one named reading of the generator (``stats.end_to_end``)
  module         ``read(ctx)`` of ``layer_metrics/<module>.py``: a reduction
                 of its own over what a run leaves behind. ``ctx`` holds
                 ``reader`` (the metric's own file), ``spans``, ``counters``,
                 ``derived``, ``values``, ``gen`` (the generator's report),
                 ``rows`` (partition -> rows of the re-read log),
                 ``window_wall_ms``, ``device``, ``peak``, ``num_vars`` and,
                 in a traced run, ``trace``: ``doc`` (the device planes'
                 events), ``window_ns`` (on the trace's clock), ``wall_ns``
                 (the same window on the wall clock) and ``reduction``
                 (``trace.reduce``)

A reader that finds nothing to read returns None and the metric is left
out of the line."""

from __future__ import annotations

import importlib
import statistics

from zbench import spec, stats


def counters_needed(readers: list) -> set:
    names = set()
    for r in readers:
        if r["kind"] == "counter_ratio":
            names.update(n for n in r["num"] + r["den"] if not n.startswith("@"))
    return names


def read(name: str, reader: dict, ctx: dict):
    kind = reader["kind"]
    if kind == "span_pair":
        deltas = []
        for span in ctx.get("spans", []):
            at = {s["stage"]: s["t_us"] for s in reversed(span["stages"])}
            if reader["from"] in at and reader["to"] in at:
                deltas.append((at[reader["to"]] - at[reader["from"]]) / 1000.0)
        if not deltas:
            return None
        stat = reader.get("stat", "median")
        if stat == "median":
            return statistics.median(deltas)
        if stat == "mean":
            return statistics.fmean(deltas)
        return stats.percentile(deltas, float(stat.lstrip("p")))
    if kind == "counter_ratio":
        values = {**ctx.get("counters", {}), **ctx.get("derived", {})}

        def count(n: str):
            # a kind of record the window's log does not hold was written 0 times
            return values.get(n, 0 if n.startswith("@log.") and "derived" in ctx else None)

        counts = {n: count(n) for n in reader["num"] + reader["den"]}
        if any(v is None for v in counts.values()):
            return None
        den = sum(counts[n] for n in reader["den"])
        if den <= 0:
            return None
        return reader.get("scale", 1.0) * sum(counts[n] for n in reader["num"]) / den
    if kind == "value":
        return ctx.get("values", {}).get(reader["value"])
    if kind == "module":
        mod = importlib.import_module(
            "zbench.layer_metrics." + spec.check_name(reader["module"], "module")
        )
        return mod.read({**ctx, "reader": reader})
    raise ValueError(f"layer metric {name}: unknown kind {kind!r}")
