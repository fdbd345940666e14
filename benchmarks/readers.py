"""Per-layer metrics are files: ``metrics/<name>.json`` declares where the
number comes from and the arithmetic, ``metrics/<name>.py`` is a reader of
its own with ``read(ctx)``. A reader that finds nothing to read returns
None and the metric is left out of the line.

Kinds a ``.json`` reader may name:

    metrics_ratio  deltas of ``/metrics`` series over the window:
                   scale * (sum(plus) - sum(minus)) / sum(per)
                   (log2 histograms: ``_sum``/``_count`` only, never a
                   bucket percentile)
    debug_field    a field of ``/debug/device`` after the window, by path
    phase          one of the parent's own clock readings of set-up
    generator      a reading of the load generator's own clock
    trace          a field of the trace reduction (trace_reduce.py)
    trace_op       time of the device programs whose name matches `match`:
                   ``us_per_event`` or ``events``
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass


@dataclass
class Context:
    before: dict  # /metrics a second into the window
    after: dict  # /metrics mid-window, just before the profiler starts
    device: dict  # /debug/device after the window
    phases: dict
    generator: dict
    trace: dict  # None unless the run was traced
    conf: dict
    traffic: dict
    table: dict  # the table's geometry as the server reports it
    items_answered: int  # in calls that returned between the two scrapes
    root: str

    def delta(self, series: str):
        if series not in self.after:
            return None
        return self.after[series] - self.before.get(series, 0.0)

    def delta_sum(self, series_list):
        total = 0.0
        for s in series_list:
            d = self.delta(s)
            if d is None:
                return None
            total += d
        return total

    def programs(self, match: str):
        """(events, seconds) of the device programs matching `match`,
        averaged over the device planes."""
        if not self.trace or not self.trace["devices"]:
            return None
        rx = re.compile(match)
        n = len(self.trace["devices"])
        events = sum(c for d in self.trace["devices"]
                     for name, (c, _) in d["programs"].items() if rx.search(name))
        secs = sum(s for d in self.trace["devices"]
                   for name, (_, s) in d["programs"].items() if rx.search(name))
        return (events / n, secs / n) if events else None


def _metrics_ratio(spec: dict, ctx: Context):
    plus = ctx.delta_sum(spec["plus"])
    minus = ctx.delta_sum(spec.get("minus", []))
    per = ctx.delta_sum(spec["per"])
    if plus is None or minus is None or not per:
        return None
    return float(spec.get("scale", 1.0)) * (plus - minus) / per


def _debug_field(spec: dict, ctx: Context):
    node = ctx.device
    for part in spec["path"].split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return float(node) * float(spec.get("scale", 1.0))


def _trace_op(spec: dict, ctx: Context):
    got = ctx.programs(spec["match"])
    if got is None:
        return None
    events, secs = got
    return events if spec["stat"] == "events" else 1e6 * secs / events


KINDS = {
    "metrics_ratio": _metrics_ratio,
    "debug_field": _debug_field,
    "phase": lambda spec, ctx: ctx.phases.get(spec["phase"]),
    "generator": lambda spec, ctx: ctx.generator.get(spec["field"]),
    "trace": lambda spec, ctx: (ctx.trace or {}).get(spec["field"]),
    "trace_op": _trace_op,
}


def read(path: str, ctx: Context):
    if path.endswith(".py"):
        spec = importlib.util.spec_from_file_location("bench_metric_reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    return KINDS[spec["kind"]](spec, ctx)
