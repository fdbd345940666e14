"""decide kernel: share of its HBM roofline.

Least time = bytes the algorithm needs for the lanes that carried an item
(roofline.decide_bytes) over the chip's peak HBM bandwidth. Measured time
= the decide program's dispatches times the device time of one. Items and
dispatches (``gubernator_engine_flush_waves_sum``: a 1,000-item call with
one key 65 times takes ~70) are counted over one span, between the traced
run's two scrapes. The time of one dispatch does not depend on the span:
the mean over the decide program's executions in the trace. The server
answers about half as fast while it is traced, so items of one span over
device seconds of the other would not do. Bound: hbm.
"""

from benchmarks import roofline

SLOT_BYTES = 80  # fused layout: 10 int64 columns


def read(ctx):
    got = ctx.programs("decide")
    dispatches = ctx.delta("gubernator_engine_flush_waves_sum")
    if got is None or not ctx.items_answered or not dispatches:
        return None
    events, decide_s = got
    least_s = roofline.decide_least_seconds(
        ctx.items_answered, SLOT_BYTES, ctx.device["device_kind"],
        ctx.table["ways"])
    return 100.0 * least_s / (dispatches * decide_s / events)
