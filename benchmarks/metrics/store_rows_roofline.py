"""store kernels: the row gather's share of its HBM roofline.

With a Store every wave, after its decide, reads back the rows it touched
for the write-behind (``gather_rows_fused``, ``jit_gather_rows_fused`` in
a capture). Least time = for each lane that carried an item one slot's
80 B of state read, its slot index in and the row out, over the chip's
peak HBM bandwidth. Measured time = the gather launches between the
traced run's two scrapes
(``gubernator_engine_wave_programs{program="gather_rows"}``) times the
mean device time of one execution in the trace. Padding lanes need
nothing. Bound: hbm. A program without the counter (the parent) or a
trace without the program gives nothing.
"""

from benchmarks import roofline

SLOT_BYTES = 80  # fused layout: 10 int64 columns of state
LANE_BYTES = SLOT_BYTES + 8 + SLOT_BYTES  # the slot read, its index in, the row out
PROGRAM = "gather_rows"
LAUNCHES = 'gubernator_engine_wave_programs{program="gather_rows"}'


def read(ctx):
    got = ctx.programs(PROGRAM)
    launches = ctx.delta(LAUNCHES)
    if got is None or not ctx.items_answered or not launches:
        return None
    events, secs = got
    least_s = ctx.items_answered * LANE_BYTES / roofline.peaks(
        ctx.device["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (launches * secs / events)
