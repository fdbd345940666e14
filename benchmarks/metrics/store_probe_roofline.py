"""store kernels: the residency probe's share of its HBM roofline.

With a Store every wave first asks which of its keys the table holds
(``probe_exists_fused``, ``jit_probe_exists_fused`` in a capture). Least
time = for each lane that carried an item the ``ways`` slots of its group
read (80 B of state each, as ``decide_roofline`` counts a slot), its key
and group in and one answer out, over the chip's peak HBM bandwidth.
Measured time = the probe launches between the traced run's two scrapes
(``gubernator_engine_wave_programs{program="probe"}``) times the mean
device time of one execution in the trace. Padding lanes need nothing:
the program runs at the engine's full width, which is the program's way
and not the algorithm's need. Bound: hbm. A program without the counter
(the parent) or a trace without the program gives nothing.
"""

from benchmarks import roofline

SLOT_BYTES = 80  # fused layout: 10 int64 columns of state
LANE_BYTES = 8 + 8 + 4 + 1  # key hash hi/lo and group in, one flag out
PROGRAM = "probe_exists"
LAUNCHES = 'gubernator_engine_wave_programs{program="probe"}'


def read(ctx):
    got = ctx.programs(PROGRAM)
    launches = ctx.delta(LAUNCHES)
    if got is None or not ctx.items_answered or not launches:
        return None
    events, secs = got
    lane_bytes = ctx.table["ways"] * SLOT_BYTES + LANE_BYTES
    least_s = ctx.items_answered * lane_bytes / roofline.peaks(
        ctx.device["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (launches * secs / events)
