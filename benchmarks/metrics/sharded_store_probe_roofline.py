"""store kernels, owner-sharded table: the residency probe's share of its
roofline.

With a Store on the four-chip daemon every wave first asks which of its
keys the table holds, in one SPMD program (``parallel/mesh.py``
``_sharded_probe_exists``, ``jit_probe_exists_fn`` in a capture): each
chip probes the lanes whose group it owns against its slice and a psum
hands every chip every lane's answer. Reckoned a chip, as
``sharded_decide_roofline`` is. Least time = the larger of two needs of
one chip: its share of the lanes that carried an item (items / chips)
times the ``ways`` slots of a group read (80 B of state each), its key
and group in and one answer out, over its peak HBM bandwidth; and the
answers it has to receive from the other chips (lanes with an item x 4 B
x (chips - 1) / chips) over the ICI peak. Measured time = the probe
launches between the traced run's two scrapes
(``gubernator_engine_wave_programs{program="probe"}``) times the device
time of one execution, averaged over the chips. Padding lanes need
nothing. Bound: hbm (661 B a lane on its owner against 3 B over ICI). A
program without the counter (the parent) or a trace without the program
gives nothing.
"""

from benchmarks import roofline

SLOT_BYTES = 80  # fused layout: 10 int64 columns of state
LANE_BYTES = 8 + 8 + 4 + 1  # key hash hi/lo and group in, one flag out
ANSWER_BYTES = 4  # the psum's word a lane
PROGRAM = "probe_exists"
LAUNCHES = 'gubernator_engine_wave_programs{program="probe"}'


def least_seconds(lanes_with_item: float, chips: int, ways: int,
                  device_kind: str) -> float:
    """The larger of one chip's HBM time for its share of the lanes and
    the time to receive the other chips' answers over ICI."""
    pk = roofline.peaks(device_kind)
    hbm = ((lanes_with_item / chips) * (ways * SLOT_BYTES + LANE_BYTES)
           / pk["hbm_bytes_per_s"])
    ici = (lanes_with_item * ANSWER_BYTES * (chips - 1) / chips
           / (pk["ici_bits_per_s"] / 8.0))
    return max(hbm, ici)


def read(ctx):
    got = ctx.programs(PROGRAM)
    launches = ctx.delta(LAUNCHES)
    tier = ctx.table.get("tiers", {}).get("sharded")
    if got is None or not ctx.items_answered or not launches or tier is None:
        return None
    events, secs = got
    least_s = least_seconds(ctx.items_answered, ctx.device["device_count"],
                            tier["ways"], ctx.device["device_kind"])
    return 100.0 * least_s / (launches * secs / events)
