"""store kernels, owner-sharded table: the row gather's share of its
roofline.

With a Store on the four-chip daemon every wave, after its decide, reads
back the rows it touched in one SPMD program (``parallel/mesh.py``
``_sharded_gather_rows``, ``jit_gather_rows_fn`` in a capture): each chip
reads the lanes whose slot lies in its slice of the table and a psum hands
every chip every lane's row. Reckoned a chip, as
``sharded_decide_roofline`` is. Least time = the larger of two needs of
one chip: its share of the lanes that carried an item (items / chips)
times one slot's 80 B of state read, its slot index in and the row out,
over its peak HBM bandwidth; and the rows it has to receive from the
other chips (lanes with an item x 80 B x (chips - 1) / chips) over the
ICI peak. Measured time = the gather launches between the traced run's
two scrapes (``gubernator_engine_wave_programs{program="gather_rows"}``)
times the device time of one execution, averaged over the chips (the
event holds the wait for the slowest chip's psum). Padding lanes need
nothing. Bound: ici (a lane's row is 80 B on three chips of four against
168 B of HBM on one: 0.3 ns against 0.05 ns an item). A program without
the counter (the parent) or a trace without the program gives nothing.
"""

from benchmarks import roofline

SLOT_BYTES = 80  # fused layout: 10 int64 columns of state
LANE_BYTES = SLOT_BYTES + 8 + SLOT_BYTES  # the slot read, its index in, the row out
PROGRAM = "gather_rows"
LAUNCHES = 'gubernator_engine_wave_programs{program="gather_rows"}'


def least_seconds(lanes_with_item: float, chips: int, device_kind: str) -> float:
    """The larger of one chip's HBM time for its share of the lanes and
    the time to receive the other chips' rows over ICI."""
    pk = roofline.peaks(device_kind)
    hbm = (lanes_with_item / chips) * LANE_BYTES / pk["hbm_bytes_per_s"]
    ici = (lanes_with_item * SLOT_BYTES * (chips - 1) / chips
           / (pk["ici_bits_per_s"] / 8.0))
    return max(hbm, ici)


def read(ctx):
    got = ctx.programs(PROGRAM)
    launches = ctx.delta(LAUNCHES)
    if got is None or not ctx.items_answered or not launches:
        return None
    events, secs = got
    least_s = least_seconds(ctx.items_answered, ctx.device["device_count"],
                            ctx.device["device_kind"])
    return 100.0 * least_s / (launches * secs / events)
