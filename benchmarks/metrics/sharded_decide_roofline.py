"""decide kernel, owner-sharded tier: share of its roofline.

As ``replica_decide_roofline``, for the tier that shards one table over
the chips (``parallel/mesh.py``): one dispatch runs on all the chips at
once, each answers the lanes whose group it owns, and a psum hands every
chip every lane's answer. Least time = the larger of two needs of one
chip: the bytes of its share of the items (items / chips lanes at the
sharded tier's ways, ``roofline.decide_least_seconds``) over its peak HBM
bandwidth, and the answers it has to receive from the other chips (lanes
with an item x ``RESPONSE_COLUMNS`` x 8 B x (chips - 1) / chips) over the
ICI peak. Measured time = dispatches times the device time of one
execution of the sharded decide (``decide_fn``), averaged over the chips.
Items and dispatches are counted between the traced run's two scrapes;
it counts items, so it reads the same work whatever implements the
program. Bound: hbm (at 8 ways a lane needs 816 B of HBM on its owner
against 24 B over ICI: the collective's need is ~1/12 of the table's).
"""

from benchmarks import roofline

SLOT_BYTES = 80  # fused layout: 10 int64 columns


def psum_least_seconds(lanes_with_item: float, chips: int, device_kind: str) -> float:
    received = lanes_with_item * roofline.RESPONSE_COLUMNS * 8 * (chips - 1) / chips
    return received / (roofline.peaks(device_kind)["ici_bits_per_s"] / 8.0)


def read(ctx):
    got = ctx.programs("decide_fn")
    dispatches = ctx.delta("gubernator_engine_flush_waves_sum")
    tier = ctx.table.get("tiers", {}).get("sharded")
    if got is None or not ctx.items_answered or not dispatches or tier is None:
        return None
    events, decide_s = got
    chips, kind = ctx.device["device_count"], ctx.device["device_kind"]
    least_s = max(
        roofline.decide_least_seconds(
            ctx.items_answered / chips, SLOT_BYTES, kind, tier["ways"]),
        psum_least_seconds(ctx.items_answered, chips, kind))
    return 100.0 * least_s / (dispatches * decide_s / events)
