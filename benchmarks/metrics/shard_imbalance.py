"""engine host stage, owner-sharded tier: how unevenly a window's lanes
fell on the shards.

Max over mean of the per-shard deltas, between the traced run's two
scrapes, of ``gubernator_shard_decisions{shard="n"}``: the lanes the
sharded decide answered on each chip, folded by the engine from every
wave's groups (``runtime/engine.py`` ``_note_shard_decisions``; a count,
the same on a CPU). 1.0 is an even split; a dispatch ends when its
fullest shard ends. A program without the counter, or a window in which
no sharded lane was answered, gives nothing.
"""

SERIES = "gubernator_shard_decisions{shard="


def read(ctx):
    deltas = [ctx.delta(s) for s in ctx.after if s.startswith(SERIES)]
    if len(deltas) < 2 or sum(deltas) <= 0:
        return None
    return max(deltas) * len(deltas) / sum(deltas)
