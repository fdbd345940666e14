"""GLOBAL sync, replica tier: how unevenly a window's GLOBAL lanes fell
on the replicas.

Max over mean of the per-device deltas, between the traced run's two
scrapes, of ``gubernator_replica_decisions{device="n"}``: the GLOBAL
lanes the replica tier answered by the home device the host assigned
(``runtime/engine.py`` ``_note_replica_decisions``; a count, the same on
a CPU). 1.0 is an even split: every replica met as often as any other,
which the probes' "eight answers a key, two a replica" rests on. A
program without the counter, or a window in which no replica lane was
answered, gives nothing.
"""

SERIES = "gubernator_replica_decisions{device="


def read(ctx):
    deltas = [ctx.delta(s) for s in ctx.after if s.startswith(SERIES)]
    if len(deltas) < 2 or sum(deltas) <= 0:
        return None
    return max(deltas) * len(deltas) / sum(deltas)
