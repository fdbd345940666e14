# Unified-core mesh scaling (ISSUE 15): the same seeded trace through
# MeshEngine at mesh width 1 and IciEngine's owner-sharded tier at every
# power-of-two width up to the full device count — decisions/s vs chips,
# the measurement the engine unification exists for. On TPU the chips
# are held by THIS process, so every cell runs in-process (the
# fresh-process isolation is for the CPU path); per-cell rows and the
# mesh/single-chip ratio row are ledgered as they land.
import os
import sys, json
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import bench
import jax

widths = [1]
while widths[-1] * 2 <= len(jax.devices()):
    widths.append(widths[-1] * 2)
r = bench.bench_mesh_ab(widths=tuple(widths))
print("RESULT " + json.dumps(r))
