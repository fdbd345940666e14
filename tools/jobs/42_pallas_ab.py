# Pallas-vs-XLA decide backend A/B (ISSUE 16): the same seeded Zipf
# trace through GUBER_KERNEL=xla and GUBER_KERNEL=pallas cells at
# identical geometry/layout, for both pallas-eligible layouts. On a
# TPU the pallas cells run the mosaic lowering (the fused one-HBM-pass
# kernel this job exists to measure); each cell's raw row and the
# pallas/xla ratio row are ledgered as they land.
import os
import sys, json
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import bench

r = None
for layout in ("fused", "narrow"):
    row = bench.bench_kernel_ab(sizes=("kernel",), layout=layout)
    r = r or row
print("RESULT " + json.dumps(r))
