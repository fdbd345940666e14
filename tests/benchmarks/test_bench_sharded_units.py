"""The two per-layer readers PR 32 adds, `sharded_decide_roofline` and
`shard_imbalance`, against synthetic traces and scrapes: values by hand,
nothing where the counter or the trace is absent (the parent commit's
program), the roofline under 100. The helpers are `test_bench_units.py`'s;
the cases live here because a PR that changes the program may only add files
to the benchmark."""

import pytest

from benchmarks import readers
from test_bench_units import FOUR, GLOBAL_TABLE, V5E4, ctx, global_reader

SHARDED_TABLE = {"ways": 8, "tiers": {"sharded": {"ways": 8, "groups": 262144},
                                      "replica": {"ways": 4, "groups": 262144}}}


def test_sharded_decide_roofline_takes_the_larger_of_a_chips_hbm_and_ici_needs():
    c = ctx(trace=FOUR, table=SHARDED_TABLE, device=V5E4, items_answered=1000,
            before={"gubernator_engine_flush_waves_sum": 0.0},
            after={"gubernator_engine_flush_waves_sum": 500.0})
    # 250 lanes a chip x 816 B = 204,000 B at 819 GB/s = 249 ns of HBM; the answers of
    # 1,000 lanes x 4 columns x 8 B, three quarters received = 24,000 B at 200 GB/s =
    # 120 ns of ICI: hbm is the bound; 500 dispatches x 200 us
    hbm, ici = 250 * 816 / 819e9, 1000 * 32 * 0.75 / 200e9
    assert hbm > ici
    got = readers.read(global_reader("sharded_decide_roofline"), c)
    assert got == pytest.approx(100 * hbm / (500 * 200e-6))
    assert 0 < got < 100
    # no sharded tier (a one-chip daemon), no trace, no dispatch counted: nothing to read
    for lacking in (dict(table=GLOBAL_TABLE), dict(trace=None), dict(after={}),
                    dict(items_answered=0)):
        kw = dict(trace=FOUR, table=SHARDED_TABLE, device=V5E4, items_answered=1000,
                  before={}, after={"gubernator_engine_flush_waves_sum": 500.0})
        kw.update(lacking)
        assert readers.read(global_reader("sharded_decide_roofline"), ctx(**kw)) is None


@pytest.mark.parametrize("after,want", [
    ([130.0, 110.0, 100.0, 100.0], 4 * 120 / 420),  # deltas 120, 100, 100, 100
    ([110.0, 110.0, 100.0, 100.0], 1.0),  # an even window: 100 each
    ([10.0, 10.0, 0.0, 0.0], None),  # nothing answered between the scrapes
    ([], None),  # the parent's program: no such counter
])
def test_shard_imbalance_is_max_over_mean_of_the_windows_deltas(after, want):
    series = 'gubernator_shard_decisions{shard="%d"}'
    before = {series % i: v for i, v in enumerate([10.0, 10.0, 0.0, 0.0])}
    c = ctx(before=before if after else {},
            after={series % i: v for i, v in enumerate(after)})
    got = readers.read(global_reader("shard_imbalance"), c)
    assert got == (pytest.approx(want) if want is not None else None)
