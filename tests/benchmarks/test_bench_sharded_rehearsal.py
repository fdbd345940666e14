"""CPU rehearsals of the twin cells of PR 32, `zipf-1m.calls100` on one device
and `sharded-4.calls100` on four forced ones, through the whole harness at a
tiny size, and the three controls on the four-device cell coming out not
correct. The helpers are `test_bench_rehearsal.py`'s; the cases live here
because a PR that changes the program may only add files to the benchmark."""

import pytest

from test_bench_rehearsal import EXACT_ROWS, ROOT, rows_printed, run_cell, sound


@pytest.mark.deadline(150)
@pytest.mark.parametrize("cell,devices,table", [
    ("zipf-1m.calls100", 1, "table: groups=4096 ways=8 slots=32768 "),
    # the pod daemon: the sharded tier's geometry on top, the replica tier's slots in the sum
    ("sharded-4.calls100", 4, "table: groups=4096 ways=8 slots=49152 ")])
def test_rehearsal_calls100_on_one_device_and_owner_sharded_over_four(cell, devices, table):
    """The twins: the same calls (100 items, Zipf 0.99) on one device and on the
    sharded tier of the four-device daemon, each an exact configuration."""
    rc, result, log = run_cell(ROOT, cell, "--trace", "0", "--platform", "cpu",
                               "--keys", "20000", timeout=140)
    sound(rc, result, log)
    assert result["device"]["count"] == devices
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}
    assert rows_printed(log) == EXACT_ROWS and "quiesce" not in log
    assert table in log
    assert "check_calls=8 check_items=800" in log and "setup.mismatches: 0" in log
    assert result["attempted"] % 100 == 0


@pytest.mark.deadline(150)
@pytest.mark.parametrize("kind,row", [
    ("double_apply", "window.token_generations_not_exact"),
    ("stale_answer", "window.token_generations_not_exact"), ("forget", "evicted_keys")])
def test_sharded_4_calls100_broken_underneath_comes_out_not_correct(kind, row):
    """8,000 keys on 4,096 groups of 8: next to no eviction of the table's own
    (allowance ~20), so the ~100 buckets every broken call forgets stand out
    whatever the host's load lets the window send (20,000 keys evict ~2,000
    by themselves: a slow window's few hundred forgotten fit under three
    times that)."""
    rc, result, log = run_cell(ROOT, "sharded-4.calls100", "--trace", "0", "--platform",
                               "cpu", "--keys", "8000", "--control", kind, timeout=140)
    assert rc == 0 and result is not None, log
    assert result["correct"] is False, log
    value, limit = result["checks"][row]
    assert value > limit, log
