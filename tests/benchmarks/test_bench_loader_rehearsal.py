"""CPU rehearsals of `loader-1m.calls100` (PR 48) through the whole harness
at a tiny size: the normal daemon with a file Loader attached by
`benchmarks/loader_daemon.py`, its table filled from the reference's
snapshot before the first call and its saved snapshot read back after the
exit; then the same run with each of the six controls underneath, every one
not correct through the row it should fail by (`strip_flags` on a test's own
files: the cell's traffic carries no flag to strip). 2,000 keys in 32,768 slots:
no group overflows, so no probe makes a bucket that could push an earlier
probed key out of the table before the Save (PERF.md §7, PR 48). The
helpers are `test_bench_rehearsal.py`'s."""

import json
import os
import sys

import pytest

from test_bench_rehearsal import (
    EXACT_ROWS,
    ROOT,
    checkout,
    only_added_to,
    rows_printed,
    run_cell,
    sound,
)

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import manifest, snapshot  # noqa: E402

CELL = "loader-1m.calls100"
ROWS = (["load.keys_not_resident"] + EXACT_ROWS
        + ["save.file_unreadable", "save.keys_unknown", "save.keys_missing",
           "save.rows_differ"])
WORK = os.path.join(ROOT, ".bench_out")


def rehearse(*extra, seconds=3):
    return run_cell(ROOT, CELL, "--platform", "cpu", "--keys", "2000", *extra,
                    seconds=seconds, timeout=110)


@pytest.mark.deadline(120)
def test_rehearsal_loader_1m_calls100_loads_serves_on_the_object_path_and_saves():
    rc, result, log = rehearse("--trace", "1", seconds=4)
    sound(rc, result, log)
    assert rows_printed(log) == ROWS and list(result["checks"]) == ROWS
    assert list(result)[-1] == "checks"
    assert all(v <= lim for v, lim in result["checks"].values()), log
    assert result["checks"]["load.keys_not_resident"] == [0, 3]
    assert "table: groups=4096 ways=8 slots=32768 keys=2000 " in log
    assert "check_calls=8 check_items=800" in log and "shutdown: save_s=" in log
    # a CPU run gives no time: the phases are named and null, save_s among them
    assert result["phases"] == {"start_s": None, "preload_s": None, "check_s": None,
                                "save_s": None}
    # every key and the two workers' own warm-up keys were saved, every key probed
    assert result["counted"]["saved_rows"] == 2002, log
    assert result["counted"]["saved_probed"] == 2000
    listed = {x["name"] for x in manifest.metrics_of(manifest.load(ROOT), CELL, "per_layer")}
    assert set(result["metrics"]) <= listed
    printed = {ln.split()[1].rstrip(":"): ln.split()[2] for ln in log.splitlines()
               if ln.startswith("per_layer ")}
    # counts are the same on a CPU: no call is columnar, each is an object call
    assert float(printed["columnar_call_share"]) == 0.0
    for name in ("object_host_ms_per_call", "engine_wait_ms_per_call", "save_s",
                 "waves_per_flush", "calls_per_flush.closed"):
        assert float(printed[name]) > 0.0, name
    # both checkpoints lie in the run's own directory and are whole
    work = os.path.join(WORK, CELL + "-t1")
    loaded, saved = (snapshot.read(os.path.join(work, f"snapshot_{x}.npz"))
                     for x in ("in", "out"))
    assert len(loaded[0]) == 2000 and set(loaded[0]) < set(saved[0])
    assert set(loaded[1]["remaining"].tolist()) == {99}  # limit 100 less the preload's hit
    assert saved[1]["remaining"].min() < 99  # the window's hits are in the Save


# the row a fault has to fail by; a relay under the timed path breaks the
# window's rows as it does in every cell, a snapshot fault the rows of its end
CONTROLS = [
    ("stale_snapshot", ("setup.mismatches", "probe.mismatches")),
    ("drop_saved", ("save.keys_missing",)),
    ("double_apply", ("window.token_generations_not_exact",)),
    ("stale_answer", ("window.token_generations_not_exact",)),
    ("forget", ("evicted_keys",)),
]


@pytest.mark.deadline(120)
@pytest.mark.parametrize("kind,rows", CONTROLS, ids=[c[0] for c in CONTROLS])
def test_loader_1m_calls100_broken_underneath_comes_out_not_correct(kind, rows):
    rc, result, log = rehearse("--trace", "0", "--control", kind)
    assert rc == 0 and result is not None, log
    assert "CONTROL RUN: " + kind in log
    assert result["correct"] is False, log
    failed = {name for name, (v, lim) in result["checks"].items() if v > lim}
    assert failed & set(rows), (failed, log)
    if kind == "drop_saved":  # the server and its answers were sound: the file alone
        assert failed == {"save.keys_missing"}, log
        assert result["counted"]["saved_rows"] == 2002 - 3
    if kind == "stale_snapshot":  # the Save is true to what was served
        assert not failed & {"save.keys_missing", "save.rows_differ"}, log


# ---- flags by key and by item, loaded, served and saved: a test's own files ---------
# `loader-1m.calls100` carries no flag, so `strip_flags` has nothing to strip
# there, the snapshot has one class of key, and no RESET_REMAINING removes a
# bucket before the Save. The same daemon with DRAIN_OVER_LIMIT a part of a
# third of the limits and RESET_REMAINING an event, added as files and entries.

FLAGS = "loader-flags.calls100-flags"


@pytest.fixture(scope="module")
def flags_tree(tmp_path_factory):
    root, m = checkout(tmp_path_factory)
    old = json.loads(json.dumps(m))
    conf = json.load(open(root / "benchmarks/configs/loader-1m.json"))
    conf["name"] = "loader-flags"
    conf["keyspace"]["behavior_of_keys"] = [{"one_in": 3, "behavior": ["DRAIN_OVER_LIMIT"]}]
    conf["preload"]["hits"] = 98  # two left: refusals with a remainder for DRAIN to empty
    json.dump(conf, open(root / "benchmarks/configs/loader-flags.json", "w"))
    traf = json.load(open(root / "benchmarks/traffic/calls100.json"))
    traf["hits"] = {"1": 0.80, "2": 0.10, "5": 0.10}
    traf["behavior_shares"] = [{"share": 0.98, "behavior": []},
                               {"share": 0.02, "behavior": ["RESET_REMAINING"]}]
    json.dump(traf, open(root / "benchmarks/traffic/calls100-flags.json", "w"))
    m["configs"].append({"name": "loader-flags", "source": "none: a test's own",
                         "file": "benchmarks/configs/loader-flags.json", "reduced": [],
                         "why": "a Loader-attached daemon whose limits carry flags"})
    m["workloads"].append({"name": FLAGS, "config": "loader-flags",
                           "traffic": "calls100-flags", "chips": 1,
                           "why": "hits of 1 to 5 and RESET_REMAINING events, loaded and saved"})
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] in ("decisions_per_s", "columnar_call_share", "save_s"):
            e["workloads"].append(FLAGS)
    json.dump(m, open(root / "BENCHMARK.json", "w"))
    assert only_added_to(old, m)
    return str(root)


@pytest.mark.deadline(120)
def test_rehearsal_of_a_loader_cell_with_flags_holds_load_and_save(flags_tree):
    """Two classes of key in the snapshot (the reference evaluated once for
    each), buckets that RESET_REMAINING removed before the Save, drained
    generations: stage 1 holds the Load, stage 4 the Save."""
    rc, result, log = run_cell(flags_tree, FLAGS, "--trace", "0", "--platform", "cpu",
                               "--keys", "2000", seconds=4)
    sound(rc, result, log)
    assert rows_printed(log) == ROWS
    assert all(v == 0 for v, _ in result["checks"].values()), log
    c = result["counted"]
    assert c["reset_removed_bucket"] > 20 and c["generations_drained"] > 0, log
    assert c["refused_with_remainder"] > 20 and c["items_hits_over_1"] > 0, log
    assert c["saved_probed"] == 2000  # a probe makes a bucket where a RESET removed one


@pytest.mark.deadline(120)
def test_a_loader_cell_with_flags_stripped_underneath_comes_out_not_correct(flags_tree):
    rc, result, log = run_cell(flags_tree, FLAGS, "--trace", "0", "--platform", "cpu",
                               "--keys", "2000", "--control", "strip_flags", seconds=4)
    assert rc == 0 and result is not None, log
    assert result["correct"] is False, log
    failed = {n for n, (v, lim) in result["checks"].items() if v > lim}
    assert failed & {"window.reset_not_fresh", "window.drain_left_remaining"}, log
    assert not failed & {"save.keys_unknown", "save.keys_missing"}, log


@pytest.mark.deadline(120)
def test_a_snapshot_control_needs_a_configuration_that_has_the_snapshot(flags_tree):
    """`zipf-1m` is preloaded over gRPC and saves nothing: both controls are
    refused before a server starts, with no result line. (In the checkout: the
    run's directory is not one a rehearsal of another file is using.)"""
    for kind in ("stale_snapshot", "drop_saved"):
        rc, result, log = run_cell(flags_tree, "zipf-1m.calls100", "--trace", "0",
                                   "--platform", "cpu", "--keys", "2000",
                                   "--control", kind, timeout=60)
        assert rc != 0 and result is None, log
        assert f"--control {kind}: the configuration" in log
