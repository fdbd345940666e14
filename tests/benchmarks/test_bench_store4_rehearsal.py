"""CPU rehearsals of the cells of PR 41 through the whole harness at a tiny
size: `store-4.calls100` (the four-chip daemon with a Store attached by
`benchmarks/store_daemon.py`, on four forced CPU devices) sound, with
`evicted_keys` 0 where `sharded-4.calls100` excuses hundreds and with no
row of the write-behind skipped; its `double_apply` control not correct;
`batching-10k.burst` (open loop, fifty calls at one instant) carrying
`call_p50_ms`; the manifest with its ten cells; and each new reader
against a hand-made pair of scrapes, the parent's scrapes giving nothing.
The helpers are `test_bench_rehearsal.py`'s; the cases live here because a
PR that changes the program may only add files to the benchmark."""

import json
import os
import sys

import pytest

from test_bench_rehearsal import EXACT_ROWS, ROOT, rows_printed, run_cell, sound

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import manifest, readers  # noqa: E402

STORE4, BURST = "store-4.calls100", "batching-10k.burst"
NEW = ("sharded_store_programs_per_wave", "sharded_store_rows_us_per_wave",
       "sharded_store_readthrough_us_per_wave",
       "sharded_store_write_behind_us_per_flush",
       "sharded_store_skipped_rows_per_flush", "sharded_store_rows_roofline",
       "sharded_store_probe_roofline")


def config(name):
    with open(os.path.join(ROOT, "benchmarks/configs", name + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.deadline(200)
def test_rehearsal_store_4_calls100_hands_every_change_to_the_store():
    """20,000 keys through the 32,768 sharded slots of four forced devices:
    the twin without a Store excuses ~2,000 evicted keys; here each is read
    back into its owner's shard, and the write-behind skips no row."""
    # six seconds: the two scrapes lie 1.5 s apart (0.5 s at four), so a flush
    # ends between them on four forced devices and a loaded host too
    rc, result, log = run_cell(ROOT, STORE4, "--trace", "1", "--platform", "cpu",
                               "--keys", "20000", seconds=6, timeout=190)
    sound(rc, result, log)
    assert result["device"]["count"] == 4
    assert rows_printed(log) == EXACT_ROWS and "quiesce" not in log
    # the pod daemon: the sharded tier's geometry on top, the replica tier's slots in the sum
    assert "table: groups=4096 ways=8 slots=49152 " in log
    assert "check_calls=8 check_items=800" in log
    assert result["checks"]["evicted_keys"][0] == 0, log
    assert result["checks"]["evicted_keys"][1] > 1000  # the table's allowance stays
    listed = {x["name"] for x in manifest.metrics_of(manifest.load(ROOT), STORE4, "per_layer")}
    assert set(NEW) <= listed and set(result["metrics"]) <= listed
    printed = {ln.split()[1].rstrip(":"): ln.split()[2] for ln in log.splitlines()
               if ln.startswith("per_layer ")}
    assert set(NEW) <= set(printed), log
    # counts are the same on a CPU: nothing skipped, every call columnar; since
    # PR 45 a flush of resident keys is one stacked probe, decide and gather:
    # fewer launches than waves, under the per-wave sequence's three programs
    assert float(printed["sharded_store_skipped_rows_per_flush"]) == 0.0, log
    assert 0.0 < float(printed["sharded_store_programs_per_wave"]) < 3.0, log
    assert float(printed["columnar_call_share"]) == 100.0
    assert 1.0 <= float(printed["launches_per_flush"]) < float(printed["waves_per_flush"])
    assert float(printed["shard_imbalance"]) >= 1.0
    for name in NEW[1:4]:
        assert float(printed[name]) > 0.0, name
    # no device plane on a CPU: the rooflines find nothing and say so
    assert printed["sharded_store_rows_roofline"] == "None"
    assert printed["sharded_store_probe_roofline"] == "None"


@pytest.mark.deadline(200)
def test_store_4_calls100_applied_twice_underneath_comes_out_not_correct():
    rc, result, log = run_cell(ROOT, STORE4, "--trace", "0", "--platform", "cpu",
                               "--keys", "20000", "--control", "double_apply",
                               seconds=4, timeout=190)
    assert rc == 0 and result is not None, log
    assert result["correct"] is False, log
    value, limit = result["checks"]["window.token_generations_not_exact"]
    assert value > limit == 0, log


@pytest.mark.deadline(120)
def test_rehearsal_batching_10k_burst_fifty_calls_at_one_instant():
    rc, result, log = run_cell(ROOT, BURST, "--trace", "0", "--platform", "cpu",
                               seconds=4, timeout=110)
    sound(rc, result, log)
    assert set(result["metrics"]) == {"call_p50_ms", "setup_s"}
    assert rows_printed(log) == EXACT_ROWS
    # four bursts of fifty two-item calls
    assert "plan: loop=open calls_made=200 items_made=400 workers=1" in log
    assert result["attempted"] == 400


def test_manifest_holds_the_two_cells_by_rules_that_the_next_cell_keeps():
    """Rules, not counts and last names: every configuration has a cell, at
    most half the cells (rounded down) ask for four chips, and the two cells
    of PR 41 report what they came for; a cell appended later breaks none."""
    m = manifest.load(ROOT)
    manifest.check(m, ROOT)
    cells = {w["name"]: w for w in m["workloads"]}
    assert {w["config"] for w in m["workloads"]} == {c["name"] for c in m["configs"]}
    four = [n for n, w in cells.items() if w["chips"] == 4]
    assert STORE4 in four and len(four) <= len(cells) // 2
    assert cells[BURST]["chips"] == 1
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert STORE4 in e2e["decisions_per_s"]["workloads"]
    assert BURST in e2e["call_p50_ms"]["workloads"]
    assert all(0 < e["bound"] <= 0.25 for e in m["end_to_end"])
    for name in NEW:
        entry = next(x for x in m["per_layer"] if x["name"] == name)
        assert entry["workloads"] == [STORE4] and entry["moves"] == "decisions_per_s"
    traffic = json.load(open(manifest.traffic_path(ROOT, manifest.bench_dir(m), "burst"),
                             encoding="utf-8"))
    assert traffic["arrivals"] == {"kind": "bursts", "calls": 50, "every_ms": 1000}
    assert (traffic["loop"], traffic["rate_calls_per_s"], traffic["items_per_call"],
            traffic["workers"]) == ("open", 50.0, 2, 1)


def test_store_4_is_the_product_of_the_two_configurations_nothing_cut():
    conf, shard, store, keys = (config(n) for n in
                                ("store-4", "sharded-4", "store-1m", "zipf-1m"))
    assert conf["command"] == store["command"] == ["-m", "benchmarks.store_daemon"]
    for key in ("env", "rehearsal_env", "chips"):
        assert conf[key] == shard[key], key  # sharded-4's, letter for letter
    for key in ("keyspace", "preload", "probes"):
        assert conf[key] == keys[key], key  # zipf-1m's
    assert conf["guarantees"][:5] == shard["guarantees"]
    assert conf["guarantees"][6] == store["guarantees"][4]
    assert "by the chip that owns it" in conf["guarantees"][5]
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    entry = next(c for c in manifest.load(ROOT)["configs"] if c["name"] == "store-4")
    assert entry["source"] == conf["source"] and entry["reduced"] == []


# ---- the new readers against a recorded pair of scrapes -------------------------


def programs(p):
    return f'gubernator_engine_wave_programs{{program="{p}"}}'


def stage(kind, s):
    return f'gubernator_engine_stage_duration_{kind}{{stage="{s}"}}'


# Between the scrapes: 20 flushes of 140 waves, 2,000 items, 4 read through,
# 6 rows of the write-behind skipped.
ADDED = {
    programs("probe"): 140.0, programs("inject"): 4.0, programs("decide"): 140.0,
    programs("gather_rows"): 140.0, "gubernator_engine_flush_waves_sum": 140.0,
    "gubernator_engine_flush_waves_count": 20.0,
    stage("sum", "readthrough"): 0.42, stage("sum", "store_rows"): 0.98,
    stage("sum", "write_behind"): 0.05, stage("count", "write_behind"): 20.0,
    "gubernator_store_rows_skipped": 6.0,
}
WANT = {
    "sharded_store_programs_per_wave": 424 / 140,
    "sharded_store_readthrough_us_per_wave": 1e6 * 0.42 / 140,
    "sharded_store_rows_us_per_wave": 1e6 * 0.98 / 140,
    "sharded_store_write_behind_us_per_flush": 1e6 * 0.05 / 20,
    "sharded_store_skipped_rows_per_flush": 6 / 20,
    # a chip: 500 lanes x (8 x 80 + 21) B of HBM at 819 GB/s against 2,000 x
    # 4 B x 3/4 over ICI at 200 GB/s (hbm bounds), 140 launches of 50 us
    "sharded_store_probe_roofline": 100 * (500 * 661 / 819e9) / (140 * 50e-6),
    # 500 lanes x 168 B of HBM against 2,000 x 80 B x 3/4 over ICI (ici
    # bounds), 140 launches of 25 us
    "sharded_store_rows_roofline": 100 * (2000 * 80 * 0.75 / 200e9) / (140 * 25e-6),
}
PLANE = {"programs": {"jit_probe_exists_fn(123)": (40, 40 * 50e-6),
                      "jit_gather_rows_fn(456)": (40, 40 * 25e-6),
                      "jit_decide_fn(789)": (40, 40 * 60e-6)}}
TRACE = {"devices": [PLANE] * 4}
# the parent's program: the waves, the flushes and the decide, none of the
# new series, and the plain layout jits GSPMD partitioned under other names
PARENT = {"gubernator_engine_flush_waves_sum": 140.0,
          "gubernator_engine_flush_waves_count": 20.0,
          stage("sum", "dispatch"): 1.9}
PARENT_TRACE = {"devices": [{"programs": {"jit_decide_fn(789)": (40, 40 * 60e-6)}}] * 4}


def ctx(before, after, trace=TRACE):
    return readers.Context(
        before=before, after=after,
        device={"device_kind": "TPU v5 lite", "device_count": 4},
        phases={}, generator={}, trace=trace, conf={}, traffic={},
        table={"ways": 8, "tiers": {"sharded": {"ways": 8}}},
        items_answered=2000, root=ROOT)


def read(name, context):
    m = manifest.load(ROOT)
    return readers.read(manifest.reader_path(ROOT, manifest.bench_dir(m), name), context)


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_new_reader_reads_a_recorded_pair_and_nothing_from_the_parent(name):
    before = {k: 7.0 for k in ADDED}
    after = {k: 7.0 + v for k, v in ADDED.items()}
    assert read(name, ctx(before, after)) == pytest.approx(WANT[name])
    # the parent: no new series and no Store program of these names
    assert read(name, ctx({k: 0.0 for k in PARENT}, PARENT, PARENT_TRACE)) is None
    if name.endswith("_roofline"):  # an untraced or device-less run: nothing
        assert read(name, ctx(before, after, None)) is None
        assert read(name, ctx(before, after)) < 100.0
    if name == "sharded_store_skipped_rows_per_flush":  # 0 is a reading, not nothing
        same = dict(after, **{"gubernator_store_rows_skipped": 7.0})
        assert read(name, ctx(before, same)) == 0.0
