"""CPU rehearsals of the cells of PR 36 through the whole harness at a tiny
size: `store-1m.calls100` (the normal daemon with a Store attached by
`benchmarks/store_daemon.py`) sound and with `evicted_keys` 0, where its
twin without a Store excuses hundreds; its `double_apply` control not
correct; `zipf-1m.steady` (open loop, mixed call sizes) carrying
`call_p50_ms`; and each new reader against a hand-made pair of scrapes,
the parent's scrapes giving nothing. The helpers are
`test_bench_rehearsal.py`'s; the cases live here because a PR that changes
the program may only add files to the benchmark."""

import json
import os
import sys

import pytest

from test_bench_rehearsal import EXACT_ROWS, ROOT, rows_printed, run_cell, sound

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import manifest, readers  # noqa: E402

STORE, STEADY = "store-1m.calls100", "zipf-1m.steady"


def config(name):
    with open(os.path.join(ROOT, "benchmarks/configs", name + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.deadline(200)
def test_rehearsal_store_1m_calls100_reads_evicted_keys_back():
    """20,000 keys through 32,768 slots evict ~2,000 keys of the twin (its
    allowance 2,500); with a Store each is read back on its next request, so
    the capacity-free reference sees no fresh bucket at all."""
    rc, result, log = run_cell(ROOT, STORE, "--trace", "1", "--platform", "cpu",
                               "--keys", "20000", seconds=4, timeout=190)
    sound(rc, result, log)
    assert rows_printed(log) == EXACT_ROWS
    assert "table: groups=4096 ways=8 slots=32768 " in log
    assert "check_calls=8 check_items=800" in log
    assert result["checks"]["evicted_keys"][0] == 0, log
    assert result["checks"]["evicted_keys"][1] > 1000  # the table's allowance stays
    listed = {x["name"] for x in manifest.metrics_of(manifest.load(ROOT), STORE, "per_layer")}
    assert set(result["metrics"]) <= listed
    # counts are the same on a CPU: every call columnar; since PR 45 a flush
    # whose keys are all resident runs its waves as one stacked probe, decide
    # and row gather, so a flush is fewer launches than waves and a wave fewer
    # than the per-wave sequence's three programs
    printed = {ln.split()[1].rstrip(":"): ln.split()[2] for ln in log.splitlines()
               if ln.startswith("per_layer ")}
    assert 0.0 < float(printed["store_programs_per_wave"]) < 3.0, log
    assert float(printed["columnar_call_share"]) == 100.0
    assert 1.0 <= float(printed["launches_per_flush"]) < float(printed["waves_per_flush"])
    assert float(printed["store_get_share"]) >= 0.0  # a number: the short span may hold no miss
    for name in ("store_readthrough_us_per_wave", "store_rows_us_per_wave",
                 "store_write_behind_us_per_flush"):
        assert float(printed[name]) > 0.0, name
    # no device plane on a CPU: the rooflines find nothing and say so
    assert printed["store_probe_roofline"] == printed["store_rows_roofline"] == "None"


@pytest.mark.deadline(200)
def test_store_1m_calls100_applied_twice_underneath_comes_out_not_correct():
    rc, result, log = run_cell(ROOT, STORE, "--trace", "0", "--platform", "cpu",
                               "--keys", "20000", "--control", "double_apply",
                               seconds=4, timeout=190)
    assert rc == 0 and result is not None, log
    assert result["correct"] is False, log
    value, limit = result["checks"]["window.token_generations_not_exact"]
    assert value > limit == 0, log


@pytest.mark.deadline(200)
def test_rehearsal_zipf_1m_steady_mixed_sizes_open_loop():
    rc, result, log = run_cell(ROOT, STEADY, "--trace", "0", "--platform", "cpu",
                               "--keys", "20000", seconds=4, timeout=190)
    sound(rc, result, log)
    assert set(result["metrics"]) == {"call_p50_ms", "setup_s"}
    assert rows_printed(log) == EXACT_ROWS
    assert "plan: loop=open " in log and "check_calls=12 " in log
    # the multiset of sizes is the file's: 70 % two items, 25 % a page, 5 % the cap
    calls = int(log.split("calls_made=")[1].split()[0])
    items = int(log.split("items_made=")[1].split()[0])
    assert abs(items / calls - 76.4) < 3.0, log


def test_store_daemon_is_the_configurations_command_and_nothing_else():
    conf, twin = config("store-1m"), config("zipf-1m")
    assert conf["command"] == ["-m", "benchmarks.store_daemon"]
    for key in ("env", "rehearsal_env", "keyspace", "preload", "probes", "chips"):
        assert conf[key] == twin[key], key  # zipf-1m's, letter for letter
    assert conf["guarantees"][:3] == twin["guarantees"][:3] and conf["reduced"] == []
    with open(os.path.join(ROOT, "benchmarks/store_daemon.py"), encoding="utf-8") as f:
        assert len(f.read().splitlines()) < 30


# ---- the new readers against a recorded pair of scrapes -------------------------


def programs(p):
    return f'gubernator_engine_wave_programs{{program="{p}"}}'


def stage(kind, s):
    return f'gubernator_engine_stage_duration_{kind}{{stage="{s}"}}'


def gets(r):
    return f'gubernator_store_gets{{result="{r}"}}'


# Between the scrapes: 20 flushes of 140 waves, 2,000 items, 5 read through.
ADDED = {
    programs("probe"): 140.0, programs("inject"): 4.0, programs("decide"): 140.0,
    programs("gather_rows"): 140.0, "gubernator_engine_flush_waves_sum": 140.0,
    stage("sum", "readthrough"): 0.42, stage("sum", "store_rows"): 0.98,
    stage("sum", "write_behind"): 0.05, stage("count", "write_behind"): 20.0,
    gets("hit"): 4.0, gets("miss"): 1.0, "gubernator_command_counter": 2000.0,
}
WANT = {
    "store_programs_per_wave": 424 / 140,
    "store_readthrough_us_per_wave": 1e6 * 0.42 / 140,
    "store_rows_us_per_wave": 1e6 * 0.98 / 140,
    "store_write_behind_us_per_flush": 1e6 * 0.05 / 20,
    "store_get_share": 100 * 5 / 2000,
    # 2,000 lanes x (8 x 80 + 21) B against 140 launches of 50 us; x 168 B
    # against 140 of 25 us, at 819 GB/s
    "store_probe_roofline": 100 * (2000 * 661 / 819e9) / (140 * 50e-6),
    "store_rows_roofline": 100 * (2000 * 168 / 819e9) / (140 * 25e-6),
}
TRACE = {"devices": [{"programs": {
    "jit_probe_exists_fused(123)": (40, 40 * 50e-6),
    "jit_gather_rows_fused(456)": (40, 40 * 25e-6),
    "jit_decide_fused(789)": (40, 40 * 60e-6)}}]}
# the parent's program: the waves, the items and the decide, none of the new series
PARENT = {"gubernator_engine_flush_waves_sum": 140.0,
          "gubernator_command_counter": 2000.0,
          stage("sum", "dispatch"): 1.9}


def ctx(before, after, trace=TRACE):
    return readers.Context(
        before=before, after=after, device={"device_kind": "TPU v5 lite"},
        phases={}, generator={}, trace=trace, conf={}, traffic={},
        table={"ways": 8}, items_answered=2000, root=ROOT)


def read(name, context):
    m = manifest.load(ROOT)
    return readers.read(manifest.reader_path(ROOT, manifest.bench_dir(m), name), context)


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_new_reader_reads_a_recorded_pair_and_nothing_from_the_parent(name):
    before = {k: 7.0 for k in ADDED}
    after = {k: 7.0 + v for k, v in ADDED.items()}
    assert read(name, ctx(before, after)) == pytest.approx(WANT[name])
    # the parent: the same trace without the Store's programs, no new series
    parent_trace = {"devices": [{"programs": {
        "jit_decide_fused(789)": (40, 40 * 60e-6)}}]}
    assert read(name, ctx({k: 0.0 for k in PARENT}, PARENT, parent_trace)) is None
    # and an untraced or device-less run gives the rooflines nothing either
    if name.endswith("_roofline"):
        assert read(name, ctx(before, after, None)) is None
    m = manifest.load(ROOT)
    entry = next(x for x in m["per_layer"] if x["name"] == name)
    assert entry["workloads"] == [STORE] and entry["moves"] == "decisions_per_s"
