"""CPU rehearsal of `global-hot-4.herd-zipf` (PR 43) through the whole
harness at a tiny size, on four forced CPU devices: sound, with the
eventual rows printed, the hottest key used up inside the window and the
hits taken beyond it equal to the program's own count
(`tools/global_hot_sums.py` wraps the run); its controls not correct;
the three new readers against a recorded pair of scrapes, the parent's
scrapes giving nothing; and the configuration held to `global-4`'s
daemon, letter for letter. Nothing here pins the manifest's length or
order: every entry is found by its name. The helpers are
`test_bench_rehearsal.py`'s; the cases live here because a PR that
changes the program may only add files to the benchmark."""

import json
import os
import subprocess
import sys

import pytest

from test_bench_rehearsal import EXACT_ROWS, GLOBAL_ROWS, ROOT, rows_printed, run_cell, sound

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import manifest, readers  # noqa: E402

CELL, CONTROL_CELL = "global-hot-4.herd-zipf", "global-4.herd"
NEW = ("global_merged_hits_share", "global_over_admitted_per_k", "replica_imbalance")
DEVICES = [f'gubernator_replica_decisions{{device="{d}"}}' for d in range(4)]


def load(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def entry(m, kind, name):
    return next(x for x in m[kind] if x["name"] == name)


@pytest.mark.deadline(200)
def test_rehearsal_global_hot_4_herd_zipf_uses_a_key_up_and_counts_what_went_over():
    """Ten keys of 1,000 under Zipf 0.99: the hottest draws a third of the
    hits and is used up inside ten seconds, so OVER_LIMIT under lag has
    work; what the replicas took beyond it is what the tick counted."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    r = subprocess.run(
        [sys.executable, "tools/global_hot_sums.py", "--", sys.executable,
         "benchmarks/run.py", "--workload", CELL, "--seed", "2147483743",
         "--seconds", "10", "--trace", "1", "--platform", "cpu", "--keys", "10"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=190)
    log = r.stdout[-12000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    result = json.loads(next(ln for ln in lines if ln.startswith('{"correct"')))
    sums = json.loads(lines[-1].split("GLOBAL_SUMS ", 1)[1])
    sound(r.returncode, result, log)
    assert result["device"]["count"] == 4
    # the exact rows stay (no plain item: all 0), the probes' row gives way
    assert rows_printed(r.stdout) == (EXACT_ROWS[:9] + GLOBAL_ROWS + EXACT_ROWS[-2:]), log
    assert "the keys live in tier 'replica'" in log
    assert "check_calls=8 check_items=16" in log
    assert "(after a preload, the rest inside check_s)" in log
    # one generation a key: nothing is made in the window, nothing joined
    assert "eventual: 0 generations joined" in log
    held = int(log.split(" probed keys held to their totals")[0].split()[-1])
    assert sums["uncertain_keys"] == 0 and sums["used_up_keys"] >= 1, sums
    assert held == 10 - sums["used_up_keys"]
    assert sums["over_limit_answers"] > 0
    assert sums["accepted_over_start"] == sums["over_admitted_hits"], sums
    assert 0 < sums["merged_hits"] < sums["window_answers"]
    by_device = sums["replica_decisions"]
    assert sum(by_device) == sums["window_answers"] == result["attempted"]
    assert max(by_device) - min(by_device) <= 1
    listed = {x["name"] for x in manifest.metrics_of(manifest.load(ROOT), CELL, "per_layer")}
    assert set(NEW) <= listed and set(result["metrics"]) <= listed
    printed = {ln.split()[1].rstrip(":"): ln.split()[2] for ln in lines
               if ln.startswith("per_layer ")}
    assert set(NEW) <= set(printed), log
    # counts are the same on a CPU: three lanes in four land off the owner,
    # less the refusals; the round-robin meets every replica alike
    assert 30.0 < float(printed["global_merged_hits_share"]) < 80.0, log
    assert float(printed["global_over_admitted_per_k"]) >= 0.0
    assert 1.0 <= float(printed["replica_imbalance"]) < 1.05
    assert float(printed["columnar_call_share"]) == 100.0
    assert float(printed["preload_s"]) > 0.0 and float(printed["quiesce_s"]) > 0.0
    # no device plane on a CPU: the rooflines find nothing and say so
    assert printed["ici_tick_roofline"] == printed["replica_decide_roofline"] == "None"


@pytest.mark.deadline(150)
@pytest.mark.parametrize("kind,row", [
    ("double_apply", "probe.global_mismatches"),
    ("stale_answer", "window.global_remaining_out_of_range"),
    ("forget", "evicted_keys")])
def test_global_hot_4_broken_underneath_comes_out_not_correct(kind, row):
    """With buckets that outlive the run a hit counted twice stays on every
    copy and the probes find it, exactly; an answer of another call lies
    outside what any copy may give; an answer from a bucket made anew shows
    a generation where the key's own still lived."""
    rc, result, log = run_cell(ROOT, CELL, "--trace", "0", "--platform", "cpu",
                               "--keys", "200", "--control", kind, seconds=8, timeout=140)
    assert rc == 0 and result is not None, log
    assert result["correct"] is False, log
    value, limit = result["checks"][row]
    assert value > limit, log


def test_global_hot_4_is_global_4s_daemon_with_keys_that_live():
    conf, g4 = load("benchmarks/configs/global-hot-4.json"), load("benchmarks/configs/global-4.json")
    for key in ("command", "env", "rehearsal_env", "chips", "consistency"):
        assert conf[key] == g4[key], key  # global-4's, letter for letter
    assert conf["keyspace"] == {"name": "bench", "keys": 100000, "algorithm": "token",
                                "limit": 1000, "duration_ms": 3600000,
                                "behavior": ["GLOBAL"]}
    assert conf["preload"] == {"hits": 1}
    assert conf["probes"] == {"hottest": 1000, "seeded": 3000}
    assert conf["guarantees"][:3] == g4["guarantees"][:3]
    assert conf["guarantees"][4] == g4["guarantees"][4]
    assert "gubernator_global_over_admitted_hits" in conf["guarantees"][-1]
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    for size in ("keys", "skew", "duration_ms", "deployment", "geometry",
                 "setup_check_calls", "probe_repeats"):
        assert conf["assumed"][size], size
    m = manifest.load(ROOT)
    listed = entry(m, "configs", "global-hot-4")
    assert listed["source"] == conf["source"] and listed["reduced"] == []
    assert listed["file"] == "benchmarks/configs/global-hot-4.json"


def test_herd_zipf_is_herd_with_the_keys_skewed_and_the_cell_is_listed():
    m = manifest.load(ROOT)
    manifest.check(m, ROOT)
    herd = load("benchmarks/traffic/herd.json")
    zipf = json.load(open(manifest.traffic_path(ROOT, manifest.bench_dir(m), "herd-zipf"),
                          encoding="utf-8"))
    assert zipf["keys"] == {"distribution": "zipf", "s": 0.99, "scrambled": True}
    for key in ("loop", "callers", "items_per_call", "hits", "pool_calls", "workers",
                "call_deadline_s", "trace_seconds"):
        assert zipf[key] == herd[key], key
    assert zipf["rehearsal"] and "0.0102" in zipf["assumed"]["items_per_call"]
    cell = entry(m, "workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("global-hot-4", "herd-zipf", 4)
    assert CELL in entry(m, "end_to_end", "decisions_per_s")["workloads"]
    assert {x["name"] for x in manifest.metrics_of(m, CELL, "end_to_end")} == {
        "decisions_per_s", "setup_s"}
    for name in NEW:  # each reads beside its control
        metric = entry(m, "per_layer", name)
        assert metric["workloads"] == [CONTROL_CELL, CELL]
        assert (metric["layer"], metric["moves"]) == ("GLOBAL sync", "decisions_per_s")
    for name in ("waves_per_flush", "ici_tick_ms", "ici_tick_roofline",
                 "replica_decide_roofline", "quiesce_s", "preload_s"):
        assert CELL in entry(m, "per_layer", name)["workloads"], name


# ---- the new readers against a recorded pair of scrapes -------------------------

# Between the scrapes: 4,000 GLOBAL lanes, 1,010 / 1,000 / 1,000 / 990 a
# replica; 2,700 hits taken off their owners, 36 of them beyond a limit.
ADDED = dict(zip(DEVICES, (1010.0, 1000.0, 1000.0, 990.0)))
ADDED["gubernator_global_merged_hits"] = 2700.0
ADDED["gubernator_global_over_admitted_hits"] = 36.0
WANT = {"global_merged_hits_share": 67.5, "global_over_admitted_per_k": 9.0,
        "replica_imbalance": 1.01}
# the parent's program: ticks and waves, none of the three series
PARENT = {"gubernator_ici_tick_groups_sum": 900.0, "gubernator_engine_flush_waves_sum": 140.0}


def ctx(before, after):
    return readers.Context(
        before=before, after=after,
        device={"device_kind": "TPU v5 lite", "device_count": 4},
        phases={}, generator={}, trace=None, conf={}, traffic={},
        table={"tiers": {"replica": {"ways": 4}}}, items_answered=4000, root=ROOT)


def read(name, context):
    m = manifest.load(ROOT)
    return readers.read(manifest.reader_path(ROOT, manifest.bench_dir(m), name), context)


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_new_reader_reads_a_recorded_pair_and_nothing_from_the_parent(name):
    before = {k: 7.0 for k in ADDED}
    after = {k: 7.0 + v for k, v in ADDED.items()}
    assert read(name, ctx(before, after)) == pytest.approx(WANT[name])
    # the parent: none of the series, and nothing raised
    assert read(name, ctx({k: 0.0 for k in PARENT}, PARENT)) is None
    # a window in which no GLOBAL lane was answered: nothing, not a division
    assert read(name, ctx(after, after)) is None
