"""The per-layer metrics that read the program's stage series (PR 24):
each ``metrics/<name>.json`` against a hand-made pair of scrapes, a
program without the series (the parent commit) giving nothing, and the
CPU rehearsal of every cell printing a value for each new name. A CPU
run yields counts, never a time: the result line holds null for each."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import manifest, readers  # noqa: E402

HERD, SATURATE, STEADY = "batching-10k.herd", "zipf-1m.saturate", "batching-10k.steady"


def call(kind, path, stage):
    return f'gubernator_call_stage_duration_{kind}{{path="{path}",stage="{stage}"}}'


def engine(kind, stage):
    return f'gubernator_engine_stage_duration_{kind}{{stage="{stage}"}}'


def edge(path, reason):
    return f'gubernator_edge_calls{{path="{path}",reason="{reason}"}}'


# What the program adds between the two scrapes: 10 columnar calls, 4 object
# calls (refused for `waves`), 20 flushes of 50 dispatches, 2 s of clock.
BEFORE = {
    call("sum", "columnar", "executor_wait"): 1.0,
    call("count", "columnar", "engine"): 5.0,
    "gubernator_engine_clock_seconds": 100.0,
    "gubernator_engine_busy_seconds": 40.0,
    edge("columnar", ""): 7.0,
}
ADDED = {
    call("sum", "columnar", "executor_wait"): 0.030,
    call("sum", "columnar", "loop_return"): 0.010,
    call("sum", "columnar", "parse"): 0.002,
    call("sum", "columnar", "build"): 0.001,
    call("sum", "columnar", "engine"): 0.060,
    call("count", "columnar", "engine"): 10.0,
    call("sum", "object", "columnar_attempt"): 0.040,
    call("sum", "object", "engine_wait"): 1.2,
    call("sum", "object", "pb_decode"): 0.020,
    call("sum", "object", "route"): 0.030,
    call("sum", "object", "pb_encode"): 0.010,
    call("count", "object", "engine_wait"): 4.0,
    engine("sum", "lock_wait"): 0.0004,
    engine("count", "lock_wait"): 20.0,
    engine("sum", "dispatch"): 0.05,
    "gubernator_engine_flush_waves_sum": 50.0,
    "gubernator_engine_busy_seconds": 0.5,
    "gubernator_engine_clock_seconds": 2.0,
    edge("columnar", ""): 10.0,
    edge("object", "waves"): 4.0,
}
# every child exists from start-up, so a scrape holds all of them
ZEROS = [edge(p, r) for p, r in (
    ("mixed", "ring"), ("mixed", "gregorian"), ("object", "slow_item"),
    ("object", "gregorian"), ("object", "ring"), ("object", "forward_only"),
    ("object", "disabled"), ("object", "error"))]

WANT = {
    "edge_wait_ms_per_call": 1000 * 0.040 / 10,
    "edge_work_us_per_call": 1e6 * 0.003 / 10,
    "engine_ms_per_call": 1000 * 0.060 / 10,
    "engine_outstanding_share": 100 * 0.5 / 2.0,
    "lock_wait_us_per_flush": 1e6 * 0.0004 / 20,
    "dispatch_us_per_wave": 1e6 * 0.05 / 50,
    "columnar_call_share": 100 * 10 / 14,
}
# `columnar_attempt_ms_per_call`, `engine_wait_ms_per_call` and
# `object_host_ms_per_call` were PR 24's too: no call of their only cell takes
# the object path since PR 44, so PR 47 took them out (the spans stay in the
# program). Two came back in PR 48 with a cell whose every call is an object
# call, `loader-1m.calls100`, at the end of the list; the third times an
# attempt no call of any cell makes.
GONE = {"columnar_attempt_ms_per_call"}
BACK = {"engine_wait_ms_per_call": 1000 * 1.2 / 4,
        "object_host_ms_per_call": 1000 * 0.060 / 4}
LOADER = "loader-1m.calls100"
NEW = sorted(
    f"{base}{sfx}" for base in WANT
    for sfx in ((".closed", ".open") if base in (
        "edge_wait_ms_per_call", "edge_work_us_per_call", "engine_ms_per_call")
    else ("",)))


def scrapes():
    before = dict.fromkeys(list(ADDED) + ZEROS, 0.0)
    before.update(BEFORE)
    after = {k: before[k] + ADDED.get(k, 0.0) for k in before}
    return before, after


def ctx(before, after):
    return readers.Context(
        before=before, after=after, device={}, phases={}, generator={}, trace=None,
        conf={}, traffic={}, table={"ways": 8}, items_answered=0, root=ROOT)


def reader(name):
    m = manifest.load(ROOT)
    path = manifest.reader_path(ROOT, manifest.bench_dir(m), name)
    assert path.endswith(".json")  # data, no reader code
    with open(path, encoding="utf-8") as f:
        assert json.load(f)["kind"] == "metrics_ratio"
    return path


def test_the_ten_names_that_are_left_stay_together_and_in_their_order():
    """Later PRs append after PR 24's list, so its names stay present,
    together and in their order; the three that emptied are gone, files and all."""
    assert len(NEW) == 10
    m = manifest.load(ROOT)
    names = [p["name"] for p in m["per_layer"]]
    first = names.index("edge_wait_ms_per_call.closed")
    assert names[first:first + 10] == [
        "edge_wait_ms_per_call.closed", "edge_wait_ms_per_call.open",
        "edge_work_us_per_call.closed", "edge_work_us_per_call.open",
        "engine_ms_per_call.closed", "engine_ms_per_call.open",
        "lock_wait_us_per_flush", "dispatch_us_per_wave", "engine_outstanding_share",
        "columnar_call_share"]
    assert not GONE & set(names)
    assert all(manifest.reader_path(ROOT, manifest.bench_dir(m), n) is None for n in GONE)
    assert names.index("columnar_call_share") < min(names.index(n) for n in BACK)


@pytest.mark.parametrize("name", sorted(BACK))
def test_the_two_object_path_readers_that_came_back_read_what_they_read(name):
    before, after = scrapes()
    assert readers.read(reader(name), ctx(before, after)) == pytest.approx(BACK[name])
    assert readers.read(reader(name), ctx({}, {"gubernator_engine_flush_waves_sum": 3.0})) is None
    entry = next(p for p in manifest.load(ROOT)["per_layer"] if p["name"] == name)
    assert entry["workloads"] == [LOADER] and entry["source"] == "program_span"


@pytest.mark.parametrize("name", NEW)
def test_reader_against_a_synthetic_pair_of_scrapes(name):
    before, after = scrapes()
    base = name.rsplit(".", 1)[0] if name.endswith((".closed", ".open")) else name
    assert readers.read(reader(name), ctx(before, after)) == pytest.approx(WANT[base])


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_where_the_program_lacks_the_series(name):
    # the parent commit: flush and handler series only
    old = {"gubernator_engine_flush_waves_sum": 3.0,
           engine("sum", "assemble"): 1.0, engine("count", "assemble"): 2.0,
           engine("sum", "dispatch"): 1.0, engine("count", "dispatch"): 2.0}
    older = {k: v / 2 for k, v in old.items()}
    got = readers.read(reader(name), ctx(older, old))
    # `dispatch` is a label the parent's object path already observes
    assert got is None or name == "dispatch_us_per_wave"


def test_manifest_gives_each_cell_its_new_metrics():
    m = manifest.load(ROOT)
    per = {c: {x["name"] for x in manifest.metrics_of(m, c, "per_layer")}
           for c in (HERD, SATURATE, STEADY)}
    assert {n for n in NEW if n.endswith(".open")} <= per[STEADY]
    assert not {n for n in NEW if n.endswith(".closed")} & per[STEADY]
    # host time with work outstanding saturates where a caller always waits
    assert "engine_outstanding_share" in per[STEADY] - per[HERD] - per[SATURATE]
    assert not (GONE | set(BACK)) & (per[SATURATE] | per[HERD] | per[STEADY])
    for n in ("lock_wait_us_per_flush", "dispatch_us_per_wave", "columnar_call_share"):
        assert n in per[HERD] and n in per[SATURATE]
    by_name = {p["name"]: p for p in m["per_layer"]}
    for n in NEW:
        want = "higher" if n == "columnar_call_share" else "lower"
        assert by_name[n]["better"] == want, n
        assert by_name[n]["source"] == (
            "program_counter" if n == "columnar_call_share" else "program_span")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A checkout of its own: run.py works under <checkout>/.bench_out/<cell>,
    where another test file's rehearsal of the same cell may be running."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    for name in ("gubernator_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), root / name)
    return str(root)


@pytest.mark.deadline(150)
@pytest.mark.parametrize("cell,extra", [
    (HERD, ()), (STEADY, ()), (SATURATE, ("--keys", "20000"))])
def test_rehearsal_prints_a_value_for_every_new_name(tree, cell, extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "2147483711", "--seconds", "6", "--trace", "1", "--platform", "cpu", *extra],
        cwd=tree, env=env, capture_output=True, text=True, timeout=140)
    log = r.stdout[-6000:] + r.stderr[-2000:]
    assert r.returncode == 0, log
    m = manifest.load(ROOT)
    mine = [x["name"] for x in manifest.metrics_of(m, cell, "per_layer")
            if x["name"] in NEW]
    assert mine
    printed = {}
    for line in r.stdout.splitlines():
        if line.startswith("per_layer "):
            name, _, rest = line[len("per_layer "):].partition(": ")
            printed[name] = rest.split(" ")[0]
    for n in mine:
        assert n in printed and printed[n] != "None", (n, log)
    if cell != STEADY:
        # since PR 44 a call over max_waves stays columnar: saturate too
        assert float(printed["columnar_call_share"]) == 100.0
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, log
    assert all(result["metrics"][n]["value"] is None for n in mine)
