"""The per-layer metrics of ISSUE 39: calls a flush (`calls_per_flush.closed`
and `.open`). The data-file reader against a hand-made pair of scrapes, a
scrape of the parent commit giving nothing and raising nothing, the
manifest listing each where its end-to-end metric is reported, and the
CPU rehearsal of `herd` printing a value of at least one call a flush (a
count is the same on a CPU; the result line of a CPU run holds null)."""

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

HERD = "batching-10k.herd"
CLOSED = [HERD, "zipf-1m.saturate", "global-4.herd", "zipf-1m.calls100",
          "sharded-4.calls100", "store-1m.calls100"]
OPEN = ["batching-10k.steady", "zipf-1m.steady"]
NEW = ["calls_per_flush.closed", "calls_per_flush.open"]
SUM = "gubernator_engine_flush_calls_sum"
COUNT = "gubernator_engine_flush_calls_count"
# what the parent already exposes beside it
PARENT = {"gubernator_engine_flush_launches_sum": 40.0,
          "gubernator_engine_flush_launches_count": 40.0}


def read(name, before, after):
    m = manifest.load(ROOT)
    path = manifest.reader_path(ROOT, manifest.bench_dir(m), name)
    return readers.read(path, readers.Context(
        before=before, after=after, device={}, phases={}, generator={},
        trace=None, conf={}, traffic={"callers": 100}, table={},
        items_answered=0, root=ROOT))


def test_the_manifest_is_sound_and_the_new_names_end_its_list():
    m = manifest.load(ROOT)
    manifest.check(m, ROOT)
    names = [p["name"] for p in m["per_layer"]]
    # together, in their order, after PR 38's last; later PRs append after them
    at = names.index(NEW[0])
    assert names[at:at + 2] == NEW
    assert names[at - 1] == "ici_tick_read_ms"


@pytest.mark.parametrize("name", NEW)
def test_reader_against_a_synthetic_pair_of_scrapes(name):
    before = dict(PARENT, **{SUM: 100.0, COUNT: 90.0})
    after = dict(PARENT, **{SUM: 100.0 + 420.0, COUNT: 90.0 + 60.0})
    assert read(name, before, after) == pytest.approx(7.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_over_the_parents_series_and_between_equal_scrapes(name):
    assert read(name, PARENT, dict(PARENT)) is None
    both = dict(PARENT, **{SUM: 5.0, COUNT: 5.0})
    assert read(name, both, both) is None


@pytest.mark.parametrize("name,cells,moves", [
    (NEW[0], CLOSED, "decisions_per_s"), (NEW[1], OPEN, "call_p50_ms"),
])
def test_manifest_lists_each_where_its_end_to_end_metric_is_reported(
        name, cells, moves):
    m = manifest.load(ROOT)
    entry = {p["name"]: p for p in m["per_layer"]}[name]
    # the cells it was given when it came, then whatever later PRs appended
    # (a rule, not a list: a new cell breaks nothing); each reports `moves`
    assert entry["workloads"][:len(cells)] == cells
    e2e = {x["name"]: x for x in m["end_to_end"]}[moves]
    assert set(entry["workloads"]) <= set(e2e["workloads"])
    loops = {w["name"]: json.load(open(manifest.traffic_path(
        ROOT, manifest.bench_dir(m), w["traffic"]), encoding="utf-8"))["loop"]
        for w in m["workloads"]}
    assert {loops[c] for c in entry["workloads"]} == {name.rsplit(".", 1)[1]}
    assert (entry["moves"], entry["layer"]) == (moves, "engine host stage")
    assert entry["source"] == "program_counter"
    assert entry["unit"] == "calls/flush" and entry["better"] == "higher"
    path = manifest.reader_path(ROOT, manifest.bench_dir(m), name)
    with open(path, encoding="utf-8") as f:  # data, no reader code
        spec = json.load(f)
    assert spec["kind"] == "metrics_ratio"
    assert (spec["plus"], spec["per"]) == ([SUM], [COUNT])


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
def test_rehearsal_of_herd_prints_calls_per_flush(tree):
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", HERD, "--seed",
         "2147483739", "--seconds", "6", "--trace", "1", "--platform", "cpu"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=140)
    log = r.stdout[-6000:] + r.stderr[-2000:]
    assert r.returncode == 0, log
    printed = {}
    for line in r.stdout.splitlines():
        if line.startswith("per_layer "):
            name, _, rest = line[len("per_layer "):].partition(": ")
            printed[name] = rest.split(" ")[0]
    assert NEW[0] in printed and NEW[1] not in printed, log
    # a hundred callers of two items: calls do share flushes, and a
    # flush never holds more items than the narrowest launch has lanes
    assert 1.0 <= float(printed[NEW[0]]) <= 64.0, log
    assert float(printed["columnar_call_share"]) == 100.0, log
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, log
    assert result["metrics"][NEW[0]]["value"] is None  # a CPU run: counts print, the line holds null
