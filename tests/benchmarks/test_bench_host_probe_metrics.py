"""The per-layer metrics of ISSUE 38: what a call spends outside every
handler stage, the event loop's lag, the interpreter lock's wait, a
call's CPU, and the sync tick in three parts. Each reader against a
hand-made pair of scrapes, a scrape of the parent commit giving nothing
and raising nothing, the manifest listing each where its end-to-end
metric is reported, and the CPU rehearsal of `herd` printing a value for
every new name (a CPU run yields counts, never a time: the result line
holds null for each)."""

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

HERD, STEADY, GLOBAL4 = "batching-10k.herd", "batching-10k.steady", "global-4.herd"
CLOSED = [HERD, "zipf-1m.saturate", GLOBAL4, "zipf-1m.calls100",
          "sharded-4.calls100", "store-1m.calls100"]
COLUMNAR = [c for c in CLOSED if c != "zipf-1m.saturate"]
OPEN = [STEADY, "zipf-1m.steady"]
OUTSIDE = "outside_handler_ms_per_call.closed"
PAIRS = ("loop_lag_ms", "interpreter_wait_us", "call_cpu_ms_per_call",
         "call_on_cpu_share")
TICK = ("ici_tick_lock_wait_ms", "ici_tick_launch_ms", "ici_tick_read_ms")
NEW = [OUTSIDE] + [f"{p}{sfx}" for p in PAIRS for sfx in (".closed", ".open")] \
    + list(TICK)


def stage(kind, path, name):
    return f'gubernator_call_stage_duration_{kind}{{path="{path}",stage="{name}"}}'


def edge(path, reason):
    return f'gubernator_edge_calls{{path="{path}",reason="{reason}"}}'


def tick(kind, name):
    return f'gubernator_ici_tick_stage_duration_{kind}{{stage="{name}"}}'


def cpu(series, kind, path="columnar"):
    return f'gubernator_{series}_{kind}{{path="{path}"}}'


# What the parent commit already exposes, and what it adds between the
# scrapes: 2 s of clock, 40 columnar and 10 object calls, 3.2 s of stages.
PARENT_BEFORE = {
    "gubernator_engine_clock_seconds": 100.0,
    edge("columnar", ""): 7.0, edge("object", "waves"): 1.0,
    edge("object", "error"): 0.0,
    stage("sum", "columnar", "executor_wait"): 1.0,
    stage("sum", "columnar", "engine"): 2.0,
    stage("count", "columnar", "engine"): 7.0,
    stage("sum", "object", "engine_wait"): 0.5,
    "gubernator_ici_tick_duration_sum": 1.0,
    "gubernator_ici_tick_duration_count": 10.0,
}
PARENT_ADDED = {
    "gubernator_engine_clock_seconds": 2.0,
    edge("columnar", ""): 40.0, edge("object", "waves"): 10.0,
    stage("sum", "columnar", "executor_wait"): 0.2,
    stage("sum", "columnar", "engine"): 2.0,
    stage("count", "columnar", "engine"): 40.0,
    stage("sum", "object", "engine_wait"): 1.0,
    "gubernator_ici_tick_duration_sum": 0.5,
    "gubernator_ici_tick_duration_count": 20.0,
}
# ... and what this PR's program adds to that.
CHANGE_ADDED = {
    "gubernator_loop_lag_seconds_sum": 0.8,
    "gubernator_loop_lag_seconds_count": 200.0,
    "gubernator_interpreter_wait_seconds_sum": 0.05,
    "gubernator_interpreter_wait_seconds_count": 200.0,
    cpu("call_cpu_seconds", "sum"): 0.009,
    cpu("call_cpu_seconds", "count"): 3.0,
    cpu("call_cpu_wall_seconds", "sum"): 0.060,
    cpu("call_cpu_wall_seconds", "count"): 3.0,
    cpu("call_cpu_seconds", "sum", "object"): 0.5,  # never read
    cpu("call_cpu_seconds", "count", "object"): 1.0,
    tick("sum", "lock_wait"): 0.30, tick("count", "lock_wait"): 20.0,
    tick("sum", "launch"): 0.12, tick("count", "launch"): 20.0,
    tick("sum", "read"): 0.06, tick("count", "read"): 20.0,
}
CALLERS = 100
WANT = {
    # 100 callers x 2 s / 50 calls = 4 s in flight; 3.2 s / 50 in stages
    OUTSIDE: 1000 * (CALLERS * 2.0 - 3.2) / 50,
    "loop_lag_ms": 1000 * 0.8 / 200,
    "interpreter_wait_us": 1e6 * 0.05 / 200,
    "call_cpu_ms_per_call": 1000 * 0.009 / 3,
    "call_on_cpu_share": 100 * 0.009 / 0.060,
    "ici_tick_lock_wait_ms": 1000 * 0.30 / 20,
    "ici_tick_launch_ms": 1000 * 0.12 / 20,
    "ici_tick_read_ms": 1000 * 0.06 / 20,
}


def base(name):
    return name.rsplit(".", 1)[0] if name.endswith(".open") or (
        name.endswith(".closed") and name != OUTSIDE) else name


def scrapes(change: bool):
    before = dict(PARENT_BEFORE)
    added = dict(PARENT_ADDED)
    if change:
        before.update(dict.fromkeys(CHANGE_ADDED, 0.0))
        added.update(CHANGE_ADDED)
    after = {k: before[k] + added.get(k, 0.0) for k in before}
    return before, after


def read(name, before, after, traffic=None):
    m = manifest.load(ROOT)
    path = manifest.reader_path(ROOT, manifest.bench_dir(m), name)
    return readers.read(path, readers.Context(
        before=before, after=after, device={}, phases={}, generator={},
        trace=None, conf={},
        traffic={"callers": CALLERS} if traffic is None else traffic,
        table={}, items_answered=0, root=ROOT))


def test_the_manifest_is_sound_and_the_new_names_end_its_list():
    m = manifest.load(ROOT)
    manifest.check(m, ROOT)
    names = [p["name"] for p in m["per_layer"]]
    first = names.index(OUTSIDE)
    assert names[first - 1] == "store_crossings_per_wave"  # PR 37's last
    assert names[first:first + len(NEW)] == NEW


@pytest.mark.parametrize("name", NEW)
def test_reader_against_a_synthetic_pair_of_scrapes(name):
    before, after = scrapes(change=True)
    assert read(name, before, after) == pytest.approx(WANT[base(name)])


@pytest.mark.parametrize("name", NEW)
def test_reader_over_the_parents_series(name):
    """Laid over the parent commit the readers of the new series find
    nothing and raise nothing. The time outside the handler is reckoned
    from three series PR 24 already had, so it reads there too."""
    before, after = scrapes(change=False)
    got = read(name, before, after)
    if name == OUTSIDE:
        assert got == pytest.approx(WANT[OUTSIDE])
    else:
        assert got is None


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_between_two_equal_scrapes(name):
    _, after = scrapes(change=True)
    assert read(name, after, after) is None


@pytest.mark.parametrize("traffic", [{}, {"callers": 0}, {"loop": "open"}])
def test_outside_handler_needs_the_closed_loops_callers(traffic):
    before, after = scrapes(change=True)
    assert read(OUTSIDE, before, after, traffic) is None


def test_outside_handler_without_the_timeline_gives_nothing():
    # a program older than PR 24: a clock, no stages and no edge calls
    old = {"gubernator_engine_clock_seconds": 5.0}
    assert read(OUTSIDE, {"gubernator_engine_clock_seconds": 3.0}, old) is None


@pytest.mark.parametrize("name", NEW)
def test_manifest_lists_each_where_its_end_to_end_metric_is_reported(name):
    m = manifest.load(ROOT)
    entry = {p["name"]: p for p in m["per_layer"]}[name]
    if name in TICK:
        cells, moves, layer = [GLOBAL4], "decisions_per_s", "GLOBAL sync"
    else:
        is_open = name.endswith(".open")
        moves = "call_p50_ms" if is_open else "decisions_per_s"
        columnar = base(name) in ("call_cpu_ms_per_call", "call_on_cpu_share")
        cells = OPEN if is_open else (COLUMNAR if columnar else CLOSED)
        layer = ("service edge" if base(name) in (OUTSIDE, "loop_lag_ms")
                 else "engine host stage")
    # the cells it was given when it came, then whatever later PRs appended
    # (a rule, not a list: a new cell breaks nothing); each reports `moves`
    assert entry["workloads"][:len(cells)] == cells
    e2e = {x["name"]: x for x in m["end_to_end"]}[moves]
    assert set(entry["workloads"]) <= set(e2e["workloads"])
    assert entry["moves"] == moves and entry["layer"] == layer
    assert entry["source"] == "program_span"
    assert entry["better"] == (
        "higher" if base(name) == "call_on_cpu_share" else "lower")
    assert entry["unit"] == {"interpreter_wait_us": "us",
                             "call_on_cpu_share": "%"}.get(base(name), "ms")
    path = manifest.reader_path(ROOT, manifest.bench_dir(m), name)
    if name == OUTSIDE:
        assert path.endswith(".py")
    else:  # data, no reader code
        with open(path, encoding="utf-8") as f:
            assert json.load(f)["kind"] == "metrics_ratio"


def test_the_columnar_cells_are_edge_waits_own_list():
    m = manifest.load(ROOT)
    by_name = {p["name"]: p for p in m["per_layer"]}
    assert by_name["call_cpu_ms_per_call.closed"]["workloads"] == by_name[
        "edge_wait_ms_per_call.closed"]["workloads"]


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
def test_rehearsal_prints_a_value_for_every_new_name(tree):
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", HERD, "--seed",
         "2147483738", "--seconds", "6", "--trace", "1", "--platform", "cpu"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=140)
    log = r.stdout[-6000:] + r.stderr[-2000:]
    assert r.returncode == 0, log
    m = manifest.load(ROOT)
    mine = [x["name"] for x in manifest.metrics_of(m, HERD, "per_layer")
            if x["name"] in NEW]
    assert sorted(mine) == sorted(n for n in NEW if n.endswith(".closed"))
    printed = {}
    for line in r.stdout.splitlines():
        if line.startswith("per_layer "):
            name, _, rest = line[len("per_layer "):].partition(": ")
            printed[name] = rest.split(" ")[0]
    for n in mine:
        assert n in printed and printed[n] != "None", (n, log)
    assert 0.0 < float(printed["call_on_cpu_share.closed"]) <= 100.0
    float(printed[OUTSIDE])  # a number; its sign is the chip's to say
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, log
    assert all(result["metrics"][n]["value"] is None for n in mine)
