"""The two per-layer metrics that count a wave's crossings of the
host-device boundary (PR 25): ``h2d_arrays_per_wave`` and
``d2h_reads_per_wave`` against a hand-made pair of scrapes, a program
without the counter (the parent commit) giving nothing, and the CPU
rehearsal of each cell printing 1 for the name it lists. Counts, so a
CPU run may print them; the result line of a CPU run still holds null."""

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
H2D, D2H = "h2d_arrays_per_wave", "d2h_reads_per_wave"
WAVES = "gubernator_engine_flush_waves_sum"


def transfers(direction):
    return f'gubernator_engine_wave_transfers{{direction="{direction}"}}'


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


@pytest.mark.parametrize("name,direction,per_wave", [
    (H2D, "h2d", 1.0), (D2H, "d2h", 1.0),
    # what the parent's program would have counted, had it had the counter
    (H2D, "h2d", 15.0), (D2H, "d2h", 8.0)])
def test_reader_against_a_synthetic_pair_of_scrapes(name, direction, per_wave):
    before = {transfers("h2d"): 40.0, transfers("d2h"): 40.0, WAVES: 40.0}
    after = dict(before)
    after[WAVES] += 70.0  # two 32-wave flushes and six single waves
    after[transfers(direction)] += 70.0 * per_wave
    assert readers.read(reader(name), ctx(before, after)) == pytest.approx(per_wave)


@pytest.mark.parametrize("name", [H2D, D2H])
def test_reader_gives_nothing_where_the_program_lacks_the_counter(name):
    old = {WAVES: 3.0}  # the parent commit counts waves and no transfers
    assert readers.read(reader(name), ctx({WAVES: 1.0}, old)) is None
    # and nothing where no wave was dispatched between the scrapes
    idle = {WAVES: 3.0, transfers("h2d"): 3.0, transfers("d2h"): 3.0}
    assert readers.read(reader(name), ctx(idle, dict(idle))) is None


def test_manifest_lists_each_where_its_end_to_end_metric_is_reported():
    m = manifest.load(ROOT)
    manifest.check(m, ROOT)
    names = [p["name"] for p in m["per_layer"]]
    # appended after PR 24's names (the last of those that are left); later
    # PRs append after these two
    assert names[names.index("columnar_call_share") + 1:][:2] == [H2D, D2H]
    by_name = {p["name"]: p for p in m["per_layer"]}
    assert by_name[H2D]["workloads"][:2] == [HERD, SATURATE]
    assert by_name[H2D]["moves"] == "decisions_per_s"
    assert by_name[H2D]["layer"] == "engine host stage"
    assert by_name[D2H]["workloads"] == [STEADY]
    assert by_name[D2H]["moves"] == "call_p50_ms"
    assert by_name[D2H]["layer"] == "transfers"
    for n in (H2D, D2H):
        assert by_name[n]["better"] == "lower"
        assert by_name[n]["source"] == "program_counter"


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
@pytest.mark.parametrize("cell,name,extra", [
    (HERD, H2D, ()), (STEADY, D2H, ()), (SATURATE, H2D, ("--keys", "20000"))])
def test_rehearsal_counts_one_crossing_a_wave(tree, cell, name, extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "2147483721", "--seconds", "6", "--trace", "1", "--platform", "cpu", *extra],
        cwd=tree, env=env, capture_output=True, text=True, timeout=140)
    log = r.stdout[-6000:] + r.stderr[-2000:]
    assert r.returncode == 0, log
    printed = {}
    for line in r.stdout.splitlines():
        if line.startswith("per_layer "):
            key, _, rest = line[len("per_layer "):].partition(": ")
            printed[key] = rest.split(" ")[0]
    # every launch between the two scrapes crossed once each way: one wave a
    # launch on herd and steady (a scrape between a flush's count and the next
    # line of the exposition may miss one flush of the rehearsal's twenty);
    # since PR 35 a run of waves is one operand and one launch, so saturate's
    # ~65 waves a flush read launches / waves: far under 1 once the stacked
    # shape is compiled, 1 while every wave is still its own launch
    want = 1.0
    if cell == SATURATE:
        want = float(printed["launches_per_flush"]) / float(printed["waves_per_flush"])
        assert 0.0 < want <= 1.0, log
    assert name in printed and float(printed[name]) == pytest.approx(want, abs=0.06), log
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, log
    assert result["metrics"][name]["value"] is None
