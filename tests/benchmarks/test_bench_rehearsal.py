"""Rehearsals of the benchmark end to end on the CPU: every configuration
through ``benchmarks/run.py --platform cpu`` at a tiny size, the controls
that have to come out not correct, the refusal to run without a chip, and a throw-away
configuration, traffic mix, cell and per-layer metric added as files plus
one entry each. A CPU run yields counts, never a time, a rate or a share:
every metric value in these result lines is null."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import psutil
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_cell(root, cell, *extra, seconds=3, seed=2147483700, timeout=110):
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    lines = r.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (ValueError, IndexError):
        result = None
    return r.returncode, result, r.stdout[-4000:] + r.stderr[-2000:]


def sound(rc, result, log):
    assert rc == 0 and result is not None, log
    assert result["correct"] is True, log
    assert result["failed"] == 0 and result["attempted"] > 0, log
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert result["metrics"], log
    assert all(v["value"] is None for v in result["metrics"].values()), log


@pytest.mark.deadline(120)
def test_rehearsal_batching_10k_herd():
    rc, result, log = run_cell(ROOT, "batching-10k.herd", "--trace", "0",
                               "--platform", "cpu")
    sound(rc, result, log)
    assert set(result["metrics"]) == {"decisions_per_s", "setup_s"}


@pytest.mark.deadline(120)
def test_rehearsal_batching_10k_steady_traced():
    rc, result, log = run_cell(ROOT, "batching-10k.steady", "--trace", "1",
                               "--platform", "cpu", seconds=4)
    sound(rc, result, log)
    # the CPU backend has no device plane: the trace readers find nothing to read
    assert "device_idle_share.open" not in result["metrics"]
    assert {"gen_late_p99_ms", "call_p99_ms", "edge_ms_per_call",
            "compile_s"} <= set(result["metrics"])


@pytest.mark.deadline(120)
def test_rehearsal_zipf_1m_saturate_at_a_tiny_size():
    rc, result, log = run_cell(ROOT, "zipf-1m.saturate", "--trace", "0",
                               "--platform", "cpu", "--keys", "20000")
    sound(rc, result, log)
    assert "evicted_keys" in log and "setup.mismatches: 0" in log


@pytest.mark.deadline(120)
@pytest.mark.parametrize("kind", ["double_apply", "stale_answer", "forget"])
def test_the_timed_path_broken_underneath_comes_out_not_correct(kind):
    """The control relay alters answers where the timed path produces them;
    the rest of the run is the harness's own (only the look for a chip is
    skipped by --platform cpu)."""
    rc, result, log = run_cell(ROOT, "batching-10k.herd", "--trace", "0",
                               "--platform", "cpu", "--control", kind)
    assert rc == 0 and result is not None, log
    assert result["correct"] is False, log
    assert "FAIL" in log


@pytest.mark.deadline(120)
def test_without_a_chip_there_is_no_result_line():
    """No --platform cpu: the server comes up on the CPU backend (the test
    environment pins JAX to it) and the run must refuse."""
    rc, result, log = run_cell(ROOT, "batching-10k.herd", "--trace", "0")
    assert rc != 0 and result is None, log
    assert "JAX found no accelerator" in log


@pytest.mark.deadline(120)
def test_a_freeze_longer_than_issue_23s_deadline_fails_no_call():
    """The driver's check of PR 23 met a freeze in which every `herd` call
    in flight passed the 5 s deadline the cell then had: 200 items failed
    in one run of twelve. With the cell's own deadline a server frozen for
    6 s in the window costs the run time and no answer."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    run = subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload", "batching-10k.herd",
         "--seed", "2147483701", "--seconds", "12", "--trace", "0",
         "--platform", "cpu"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    log = []
    try:
        for line in run.stdout:
            log.append(line)
            if line.startswith("setup:"):  # the window opens about a second on
                break
        time.sleep(3.0)
        daemon = [p for p in psutil.Process(run.pid).children(recursive=True)
                  if "gubernator_tpu.cmd.daemon" in " ".join(p.cmdline())]
        assert len(daemon) == 1, "".join(log)
        daemon[0].send_signal(signal.SIGSTOP)
        time.sleep(6.0)
        daemon[0].send_signal(signal.SIGCONT)
        log.extend(run.stdout)
        assert run.wait(timeout=90) == 0, "".join(log)
    finally:
        if run.poll() is None:
            for p in psutil.Process(run.pid).children(recursive=True):
                p.kill()
            run.kill()
    text = "".join(log)
    result = json.loads(text.strip().splitlines()[-1])
    gap = float(text.split("longest gap between replies: ")[1].split(" s")[0])
    assert 5.5 < gap < 9.0, text  # the freeze fell inside the window
    assert result["correct"] is True and result["failed"] == 0, text
    assert "failed calls by gRPC status: {}" in text


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A checkout with additions only: new files under the benchmark's
    directories and new entries in BENCHMARK.json; no file that was there
    is edited."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("gubernator_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), root / name)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        m = json.load(f)
    before = json.dumps(m, sort_keys=True)

    conf = json.load(open(root / "benchmarks/configs/batching-10k.json"))
    conf["name"] = "throwaway"
    conf["keyspace"].update(keys=300, algorithm="token", limit=50, duration_ms=60000)
    conf["preload"] = {"hits": 1}
    conf["probes"] = {"hottest": 5, "seeded": 100}
    json.dump(conf, open(root / "benchmarks/configs/throwaway.json", "w"))
    json.dump({
        "loop": "open", "rate_calls_per_s": 40.0,
        "arrivals": {"kind": "bursts", "calls": 4, "every_ms": 100},
        "items_per_call": {"1": 0.5, "3": 0.5}, "hits": 1, "workers": 1,
        "keys": {"distribution": "hotset", "hot_keys": 5, "hot_share": 0.5},
    }, open(root / "benchmarks/traffic/trickle.json", "w"))
    json.dump({"kind": "metrics_ratio", "scale": 1.0,
               "plus": ['gubernator_engine_flush_duration_count{path="columnar"}'],
               "per": ['gubernator_engine_flush_duration_count{path="columnar"}']},
              open(root / "benchmarks/metrics/throwaway_ratio.json", "w"))
    with open(root / "benchmarks/metrics/throwaway_reader.py", "w") as f:
        f.write("def read(ctx):\n"
                "    print('throwaway reader saw', ctx.traffic['loop'], flush=True)\n"
                "    return float(ctx.conf['keyspace']['keys'])\n")
    m["configs"].append({"name": "throwaway", "source": "none: a test's own",
                         "file": "benchmarks/configs/throwaway.json", "reduced": [],
                         "why": "shows that a configuration is a file"})
    m["workloads"].append({"name": "throwaway.trickle", "config": "throwaway",
                           "traffic": "trickle", "chips": 1,
                           "why": "shows that a traffic mix and a cell are files"})
    for e in m["end_to_end"]:
        if e["name"] == "call_p50_ms":
            e["workloads"].append("throwaway.trickle")
    for name in ("throwaway_ratio", "throwaway_reader"):
        m["per_layer"].append({"name": name, "unit": "x", "better": "higher",
                               "source": "program_counter", "layer": "service edge",
                               "moves": "call_p50_ms",
                               "workloads": ["throwaway.trickle"]})
    json.dump(m, open(root / "BENCHMARK.json", "w"))
    # nothing that was there changed: the old manifest is a subset of the new
    old = json.loads(before)
    assert all(w in m["workloads"] for w in old["workloads"])
    assert all(c in m["configs"] for c in old["configs"])
    return str(root)


@pytest.mark.deadline(120)
def test_a_configuration_traffic_cell_and_metric_are_files_plus_one_entry(tree):
    rc, result, log = run_cell(tree, "throwaway.trickle", "--trace", "1",
                               "--platform", "cpu", "--keys", "300", seconds=3)
    sound(rc, result, log)
    assert "throwaway reader saw open" in log
    assert "per_layer throwaway_reader: 300.0 x" in log
    assert "per_layer throwaway_ratio: 1.0 x" in log
    assert {"throwaway_ratio", "throwaway_reader"} <= set(result["metrics"])
    # bursts of 4 calls every 100 ms over 3 s, sizes 1 and 3 in equal shares
    assert "calls=120" in log and result["attempted"] == 240
