"""Local bench-result ledger and its regression gate (utils/ledger.py).

The reference's analog contract is its benchmark workflow artifact
(reference .github/workflows/on-pull-request.yml:87-99) — a bench that
doesn't produce a comparable artifact doesn't exist.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def led(tmp_path, monkeypatch):
    from gubernator_tpu.utils import ledger

    monkeypatch.setattr(
        ledger, "REPO_LEDGER", str(tmp_path / "repo" / "results.jsonl")
    )
    return ledger


def test_append_load(led):
    led.append(
        {"metric": "x (tpu, fused layout)", "value": 100.0,
         "unit": "decisions/s", "vs_baseline": 25.0},
        job="02_kernel_fused", mode="kernel", layout="fused", ts=2000.0,
    )
    led.append(
        {"metric": "engine (cpu, 10k keys)", "value": 50.0,
         "unit": "decisions/s", "vs_baseline": 12.0},
        job="05_engine", mode="engine", ts=1000.0,
    )
    recs = led.load()
    assert sum(1 for _ in open(led.REPO_LEDGER)) == 2
    # oldest first, platform inferred from the metric string
    assert [r["value"] for r in recs] == [50.0, 100.0]
    assert [r["platform"] for r in recs] == ["cpu", "tpu"]
    assert recs[1]["mode"] == "kernel" and recs[1]["layout"] == "fused"


def test_infer_platform(led):
    assert led.infer_platform("x (kernel, tpu, fused layout)") == "tpu"
    assert led.infer_platform("engine decisions/sec (cpu, 10k keys)") == "cpu"
    assert led.infer_platform("nothing here") == "unknown"


def _row(value, p99=None):
    r = {"metric": "x (tpu, fused layout)", "value": value,
         "unit": "decisions/s", "vs_baseline": 1.0}
    if p99 is not None:
        r["telemetry"] = {"flush_us": {"p50": 10.0, "p99": p99, "count": 8}}
    return r


def test_gate_flags_throughput_regression(led):
    led.append(_row(100.0), job="bench_child", mode="kernel",
               layout="fused", ts=1000.0)
    led.append(_row(79.0), job="bench_child", mode="kernel",
               layout="fused", ts=2000.0)  # 21% below best prior
    v = led.gate(mode="kernel", layout="fused")
    assert v["ok"] is False
    assert "throughput regression" in v["reason"]
    assert v["throughput_ratio"] == pytest.approx(0.79)
    assert v["current"]["value"] == 79.0 and v["best"]["value"] == 100.0
    # a looser explicit threshold passes the same ledger
    assert led.gate(mode="kernel", layout="fused", threshold=0.25)["ok"]


def test_gate_passes_within_threshold_env_override(led, monkeypatch):
    led.append(_row(100.0), job="bench_child", mode="kernel",
               layout="fused", ts=1000.0)
    led.append(_row(95.0), job="bench_child", mode="kernel",
               layout="fused", ts=2000.0)
    v = led.gate(mode="kernel", layout="fused")
    assert v["ok"] is True and v["reason"] == "within threshold"
    # GUBER_GATE_THRESHOLD is read at call time (GL004), not import
    monkeypatch.setenv("GUBER_GATE_THRESHOLD", "0.01")
    v = led.gate(mode="kernel", layout="fused")
    assert v["ok"] is False and v["threshold"] == 0.01


def test_gate_flags_p99_inflation(led):
    led.append(_row(100.0, p99=100.0), job="bench_child", mode="kernel",
               layout="fused", ts=1000.0)
    # throughput even improved — the latency gate still fires
    led.append(_row(101.0, p99=130.0), job="bench_child", mode="kernel",
               layout="fused", ts=2000.0)
    v = led.gate(mode="kernel", layout="fused")
    assert v["ok"] is False
    assert "p99 inflation" in v["reason"]
    assert v["p99_ratio"] == pytest.approx(1.3)


def test_gate_vacuous_and_platform_isolation(led):
    # empty ledger and single-row ledger both pass vacuously
    assert led.gate(mode="kernel")["ok"] is True
    led.append(_row(100.0), job="bench_child", mode="kernel",
               layout="fused", ts=1000.0)
    assert "vacuously" in led.gate(mode="kernel")["reason"]
    # a CPU smoke row must never gate against the TPU headline
    led.append(
        {"metric": "x (cpu, fused layout)", "value": 5.0,
         "unit": "decisions/s", "vs_baseline": 1.0},
        job="bench_child", mode="kernel", layout="fused", ts=2000.0,
    )
    v = led.gate(mode="kernel", layout="fused")
    assert v["ok"] is True and "vacuously" in v["reason"]


def test_bench_run_gate_prints_verdict(led, capsys):
    """bench.py --gate plumbing: _run_gate prints one GATE json line and
    returns the verdict bool the caller turns into the exit code."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import bench

    led.append(_row(100.0), job="bench_child", mode="kernel",
               layout="fused", ts=1000.0)
    led.append(_row(79.0), job="bench_child", mode="kernel",
               layout="fused", ts=2000.0)

    class Args:
        mode = "kernel"
        layout = "fused"
        layout_explicit = True
        gate_threshold = None

    assert bench._run_gate(Args) is False
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines() if l.startswith("GATE "))
    verdict = json.loads(line[len("GATE "):])
    assert verdict["ok"] is False
    assert "throughput regression" in verdict["reason"]
    # a generous threshold flips it
    Args.gate_threshold = 0.5
    assert bench._run_gate(Args) is True


def test_infer_mode_layout_mesh_keys():
    """mesh_ab ledger keys (ISSUE 15): the comparison row keys as
    mesh_ab, per-width cell rows as mesh, and the longest-prefix
    ordering keeps bench_mesh_ab_n8 from keying as ici or mesh."""
    from gubernator_tpu.utils import ledger

    assert ledger.infer_mode_layout("bench_mesh_ab") == ("mesh_ab", "")
    assert ledger.infer_mode_layout("bench_mesh_ab_n8") == ("mesh_ab", "")
    # job 39 keys as "mesh" (the scaling cells), with no layout pinned
    # — comparable rows match on platform alone.
    assert ledger.infer_mode_layout("39_mesh_scaling") == ("mesh", "")
    # the pre-existing ici mode must not swallow mesh rows
    assert ledger.infer_mode_layout("bench_ici_sync") == ("ici", "")
