"""Local benchmark-result ledger and its regression gate.

An append-only JSONL file, <repo>/bench_results/results.jsonl (made at
run time, git-ignored), that `bench.py` and the soak jobs under
tools/jobs/ append their RESULT rows to, and that `gate()` reads to
compare a fresh row with the best prior comparable one. It is a
developer's local record: the numbers that enter PERF.md come from the
driver's PERF_LEDGER.jsonl, not from here.

Records: {ts, iso, job, mode, layout, platform, metric, value, unit,
vs_baseline[, telemetry]}. `mode`/`layout` mirror bench.py's CLI;
`telemetry` (when the bench ran an engine) carries flush-latency
p50/p99 and the wave-count histogram summary so the ledger tracks
distribution shape, not just means.
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Any

# guberlint: allow-import-env -- bench ledger path is process-constant tooling, not daemon --config
REPO_LEDGER = os.environ.get("GUBER_REPO_LEDGER") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "bench_results",
    "results.jsonl",
)


def _iso(ts: float) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(ts))


def infer_platform(metric: str) -> str:
    m = re.search(r"[(,]\s*(tpu|cpu|gpu)\b", metric)
    return m.group(1) if m else "unknown"


def append(
    result: dict[str, Any],
    *,
    job: str = "",
    mode: str = "",
    layout: str = "",
    platform: str = "",
    ts: float | None = None,
) -> dict[str, Any]:
    """Append one bench result (a bench.py JSON dict) to the ledger.
    Best-effort: a read-only repo checkout must not break a measurement."""
    ts = time.time() if ts is None else ts
    rec = {
        "ts": round(ts, 3),
        "iso": _iso(ts),
        "job": job,
        "mode": mode,
        "layout": layout,
        "platform": platform or infer_platform(str(result.get("metric", ""))),
        **{k: result.get(k) for k in ("metric", "value", "unit", "vs_baseline")},
    }
    if "telemetry" in result:
        # Distribution shape (flush p50/p99, wave-count histogram) rides
        # along so results.jsonl tracks shape, not just means.
        rec["telemetry"] = result["telemetry"]
    try:
        os.makedirs(os.path.dirname(REPO_LEDGER), exist_ok=True)
        with open(REPO_LEDGER, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass
    return rec


def load() -> list[dict[str, Any]]:
    """All records, oldest first."""
    recs: list[dict[str, Any]] = []
    try:
        with open(REPO_LEDGER) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return sorted(recs, key=lambda r: r.get("ts") or 0)


def _telemetry_p99(rec: dict[str, Any]) -> float | None:
    """Flush-latency p99 (µs) from a record's telemetry blob, if any.
    Matches the blob bench.py's _engine_telemetry writes: telemetry.
    flush_us.p99."""
    tel = rec.get("telemetry")
    if not isinstance(tel, dict):
        return None
    fu = tel.get("flush_us")
    if isinstance(fu, dict):
        v = fu.get("p99")
        if isinstance(v, (int, float)) and v > 0:
            return float(v)
    return None


def gate(
    *,
    job: str = "",
    mode: str = "",
    layout: str = "",
    platform: str = "",
    threshold: float | None = None,
) -> dict[str, Any]:
    """Perf regression gate (ROADMAP item 5): compare the FRESHEST ledger
    row against the BEST prior row for the same (job, mode, layout,
    platform) tuple. Returns a verdict dict:

      {ok, reason, current, best, threshold, throughput_ratio, p99_ratio}

    Fails (ok=False) when the fresh row's value drops more than
    `threshold` below the best prior value, or when its telemetry flush
    p99 inflates more than `threshold` above the best prior row's p99.
    A ledger with fewer than two matching rows passes vacuously — the
    gate protects against regressions, it doesn't block first runs.

    `threshold` resolution: explicit arg, else GUBER_GATE_THRESHOLD
    (read at call time, not import — GL004), else 0.15.
    """
    if threshold is None:
        env = os.environ.get("GUBER_GATE_THRESHOLD")
        threshold = float(env) if env else 0.15
    rows = [
        r
        for r in load()
        if r.get("value")
        and (not job or r.get("job") == job)
        and (not mode or r.get("mode") == mode)
        and (not layout or not r.get("layout") or r.get("layout") == layout)
        and (not platform or r.get("platform") == platform)
    ]
    verdict: dict[str, Any] = {
        "ok": True,
        "reason": "",
        "threshold": threshold,
        "current": None,
        "best": None,
        "throughput_ratio": None,
        "p99_ratio": None,
    }
    if not rows:
        verdict["reason"] = "no matching rows; gate passes vacuously"
        return verdict
    current = rows[-1]  # load() is oldest-first
    # Priors must be comparable to the fresh row: same platform always
    # (a CPU smoke must never gate against a TPU headline), and same
    # layout when the caller didn't already pin one.
    cur_plat = current.get("platform")
    cur_layout = current.get("layout")
    prior = [
        r
        for r in rows[:-1]
        if (not cur_plat or r.get("platform") == cur_plat)
        and (
            layout
            or not cur_layout
            or not r.get("layout")
            or r.get("layout") == cur_layout
        )
    ]
    if not prior:
        verdict["reason"] = "no comparable prior rows; gate passes vacuously"
        verdict["current"] = current
        return verdict
    best = max(prior, key=lambda r: float(r.get("value") or 0))
    verdict["current"] = current
    verdict["best"] = best
    cur_v = float(current.get("value") or 0)
    best_v = float(best.get("value") or 0)
    if best_v > 0:
        ratio = cur_v / best_v
        verdict["throughput_ratio"] = round(ratio, 4)
        if ratio < 1.0 - threshold:
            verdict["ok"] = False
            verdict["reason"] = (
                f"throughput regression: {cur_v:.6g} is "
                f"{(1.0 - ratio) * 100:.1f}% below best prior {best_v:.6g} "
                f"(threshold {threshold * 100:.0f}%)"
            )
            return verdict
    cur_p99 = _telemetry_p99(current)
    # p99 baseline: the best prior row's p99 when it has one, else the
    # smallest prior p99 — a row without telemetry shouldn't exempt the
    # fresh run from the latency gate.
    best_p99 = _telemetry_p99(best)
    if best_p99 is None:
        p99s = [p for p in (_telemetry_p99(r) for r in prior) if p]
        best_p99 = min(p99s) if p99s else None
    if cur_p99 is not None and best_p99 is not None:
        ratio = cur_p99 / best_p99
        verdict["p99_ratio"] = round(ratio, 4)
        if ratio > 1.0 + threshold:
            verdict["ok"] = False
            verdict["reason"] = (
                f"p99 inflation: {cur_p99:.6g}s is "
                f"{(ratio - 1.0) * 100:.1f}% above best prior {best_p99:.6g}s "
                f"(threshold {threshold * 100:.0f}%)"
            )
            return verdict
    verdict["reason"] = "within threshold"
    return verdict


_MODE_FROM_JOB = re.compile(
    # order matters: longest-prefix first (mesh_ab before mesh, ici
    # after mesh so bench_mesh_ab_n8 never keys as ici). Every job in
    # tools/jobs/ must key to exactly one of these modes — guberlint
    # GL016 pins the parity (a job whose name matches nothing would
    # ledger with mode="" and silently fall out of gate() baselines).
    r"(kernel10m|kernel|engine_ab|engine|server|global|latency"
    r"|edge|mesh_ab|mesh|ici|paged_table|table_census|lease_soak"
    r"|admission_soak|slo_soak|crash_soak|overload_soak|chaos_soak"
    r"|consistency_soak"
    r"|sanity|device_observatory|rolling_restart)"
)
_LAYOUT_FROM_JOB = re.compile(r"(fused|wide)")


def infer_mode_layout(job: str, metric: str = "") -> tuple[str, str]:
    """Best-effort (mode, layout) from a job name, falling back to the
    metric string."""
    m = _MODE_FROM_JOB.search(job) or _MODE_FROM_JOB.search(metric)
    lay = _LAYOUT_FROM_JOB.search(job) or _LAYOUT_FROM_JOB.search(metric)
    return (m.group(1) if m else "", lay.group(1) if lay else "")
