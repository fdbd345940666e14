"""``BENCHMARK.json`` and the files it names, checked before anything
starts: a manifest that breaks the contract fails here, not on the chip.

Of a configuration's own file two optional keys are checked
(``check_config``), both off by default:

    "preload":  {"hits": 1, "via": "grpc" | "snapshot"}
    "shutdown": {"saved": "checked"}

``via`` says how the keys get into the table before the window: ``grpc``
(the default) is run.py's own loop of 1,000-key calls; ``snapshot`` is a
checkpoint file of the reference's state (snapshot.py) that the
configuration's launcher loads at start, refused for a keyspace with leaky
keys (the file carries upstream's fields, a leaky remainder is the
program's own fixed-point form). With ``shutdown.saved`` the run reads back
what the server's Loader saved at shutdown (check.py, stage 4); without
the key nothing is read after the exit."""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")


class ManifestError(Exception):
    pass


def _need(cond, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _line(text, what: str) -> None:
    _need(isinstance(text, str) and 1 <= len(text) <= 200
          and "\n" not in text and "\t" not in text,
          f"{what}: 1 to 200 characters on one line")


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def bench_dir(manifest: dict) -> str:
    """The first of `paths` holds configs/, traffic/ and metrics/."""
    return manifest["paths"][0]


def check(m: dict, root: str) -> None:
    _need(set(m) == {"command", "paths", "run_seconds", "configs",
                     "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json: exactly the contract's seven keys")
    _need(1 <= len(m["paths"]) <= 16, "paths: 1 to 16 directories")
    for p in m["paths"]:
        _need(re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and not p.startswith("/")
              and ".." not in p.split("/"), f"path {p!r}")
    _need(1 <= len(m["command"]) <= 32, "command: at most 32 words")
    for w in m["command"]:
        _line(w, "command word")
    _need(isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51,
          "run_seconds: a whole number from 1 to 51")

    base = bench_dir(m)
    configs = {}
    _need(1 <= len(m["configs"]) <= 24, "configs: 1 to 24")
    for c in m["configs"]:
        _need(set(c) == {"name", "source", "file", "reduced", "why"},
              f"config {c.get('name')}: keys")
        _need(NAME.match(c["name"]) and c["name"] not in configs,
              f"config name {c['name']!r}")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        _need(any(c["file"].startswith(p + "/") for p in m["paths"]),
              f"config file {c['file']} outside paths")
        _need(os.path.isfile(os.path.join(root, c["file"])),
              f"config file {c['file']} missing")
        with open(os.path.join(root, c["file"]), encoding="utf-8") as f:
            check_config(json.load(f), c["name"])
        _need(len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"]),
              "reduced: at most 16 names")
        configs[c["name"]] = c
    _need(len({c["file"] for c in m["configs"]}) == len(configs),
          "two configurations share a file")

    cells = {}
    _need(1 <= len(m["workloads"]) <= 24, "workloads: 1 to 24")
    for w in m["workloads"]:
        _need(set(w) == {"name", "config", "traffic", "chips", "why"},
              f"workload {w.get('name')}: keys")
        _need(NAME.match(w["name"]) and w["name"] not in cells,
              f"workload name {w['name']!r}")
        _need(w["config"] in configs, f"{w['name']}: unknown config")
        _need(NAME.match(w["traffic"]), f"{w['name']}: traffic name")
        _need(w["chips"] in (1, 4), f"{w['name']}: chips is 1 or 4")
        _line(w["why"], "workload why")
        _need(traffic_path(root, base, w["traffic"]) is not None,
              f"{w['name']}: no traffic file {base}/traffic/{w['traffic']}.*")
        cells[w["name"]] = w
    pairs = {(w["config"], w["traffic"]) for w in m["workloads"]}
    _need(len(pairs) == len(cells), "a (config, traffic) pair appears twice")
    _need({w["config"] for w in m["workloads"]} == set(configs),
          "a configuration has no cell")
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    _need(four <= max(len(cells) // 2, 1),
          "more than half the cells (rounded down, at least one) ask for 4 chips")

    metrics = {}
    _need(1 <= len(m["end_to_end"]) <= 16, "end_to_end: 1 to 16")
    for e in m["end_to_end"]:
        _need(set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"},
              f"end_to_end {e.get('name')}: keys")
        _check_metric(e, metrics, cells)
        _need(e["source"] in ("host_clock", "device_trace"),
              f"{e['name']}: an end-to-end metric is taken by the benchmark")
        _need(0 < e["bound"] <= 0.25, f"{e['name']}: bound")
    _need("setup_s" in metrics and "workloads" not in metrics["setup_s"],
          "setup_s is reported by every cell")
    e2e = dict(metrics)
    names = list(cells)
    for w in cells:
        _need(any(w in cells_of(e, e2e, names) for n, e in e2e.items() if n != "setup_s"),
              f"cell {w} reports no end-to-end metric besides setup_s")
    _need(1 <= len(m["per_layer"]) <= 128, "per_layer: 1 to 128")
    for p in m["per_layer"]:
        _need(set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"},
              f"per_layer {p.get('name')}: keys")
        _check_metric(p, metrics, cells)
        _line(p["layer"], "layer")
        _need(p["moves"] in e2e, f"{p['name']}: moves an unknown metric")
        _need(set(cells_of(p, e2e, names)) <= set(cells_of(e2e[p["moves"]], e2e, names)),
              f"{p['name']}: a cell of it does not report {p['moves']}")
        _need(reader_path(root, base, p["name"]) is not None,
              f"{p['name']}: no reader {base}/metrics/{p['name']}.json|.py")
    for w in cells:
        _need(any(w in cells_of(p, e2e, names) for p in m["per_layer"]),
              f"cell {w} reports no per-layer metric")


def check_config(conf: dict, name: str = "config") -> None:
    """The two optional keys of a configuration's file (the module's doc)."""
    pre = conf.get("preload")
    if pre is not None:
        _need(isinstance(pre, dict) and set(pre) <= {"hits", "via"},
              f"{name}: preload holds `hits` and `via` alone")
        via = pre.get("via", "grpc")
        _need(via in ("grpc", "snapshot"),
              f"{name}: preload.via is \"grpc\" or \"snapshot\"")
        _need(via == "grpc" or conf.get("keyspace", {}).get("algorithm") == "token",
              f"{name}: preload.via snapshot needs a keyspace of token keys")
    _need(conf.get("shutdown", {"saved": "checked"}) == {"saved": "checked"},
          f"{name}: shutdown is {{\"saved\": \"checked\"}} or absent")
    _need("shutdown" not in conf or "consistency" not in conf,
          f"{name}: shutdown.saved is checked against probes that are exact")


def cells_of(metric: dict, e2e: dict, all_cells: list) -> list:
    """The cells that report `metric`: its `workloads`; without that key
    every cell (end-to-end) or every cell that reports the end-to-end
    metric it moves (per-layer)."""
    if "workloads" in metric:
        return metric["workloads"]
    if "moves" in metric:
        return cells_of(e2e[metric["moves"]], e2e, all_cells)
    return all_cells


def _check_metric(e: dict, seen: dict, cells: dict) -> None:
    _need(NAME.match(e["name"]) and e["name"] not in seen,
          f"metric name {e['name']!r}")
    _need(UNIT.match(e["unit"]), f"{e['name']}: unit {e['unit']!r}")
    _need(e["better"] in ("lower", "higher"), f"{e['name']}: better")
    _need(e["source"] in SOURCES, f"{e['name']}: source")
    if "workloads" in e:
        _need(e["workloads"] and set(e["workloads"]) <= set(cells),
              f"{e['name']}: unknown cell")
    seen[e["name"]] = e


def traffic_path(root: str, base: str, name: str):
    for ext in TRAFFIC_EXT:
        p = os.path.join(root, base, "traffic", name + ext)
        if os.path.isfile(p):
            return p
    return None


def reader_path(root: str, base: str, name: str):
    for ext in (".json", ".py"):
        p = os.path.join(root, base, "metrics", name + ext)
        if os.path.isfile(p):
            return p
    return None


def metrics_of(m: dict, cell: str, kind: str) -> list:
    """The cell's `end_to_end` or `per_layer` metrics."""
    e2e = {e["name"]: e for e in m["end_to_end"]}
    names = [w["name"] for w in m["workloads"]]
    return [x for x in m[kind] if cell in cells_of(x, e2e, names)]
