"""What PR 48 gave the harness, piece by piece and without a server: the
reference's export against the per-key evaluation, the snapshot file and the
launcher's file Loader there and back, stage 4's rows (`check_saved`) sound
and each with a failing twin, the load row's allowance, the two keys of a
configuration's file as `manifest.check` holds them, the two snapshot
controls, and what `BENCHMARK.json` says of the new cell."""

import copy
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import check, control, manifest, snapshot, traffic  # noqa: E402
from benchmarks.reference import oracle as ref  # noqa: E402

CELL, TWIN = "loader-1m.calls100", "zipf-1m.calls100"
T_PIN = 1_790_000_000_000


def config(name):
    with open(os.path.join(ROOT, "benchmarks/configs", name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def keyspace(n=1000, limit=100, rules=(), behavior=()):
    return traffic.Keyspace.from_config({"keyspace": {
        "keys": n, "limit": limit, "duration_ms": 3_600_000, "algorithm": "token",
        "behavior": list(behavior), "behavior_of_keys": list(rules)}}, 7)


# ---- the reference's export -------------------------------------------------------


@pytest.mark.parametrize("hits,rules", [
    (1, ()),
    (5, ({"one_in": 4, "behavior": ["DRAIN_OVER_LIMIT"]},)),
    (100, ({"one_in": 3, "behavior": ["RESET_REMAINING"]},
           {"one_in": 5, "behavior": ["DRAIN_OVER_LIMIT"]})),
    (101, ({"one_in": 2, "behavior": ["DRAIN_OVER_LIMIT"]},)),  # over the limit at once
], ids=["one-hit", "drain-keys", "two-rules-used-up", "first-request-refused"])
def test_one_evaluation_a_class_of_key_is_the_per_key_evaluation_on_1000_keys(hits, rules):
    ks = keyspace(1000, rules=rules)
    cols = snapshot.preload_rows(ks, hits, T_PIN)
    keys = snapshot.hash_keys(ks)
    one_by_one = ref.Reference()
    for k in range(ks.n):
        one_by_one.get_rate_limits([ks.request(k, hits, created_at=T_PIN)], T_PIN)
    want = one_by_one.export()
    assert [r["key"] for r in want] == keys
    for f in snapshot.FIELDS:
        assert cols[f].tolist() == [r[f] for r in want], f
    assert len({len(v) for v in cols.values()}) == 1 and cols["limit"].dtype == np.int64


def test_the_export_is_upstreams_cache_item_and_refuses_a_leaky_bucket():
    r = ref.Reference()
    r.get_rate_limits([ref.Request(name="n", unique_key="k", hits=3, limit=10,
                                   duration=5000, created_at=T_PIN)], T_PIN + 7)
    assert r.export() == [{
        "key": "n_k", "algorithm": ref.TOKEN_BUCKET, "expire_at": T_PIN + 5000,
        "status": ref.UNDER_LIMIT, "limit": 10, "duration": 5000, "remaining": 7,
        "created_at": T_PIN}]
    r.get_rate_limits([ref.Request(name="n", unique_key="l", hits=1, limit=10,
                                   duration=5000, algorithm=ref.LEAKY_BUCKET)], T_PIN)
    with pytest.raises(ValueError):
        r.export()


# ---- the file and the launcher's Loader ---------------------------------------------


def test_snapshot_file_there_and_back_and_a_broken_one_says_so(tmp_path):
    ks = keyspace(300)
    keys, cols = snapshot.hash_keys(ks), snapshot.preload_rows(ks, 2, T_PIN)
    path = str(tmp_path / "in.npz")
    snapshot.write(path, keys, cols)
    assert sorted(os.listdir(tmp_path)) == ["in.npz"]  # the temporary file is gone
    got_keys, got = snapshot.read(path)
    assert got_keys == keys and all(got[f].tolist() == cols[f].tolist()
                                    for f in snapshot.FIELDS)
    snapshot.write(path, [], {f: [] for f in snapshot.FIELDS})
    assert snapshot.read(path)[0] == []
    with open(path, "wb") as f:
        f.write(b"PK\x03\x04 half a file")
    for broken in (path, str(tmp_path / "absent.npz")):
        with pytest.raises(ValueError):
            snapshot.read(broken)


def test_file_loader_round_trip_rows_go_in_and_out_as_they_are(tmp_path):
    from benchmarks.loader_daemon import FileLoader

    ks = keyspace(500, rules=({"one_in": 4, "behavior": ["DRAIN_OVER_LIMIT"]},))
    keys, cols = snapshot.hash_keys(ks), snapshot.preload_rows(ks, 3, T_PIN)
    cols["remaining"][::7] = 0
    cols["status"][::7] = ref.OVER_LIMIT
    path_in, path_out = str(tmp_path / "in.npz"), str(tmp_path / "out.npz")
    snapshot.write(path_in, keys, cols)
    loader = FileLoader(path_in, path_out)
    items = list(loader.load())
    assert len(items) == 500
    first = items[0]
    assert (first.key, first.algorithm, first.status, first.limit, first.duration,
            first.remaining, first.stamp, first.expire_at, first.burst) == (
        keys[0], 0, ref.OVER_LIMIT, 100, 3_600_000, 0, T_PIN, T_PIN + 3_600_000, 0)
    assert type(first.remaining) is int  # plain numbers, as a Loader of the program's hands them
    loader.save(iter(items))
    got_keys, got = snapshot.read(path_out)
    assert got_keys == keys
    assert all(got[f].tolist() == cols[f].tolist() for f in snapshot.FIELDS)
    # either end may be unset: nothing to load, nothing written
    idle = FileLoader(None, None)
    assert list(idle.load()) == []
    idle.save(items)
    assert sorted(os.listdir(tmp_path)) == ["in.npz", "out.npz"]


# ---- stage 4 ---------------------------------------------------------------------------


N = 40
HITS0 = 1


def saved_state(ks, probed, taken):
    """A sound world: carried state, the probes' answers and the saved rows
    all say limit - 1 - taken."""
    carried = check.Carried.empty(ks.n)
    carried.remaining[:] = ks.limit - HITS0
    carried.reset_time[:] = T_PIN + ks.duration_ms
    rem = ks.limit - HITS0 - taken
    it = check.Items(
        key=probed, status=np.zeros(len(probed), np.int64),
        limit=np.full(len(probed), ks.limit), remaining=rem[probed],
        reset_time=carried.reset_time[probed], valid=np.ones(len(probed), bool),
        behavior=np.zeros(len(probed), np.int64), hits=np.zeros(len(probed), np.int64))
    cols = snapshot.preload_rows(ks, HITS0, T_PIN)
    cols["remaining"] = rem.copy()
    wc = check.WindowCheck(ks, carried, np.zeros(ks.n, bool))
    return wc, it, snapshot.hash_keys(ks), cols


def rows_of(wc, it, saved, hash_keys):
    v = check.Verdict()
    wc.check_saved(it, saved, hash_keys, ["bench-warmup_worker0"], v)
    return {name: value for name, value, limit in v.rows if limit == 0}, v


SOUND = {"save.file_unreadable": 0, "save.keys_unknown": 0,
         "save.keys_missing": 0, "save.rows_differ": 0}


def world():
    ks = keyspace(N)
    probed = np.arange(0, N, 2)
    taken = np.arange(N) % 5
    return (ks, probed) + saved_state(ks, probed, taken)


def test_check_saved_sound_rows_in_any_order_and_the_harness_own_key_beside_them():
    ks, probed, wc, it, hk, cols = world()
    order = np.random.default_rng(3).permutation(N)
    keys = [hk[i] for i in order] + ["bench-warmup_worker0"]
    shuffled = {f: np.append(cols[f][order], 0) for f in snapshot.FIELDS}
    got, v = rows_of(wc, it, (keys, shuffled), hk)
    assert got == SOUND and v.correct
    assert [r[0] for r in v.rows] == list(SOUND)
    assert wc.counted["saved_rows"] == N + 1 and wc.counted["saved_probed"] == len(probed)


def test_check_saved_a_file_that_cannot_be_read_fails_alone():
    ks, probed, wc, it, hk, cols = world()
    got, v = rows_of(wc, it, "out.npz: not a snapshot: FileNotFoundError()", hk)
    assert got == dict(SOUND, **{"save.file_unreadable": 1}) and not v.correct
    assert "not a snapshot" in "\n".join(v.lines())


def test_check_saved_a_key_not_of_the_keyspace_or_saved_twice_is_unknown():
    ks, probed, wc, it, hk, cols = world()
    other = snapshot.hash_keys(traffic.Keyspace.from_config(
        {"keyspace": {"keys": 3, "limit": 100, "duration_ms": 3_600_000,
                      "algorithm": "token"}}, 8))  # another seed's salt
    keys = hk + [other[0], hk[5]]
    more = {f: np.append(cols[f], cols[f][[0, 5]]) for f in snapshot.FIELDS}
    got, v = rows_of(wc, it, (keys, more), hk)
    assert got == dict(SOUND, **{"save.keys_unknown": 2}) and not v.correct


def without(hk, cols, k):
    keep = np.arange(len(hk)) != k
    return [h for i, h in enumerate(hk) if i != k], {f: cols[f][keep] for f in cols}


def test_check_saved_a_probed_key_the_file_lacks_is_missing_unless_excused():
    ks, probed, wc, it, hk, cols = world()
    got, v = rows_of(wc, it, without(hk, cols, 4), hk)
    assert got == dict(SOUND, **{"save.keys_missing": 1}) and not v.correct
    assert "key ids [4]" in "\n".join(v.lines())
    # a key that was not probed may be missing: nothing says it should be there
    got, _ = rows_of(wc, it, without(hk, cols, 5), hk)
    assert got == SOUND
    # a sign of eviction, a removing RESET_REMAINING or a failed call excuses it
    wc.evicted.add(4)
    assert rows_of(wc, it, without(hk, cols, 4), hk)[0] == SOUND
    wc.evicted.clear()
    wc.removed[4] = 1
    assert rows_of(wc, it, without(hk, cols, 4), hk)[0] == SOUND
    wc.removed[4] = 0
    wc.uncertain[4] = True
    assert rows_of(wc, it, without(hk, cols, 4), hk)[0] == SOUND
    wc.uncertain[4] = False
    it.valid[2] = False  # key 4's probe carried an error
    assert rows_of(wc, it, without(hk, cols, 4), hk)[0] == SOUND


def test_check_saved_an_empty_snapshot_lacks_every_probed_key():
    ks, probed, wc, it, hk, cols = world()
    got, v = rows_of(wc, it, ([], {f: np.zeros(0, np.int64) for f in snapshot.FIELDS}), hk)
    assert got == dict(SOUND, **{"save.keys_missing": len(probed)}) and not v.correct


@pytest.mark.parametrize("field,delta", [
    ("limit", 1), ("duration", -1), ("remaining", 1), ("remaining", -1),
    ("expire_at", 1)])
def test_check_saved_a_row_that_differs_from_its_probe_fails(field, delta):
    ks, probed, wc, it, hk, cols = world()
    cols[field][6] += delta
    got, v = rows_of(wc, it, (hk, cols), hk)
    assert got == dict(SOUND, **{"save.rows_differ": 1}) and not v.correct
    assert "key=6: saved " in "\n".join(v.lines())
    # a row of a key that was not probed is held by nothing; an evicted key
    # is excused here as stage 3 excuses it
    cols[field][6] -= delta
    cols[field][7] += delta
    assert rows_of(wc, it, (hk, cols), hk)[0] == SOUND
    cols[field][6] += delta
    wc.evicted.add(6)
    assert rows_of(wc, it, (hk, cols), hk)[0] == SOUND


def test_the_hit_a_window_took_has_to_be_in_the_saved_row():
    """The guarantee in one case: the probe shows what was carried less the
    accepted hits (stage 3 holds that); a Save that wrote the loaded value
    back lost the hits."""
    ks, probed, wc, it, hk, cols = world()
    stale = dict(cols, remaining=np.full(N, ks.limit - HITS0))
    got, _ = rows_of(wc, it, (hk, stale), hk)
    took = int(np.sum(np.arange(N)[probed] % 5 != 0))
    assert got["save.rows_differ"] == took > 0


# ---- the load row ------------------------------------------------------------------------


def test_resident_allowance_is_the_expected_overflow_and_six_deviations():
    # zipf-1m's geometry: 0.659 % of 1M keys find their group full
    assert check.resident_allowance(1_000_000, 1 << 18, 8) == 7277
    assert 6580 < 1_000_000 * check.lost_share(1_000_000, 1 << 18, 8) < 6595
    assert check.resident_allowance(2000, 4096, 8) == 3  # nothing overflows
    v = check.Verdict()
    check.check_load({"live": 993_500, "groups": 1 << 18, "ways": 8}, 1_000_000, v)
    check.check_load({"live": 992_700, "groups": 1 << 18, "ways": 8}, 1_000_000, v)
    check.check_load({"live": 1_000_002, "groups": 1 << 18, "ways": 8}, 1_000_000, v)
    assert v.rows == [("load.keys_not_resident", 6500, 7277),
                      ("load.keys_not_resident", 7300, 7277),
                      ("load.keys_not_resident", 0, 7277)]
    assert not v.correct


def test_a_simulated_load_stays_inside_the_allowance():
    rng = np.random.default_rng(11)
    for keys, groups in ((100_000, 1 << 14), (20_000, 4096), (300_000, 1 << 16)):
        fill = np.bincount(rng.integers(0, groups, keys), minlength=groups)
        lost = int(np.maximum(fill - 8, 0).sum())
        assert 0 < lost <= check.resident_allowance(keys, groups, 8)
        assert lost > 0.8 * keys * check.lost_share(keys, groups, 8)


# ---- the configuration's two keys ------------------------------------------------------------


def test_check_config_defaults_are_off_and_the_new_configuration_passes():
    manifest.check_config(config("zipf-1m"))  # via grpc by default, nothing saved
    manifest.check_config({"keyspace": {"algorithm": "even_token_odd_leaky"}})
    manifest.check_config(config("loader-1m"))
    manifest.check_config(dict(config("zipf-1m"), preload={"hits": 1, "via": "grpc"}))


@pytest.mark.parametrize("edit", [
    lambda c: c["preload"].update(via="file"),
    lambda c: c["preload"].update(path="/tmp/x"),
    lambda c: c.update(preload=["snapshot"]),
    lambda c: c["keyspace"].update(algorithm="even_token_odd_leaky"),
    lambda c: c["keyspace"].update(algorithm="leaky"),
    lambda c: c.update(shutdown={"saved": "unchecked"}),
    lambda c: c.update(shutdown={"saved": "checked", "keep": True}),
    lambda c: c.update(shutdown=True),
    lambda c: c.update(consistency={"kind": "eventual"}),
], ids=["via", "extra-key", "not-an-object", "leaky-half", "leaky", "saved-value",
        "shutdown-extra-key", "shutdown-not-an-object", "eventual"])
def test_check_config_refuses(edit):
    conf = config("loader-1m")
    edit(conf)
    with pytest.raises(manifest.ManifestError):
        manifest.check_config(conf)


def test_manifest_check_reads_every_configurations_file(tmp_path):
    m = manifest.load(ROOT)
    bad = copy.deepcopy(config("zipf-1m"))
    bad["preload"]["via"] = "carrier-pigeon"
    os.makedirs(tmp_path / "benchmarks/configs")
    for sub in ("traffic", "metrics"):
        os.symlink(os.path.join(ROOT, "benchmarks", sub), tmp_path / "benchmarks" / sub)
    for c in m["configs"]:
        with open(tmp_path / c["file"], "w", encoding="utf-8") as f:
            json.dump(bad if c["name"] == "zipf-1m" else config(c["name"]), f)
    with pytest.raises(manifest.ManifestError, match="zipf-1m: preload.via"):
        manifest.check(m, str(tmp_path))


# ---- the two controls -------------------------------------------------------------------------


def test_stale_snapshot_gives_one_key_in_1000_a_hit_back_and_drop_saved_loses_one_row():
    ks = keyspace(2500)
    cols = snapshot.preload_rows(ks, 1, T_PIN)
    before = cols["remaining"].copy()
    control.stale_snapshot(cols)
    assert np.nonzero(cols["remaining"] - before)[0].tolist() == [0, 1000, 2000]
    assert (cols["remaining"] - before).max() == 1
    keys, kept = control.drop_saved(snapshot.hash_keys(ks), cols)
    assert len(keys) == 2497 and all(len(v) == 2497 for v in kept.values())
    assert snapshot.hash_keys(ks)[1000] not in keys
    assert set(control.SNAPSHOT_KINDS).isdisjoint(control.KINDS)


# ---- the manifest's new entries -------------------------------------------------------------------


def test_loader_1m_is_zipf_1m_with_a_loader_and_nothing_else():
    conf, twin = config("loader-1m"), config("zipf-1m")
    assert conf["command"] == ["-m", "benchmarks.loader_daemon"]
    for key in ("env", "rehearsal_env", "keyspace", "probes", "chips"):
        assert conf[key] == twin[key], key  # zipf-1m's, letter for letter
    assert conf["preload"] == {"hits": 1, "via": "snapshot"}
    assert conf["shutdown"] == {"saved": "checked"} and conf["reduced"] == []
    assert conf["guarantees"][:4] == twin["guarantees"] and len(conf["guarantees"]) == 7
    assert {"loader", "keys", "file_format"} <= set(conf["assumed"])
    assert len(conf["source"]) <= 200 and "store.go:69-78" in conf["source"]
    with open(os.path.join(ROOT, "benchmarks/loader_daemon.py"), encoding="utf-8") as f:
        assert len(f.read().splitlines()) < 75


def test_manifest_holds_the_cell_and_lists_it_where_the_object_path_reads():
    m = manifest.load(ROOT)
    manifest.check(m, ROOT)
    cells = {w["name"]: w for w in m["workloads"]}
    assert len(cells) == 12 and len(m["configs"]) == 8
    assert sum(w["chips"] == 4 for w in cells.values()) == 4
    assert cells[CELL] == dict(cells[CELL], config="loader-1m", traffic="calls100", chips=1)
    e2e = {x["name"] for x in manifest.metrics_of(m, CELL, "end_to_end")}
    assert e2e == {"decisions_per_s", "setup_s"}
    mine = {x["name"]: x for x in manifest.metrics_of(m, CELL, "per_layer")}
    for name in ("object_host_ms_per_call", "engine_wait_ms_per_call", "save_s"):
        assert mine[name]["workloads"] == [CELL], name
    assert mine["save_s"]["moves"] == "setup_s" and mine["save_s"]["source"] == "host_clock"
    assert mine["save_s"]["layer"] == "shutdown and Save"
    assert mine["object_host_ms_per_call"]["moves"] == "decisions_per_s"
    assert "columnar_call_share" in mine and "compile_s" in mine
    # the columnar edge's own spans read nothing where no call is columnar
    twin = {x["name"] for x in manifest.metrics_of(m, TWIN, "per_layer")}
    assert {"edge_wait_ms_per_call.closed", "edge_work_us_per_call.closed",
            "engine_ms_per_call.closed"} <= twin - set(mine)
    # no bound moved
    assert {e["name"]: e["bound"] for e in m["end_to_end"]} == {
        "decisions_per_s": 0.12, "call_p50_ms": 0.15, "setup_s": 0.25}


APPENDED = {
    "store-4.calls100": [
        "calls_per_flush.closed", "outside_handler_ms_per_call.closed",
        "loop_lag_ms.closed", "interpreter_wait_us.closed",
        "call_cpu_ms_per_call.closed", "call_on_cpu_share.closed",
        "edge_wait_ms_per_call.closed"],
    "batching-10k.burst": [
        "calls_per_flush.open", "loop_lag_ms.open", "interpreter_wait_us.open",
        "call_cpu_ms_per_call.open", "call_on_cpu_share.open", "edge_ms_per_call"],
    "global-hot-4.herd-zipf": [
        "ici_tick_lock_wait_ms", "ici_tick_launch_ms", "ici_tick_read_ms",
        "calls_per_flush.closed", "edge_wait_ms_per_call.closed",
        "outside_handler_ms_per_call.closed", "loop_lag_ms.closed",
        "interpreter_wait_us.closed", "call_cpu_ms_per_call.closed",
        "call_on_cpu_share.closed"],
}


@pytest.mark.parametrize("cell", sorted(APPENDED))
def test_the_appends_that_perf_md_7_22_and_26_listed_are_made(cell):
    m = manifest.load(ROOT)
    mine = {x["name"] for x in manifest.metrics_of(m, cell, "per_layer")}
    assert set(APPENDED[cell]) <= mine
