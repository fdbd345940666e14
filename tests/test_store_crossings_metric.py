"""`store_crossings_per_wave` (ISSUE 37): the benchmark's data-file
reader of gubernator_engine_store_wave_crossings. The manifest lists it
for `store-1m.calls100` alone, the file is data, the ratio comes out of
two scrapes, a scrape of a program without the counter (the parent
commit) reads nothing and raises nothing, and a live engine with a
Store exposes what the reader asks /metrics for."""

import os

import pytest

from benchmarks import manifest, readers
from gubernator_tpu.api.types import RateLimitReq
from gubernator_tpu.metrics import Metrics, wire_engine_telemetry
from gubernator_tpu.runtime.engine import DeviceEngine, EngineConfig
from gubernator_tpu.store import MemoryStore, attach_store

NAME = "store_crossings_per_wave"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H2D = 'gubernator_engine_store_wave_crossings{direction="h2d"}'
D2H = 'gubernator_engine_store_wave_crossings{direction="d2h"}'
WAVES = "gubernator_engine_flush_waves_sum"


def reader_path():
    man = manifest.load(ROOT)
    return manifest.reader_path(ROOT, manifest.bench_dir(man), NAME)


def read(before, after):
    return readers.read(reader_path(), readers.Context(
        before=before, after=after, device={}, phases={}, generator={},
        trace=None, conf={}, traffic={}, table={}, items_answered=0,
        root=ROOT,
    ))


def test_manifest_lists_it_for_the_store_cell_alone():
    man = manifest.load(ROOT)
    names = [p["name"] for p in man["per_layer"]]
    # appended by PR 37 after PR 36's last, and nothing moved
    at = names.index(NAME)
    assert names[at - 1] == "store_rows_roofline"
    entry = man["per_layer"][at]
    assert entry == {
        "name": NAME, "unit": "crossings", "better": "lower",
        "source": "program_counter", "layer": "engine host stage",
        "moves": "decisions_per_s", "workloads": ["store-1m.calls100"],
    }
    assert reader_path().endswith(".json")  # data, no reader code


@pytest.mark.parametrize("h2d,d2h,waves,want", [
    (0.0, 420.0, 140.0, 3.0),        # nothing read through
    (13.0 * 6, 420.0 + 2 * 6, 140.0, 3.0 + 15 * 6 / 140),  # six injects
])
def test_the_ratio_from_two_scrapes(h2d, d2h, waves, want):
    before = {H2D: 26.0, D2H: 900.0, WAVES: 300.0}
    after = {H2D: 26.0 + h2d, D2H: 900.0 + d2h, WAVES: 300.0 + waves}
    assert read(before, after) == pytest.approx(want)


def test_nothing_from_a_scrape_without_the_counter():
    """The parent commit: waves but no such series; and no waves."""
    parent = {WAVES: 300.0}
    assert read(parent, {WAVES: 440.0}) is None
    same = {H2D: 0.0, D2H: 9.0, WAVES: 3.0}
    assert read(same, same) is None  # no flush between the scrapes


def test_a_live_engine_exposes_what_the_reader_reads():
    def scrape(m):
        series = {}
        for line in m.render().decode().splitlines():
            name, _, value = line.rpartition(" ")
            if name and not line.startswith("#"):
                series[name] = float(value)
        return series

    now = 1_753_700_000_000
    eng = DeviceEngine(
        EngineConfig(num_groups=64, ways=8, batch_size=32,
                     batch_wait_s=0.001),
        now_fn=lambda: now,
    )
    try:
        m = Metrics()
        wire_engine_telemetry(m, eng)
        idle = scrape(m)
        assert idle[H2D] == idle[D2H] == 0.0  # 0 without a Store
        eng.check_batch([RateLimitReq(
            name="x", unique_key="k", limit=9, duration=60_000, hits=1)])
        assert scrape(m)[D2H] == 0.0
        attach_store(eng, MemoryStore())
        before = scrape(m)
        eng.check_batch([
            RateLimitReq(name="x", unique_key=k, limit=9, duration=60_000,
                         hits=1)
            for k in ("a", "b", "b", "b")
        ])  # three waves, nothing in the Store to read through
        after = scrape(m)
        assert after[WAVES] - before[WAVES] == 3.0
        # under the lock a wave reads the probe's answer and no more: its
        # output vector and its packed rows are read after the release
        assert read(before, after) == pytest.approx(1.0)
        # exposed right after the programs it is counted beside
        text = m.render().decode()
        assert text.index("gubernator_engine_wave_programs{") < text.index(
            "gubernator_engine_store_wave_crossings{"
        )
    finally:
        eng.close()
