"""The instruments of what no call's own thread times (ISSUE 38):
`loop.lag` reads how late the serving event loop runs, `interp.wait` how
long a thread waits for the interpreter lock, and one call in sixteen, by
its sequence number, reads its executor thread's CPU against its wall
time. Counts and orderings only: a CPU run yields no time worth keeping."""

import asyncio
import threading
import time

import grpc
import pytest
from prometheus_client import parser

from gubernator_tpu.metrics import Log2Histogram, Metrics
from gubernator_tpu.service import pb
from gubernator_tpu.service.config import DaemonConfig
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.utils import tracing

V1 = "/pb.gubernator.V1/GetRateLimits"
NEW_FAMILIES = (
    "gubernator_loop_lag_seconds",
    "gubernator_interpreter_wait_seconds",
    "gubernator_call_cpu_seconds",
    "gubernator_call_cpu_wall_seconds",
)


def hist(name="h"):
    return Log2Histogram(name, "doc", scale=1e-6, n_buckets=24)


def probes_on_a_loop(body, seconds):
    """(loop lag histogram, interpreter wait histogram) of probes that
    ran `seconds` on a loop of their own while `body(loop)` ran once."""
    lag, wait = hist("lag"), hist("wait")

    async def main():
        p = tracing.HostProbes(lag, wait)
        loop = asyncio.get_running_loop()
        p.start(loop)
        try:
            await asyncio.sleep(0.05)
            body(loop)
            await asyncio.sleep(seconds)
        finally:
            p.stop()
            p.join()

    asyncio.run(main())
    return lag, wait


def worst(h: Log2Histogram) -> float:
    """Upper bound of the highest bucket that holds an observation."""
    counts = h._series[()][0]
    top = max(i for i, c in enumerate(counts) if c)
    return h._les[min(top, h.n_buckets - 1)]


def test_loop_probe_is_quiet_on_an_idle_loop():
    lag, _ = probes_on_a_loop(lambda loop: None, 0.4)
    s = lag.summary()
    assert s["count"] >= 20  # 100 firings a second
    # the median: one stall of a loaded test machine is not the loop's
    assert s["p50"] < 0.005


def test_loop_probe_reads_a_blocked_loop():
    lag, _ = probes_on_a_loop(lambda loop: time.sleep(0.05), 0.2)
    # the firing that was due while the loop slept ran at least 40 ms late
    assert worst(lag) >= 0.040
    assert lag.summary()["sum"] >= 0.040


def test_interpreter_probe_reads_more_beside_a_spinning_thread():
    def mean_wait(spin: bool) -> float:
        stop = threading.Event()

        def burn():
            x = 0
            while not stop.is_set():
                x += 1

        t = threading.Thread(target=burn, daemon=True)
        if spin:
            t.start()
        try:
            _, wait = probes_on_a_loop(lambda loop: None, 0.6)
        finally:
            stop.set()
            if spin:
                t.join(5)
        s = wait.summary()
        assert s["count"] >= 20
        return s["sum"] / s["count"]

    quiet = mean_wait(False)
    contended = mean_wait(True)
    # a thread that never blocks gives the lock up once a switch
    # interval (5 ms): the sleeper waits for it on every wake-up
    assert contended > quiet
    assert contended > 0.0005


def test_probe_thread_and_timer_end_with_stop():
    def probe_threads():
        # (a daemon another test of this process left open has its own)
        return sum(t.name == "interp-probe" for t in threading.enumerate())

    before = probe_threads()
    lag, wait = probes_on_a_loop(lambda loop: None, 0.05)
    assert probe_threads() == before
    n = lag.summary()["count"], wait.summary()["count"]
    time.sleep(0.05)  # nothing fires after stop
    assert n == (lag.summary()["count"], wait.summary()["count"])


def test_marks_are_built_only_while_a_capture_runs(monkeypatch):
    built = []

    class Fake:
        def __init__(self, name, **attrs):
            built.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_annotation", lambda n, a: Fake(n, **a))
    probes_on_a_loop(lambda loop: None, 0.1)
    assert built == []
    monkeypatch.setattr(tracing, "_capturing", True)
    probes_on_a_loop(lambda loop: None, 0.1)
    # (another test's engine, still alive in this process, may add its own)
    built = [b for b in built if b[0] in ("loop.lag", "interp.wait")]
    assert {n for n, _ in built} == {"loop.lag", "interp.wait"}
    for name, attrs in built:
        key = "lag_us" if name == "loop.lag" else "wait_us"
        assert list(attrs) == [key] and attrs[key] >= 0


# ---- on a daemon ------------------------------------------------------------


def body(keys) -> bytes:
    msg = pb.pb.GetRateLimitsReq()
    for k in keys:
        r = msg.requests.add()
        r.name, r.unique_key = "probe", k
        r.hits, r.limit, r.duration = 1, 1_000_000, 60_000
    return msg.SerializeToString()


def scrape(daemon) -> dict:
    out = {}
    for line in daemon.svc.metrics.render().decode().splitlines():
        name, _, value = line.rpartition(" ")
        if name and not line.startswith("#"):
            out[name] = float(value)
    return out


@pytest.fixture(scope="module")
def daemon(loop_thread):
    d = loop_thread.run(
        Daemon.spawn(DaemonConfig(cache_size=4096)), timeout=120)
    yield d
    loop_thread.run(d.close())


def test_one_call_in_sixteen_reads_its_cpu(daemon):
    cpu = 'gubernator_call_cpu_seconds_%s{path="columnar"}'
    wall = 'gubernator_call_cpu_wall_seconds_%s{path="columnar"}'
    before = scrape(daemon)
    assert before[cpu % "count"] == before[wall % "count"]
    first = next(tracing._CALL_SEQ)  # the calls below take the next ones
    n = 64
    with grpc.insecure_channel(daemon.grpc_address) as ch:
        call = ch.unary_unary(V1, request_serializer=None,
                              response_deserializer=None)
        for i in range(n):
            call(body([f"a{i}", f"b{i}"]), timeout=30)
    after = scrape(daemon)
    sampled = sum(1 for s in range(first + 1, first + 1 + n) if s & 15 == 0)
    assert sampled == 4
    assert after[cpu % "count"] - before[cpu % "count"] == sampled
    assert after[wall % "count"] - before[wall % "count"] == sampled
    d_cpu = after[cpu % "sum"] - before[cpu % "sum"]
    d_wall = after[wall % "sum"] - before[wall % "sum"]
    assert 0 < d_cpu <= d_wall
    # every call is timed by its stages; only the sampled ones by the CPU
    calls = 'gubernator_call_stage_duration_count{path="columnar",stage="engine"}'
    assert after[calls] - before[calls] == n


def test_a_call_without_a_record_is_never_sampled(daemon):
    from gubernator_tpu.service import fastpath

    before = scrape(daemon)
    for _ in range(3):  # NO_CALL's sequence number is 0
        assert isinstance(
            fastpath.try_serve(daemon.svc, body(["x", "y"]), False), bytes)
    after = scrape(daemon)
    key = 'gubernator_call_cpu_seconds_count{path="columnar"}'
    assert after[key] == before[key]


def test_the_daemons_probes_run_and_are_exposed_with_help(daemon):
    time.sleep(0.3)
    text = daemon.svc.metrics.render().decode()
    fams = {f.name: f for f in parser.text_string_to_metric_families(text)}
    for name in NEW_FAMILIES:
        assert name in fams, name
        assert fams[name].type == "histogram"
        assert len(fams[name].documentation) > 40, name
    series = scrape(daemon)
    assert series["gubernator_loop_lag_seconds_count"] >= 10
    assert series["gubernator_interpreter_wait_seconds_count"] >= 10
    # every path's children are exposed from start-up, at 0
    for path in ("columnar", "mixed", "object", "peer_columnar", "peer_object"):
        assert f'gubernator_call_cpu_seconds_count{{path="{path}"}}' in series
        assert f'gubernator_call_cpu_wall_seconds_count{{path="{path}"}}' in series
    # a histogram on a series of their own: none is a label of another
    assert series["gubernator_loop_lag_seconds_sum"] >= 0.0


def test_the_probes_end_with_the_daemon(loop_thread):
    def probe_threads():
        return [t for t in threading.enumerate() if t.name == "interp-probe"]

    n0 = len(probe_threads())
    d = loop_thread.run(
        Daemon.spawn(DaemonConfig(cache_size=4096)), timeout=120)
    assert len(probe_threads()) == n0 + 1
    m = d.svc.metrics
    loop_thread.run(d.close())
    assert len(probe_threads()) == n0
    fired = m.loop_lag.summary()["count"], m.interpreter_wait.summary()["count"]
    time.sleep(0.05)
    assert fired == (
        m.loop_lag.summary()["count"], m.interpreter_wait.summary()["count"])


def test_new_series_have_catalog_rows():
    import tools.check_metrics_names as names

    documented = names.doc_names()
    for name in NEW_FAMILIES + ("gubernator_ici_tick_stage_duration",):
        assert name in documented, name
    assert set(NEW_FAMILIES) <= Metrics().sample_family_names()
