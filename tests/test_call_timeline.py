"""One timeline per call (docs/monitoring.md "Tracing the pipeline"):
the stages a GetRateLimits handler records partition its time, by the
path that served the call; the engine's flush stages, occupancy counter
and flight-recorder record carry the same call. Counts and orderings
only: no time is compared with a constant."""

import re

import grpc
import pytest

from gubernator_tpu.metrics import CALL_STAGES
from gubernator_tpu.service import pb
from gubernator_tpu.service.config import DaemonConfig
from gubernator_tpu.service.daemon import Daemon

V1 = "/pb.gubernator.V1/GetRateLimits"
PEERS = "/pb.gubernator.PeersV1/GetPeerRateLimits"
GREGORIAN = 4  # Behavior.DURATION_IS_GREGORIAN


@pytest.fixture(scope="module")
def daemon(loop_thread):
    d = loop_thread.run(Daemon.spawn(DaemonConfig(cache_size=4096)), timeout=120)
    yield d
    loop_thread.run(d.close())


@pytest.fixture(scope="module")
def channel(daemon):
    with grpc.insecure_channel(daemon.grpc_address) as ch:
        yield ch


def scrape(daemon) -> dict:
    out = {}
    for line in daemon.svc.metrics.render().decode().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def delta(after: dict, before: dict, series: str) -> float:
    return after.get(series, 0.0) - before.get(series, 0.0)


def v1_body(keys, behavior=0, name="tl"):
    msg = pb.pb.GetRateLimitsReq()
    for k in keys:
        r = msg.requests.add()
        r.name, r.unique_key = name, k
        r.hits, r.limit, r.duration = 1, 1_000_000, 60_000
        r.behavior = behavior
    return msg


def send(channel, method: str, body: bytes) -> bytes:
    return channel.unary_unary(
        method, request_serializer=None, response_deserializer=None
    )(body, timeout=30)


def stage_counts(after, before) -> dict:
    """{(path, stage): calls observed} over every declared child."""
    return {
        (path, stage): delta(
            after, before,
            f'gubernator_call_stage_duration_count{{path="{path}",stage="{stage}"}}',
        )
        for path, stages in CALL_STAGES.items()
        for stage in stages
    }


def columnar_call():
    return V1, v1_body(["a", "b"]).SerializeToString()


def mixed_call():
    msg = v1_body(["m1", "m2"])
    msg.requests[1].behavior = GREGORIAN
    msg.requests[1].duration = 1  # GregorianHours
    return V1, msg.SerializeToString()


def object_call():
    # an item with metadata needs the object path (wire.parse_requests
    # marks it slow); one key 40 times no longer does: more than
    # max_waves waves are further launches of a columnar flush
    msg = v1_body(["o1", "o2"])
    msg.requests[1].metadata["tenant"] = "t"
    return V1, msg.SerializeToString()


def peer_call():
    msg = pb.peers_pb.GetPeerRateLimitsReq()
    for k in ("p1", "p2"):
        r = msg.requests.add()
        r.name, r.unique_key = "tl", k
        r.hits, r.limit, r.duration = 1, 1_000_000, 60_000
    return PEERS, msg.SerializeToString()


CASES = {
    # case: (builder, path, reason, stages that must be observed once)
    "columnar": (columnar_call, "columnar", "", CALL_STAGES["columnar"]),
    "mixed": (mixed_call, "mixed", "gregorian", CALL_STAGES["mixed"]),
    "object": (object_call, "object", "slow_item", CALL_STAGES["object"]),
    "peer": (peer_call, "peer_columnar", "", CALL_STAGES["peer_columnar"]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stage_set_is_the_catalog_and_partitions_the_handler_time(
    daemon, channel, case
):
    build, path, reason, stages = CASES[case]
    method, body = build()
    before = scrape(daemon)
    send(channel, method, body)
    after = scrape(daemon)

    want = {(path, s): 1.0 for s in stages}
    got = {k: v for k, v in stage_counts(after, before).items() if v}
    assert got == want

    # the stages of the call add up to the handler's own observation:
    # both run between the same two clock reads
    total = sum(
        delta(after, before,
              f'gubernator_call_stage_duration_sum{{path="{path}",stage="{s}"}}')
        for s in stages
    )
    handler = delta(
        after, before, f'gubernator_grpc_request_duration_sum{{method="{method}"}}'
    )
    assert delta(
        after, before, f'gubernator_grpc_request_duration_count{{method="{method}"}}'
    ) == 1
    assert total == pytest.approx(handler, rel=1e-6, abs=1e-9)

    # counted once, where the duration is observed, with the reason
    edge = {
        k: delta(after, before, k) for k in after
        if k.startswith("gubernator_edge_calls{") and delta(after, before, k)
    }
    assert edge == {
        f'gubernator_edge_calls{{path="{path}",reason="{reason}"}}': 1.0
    }


def test_disabled_fast_edge_is_an_object_call_without_an_attempt(
    daemon, channel, monkeypatch
):
    monkeypatch.setenv("GUBER_DISABLE_FAST_EDGE", "1")
    before = scrape(daemon)
    send(channel, *columnar_call())
    after = scrape(daemon)
    got = {k for k, v in stage_counts(after, before).items() if v}
    assert got == {
        ("object", s) for s in CALL_STAGES["object"] if s != "columnar_attempt"
    }
    assert delta(
        after, before, 'gubernator_edge_calls{path="object",reason="disabled"}'
    ) == 1


def test_a_columnar_attempt_that_raises_counts_as_error(
    daemon, channel, monkeypatch
):
    from gubernator_tpu.service import fastpath

    def boom(*a, **k):
        raise RuntimeError("boom")

    monkeypatch.setattr(fastpath, "_try_serve", boom)
    before = scrape(daemon)
    with pytest.raises(grpc.RpcError):
        send(channel, *columnar_call())
    after = scrape(daemon)
    edge = {
        k: delta(after, before, k) for k in after
        if k.startswith("gubernator_edge_calls{") and delta(after, before, k)
    }
    assert edge == {'gubernator_edge_calls{path="object",reason="error"}': 1.0}


def test_every_child_of_the_new_families_is_exposed_at_zero():
    from gubernator_tpu.metrics import EDGE_REASONS, Metrics
    from gubernator_tpu.runtime.engine import EngineMetrics
    from gubernator_tpu.metrics import wire_engine_telemetry

    class _Engine:
        metrics = EngineMetrics()

        def queue_depth(self):
            return 0

        def live_count(self):
            return 0

    m = Metrics()
    wire_engine_telemetry(m, _Engine())
    text = m.render().decode()
    for path, stages in CALL_STAGES.items():
        for s in stages:
            lbl = f'{{path="{path}",stage="{s}"}}'
            assert f"gubernator_call_stage_duration_count{lbl} 0" in text
        for r in EDGE_REASONS[path]:
            assert f'gubernator_edge_calls{{path="{path}",reason="{r}"}} 0.0' in text
    for s in ("hash", "waves", "keydict", "lock_wait", "readback", "post"):
        assert f'gubernator_engine_stage_duration_count{{stage="{s}"}} 0' in text
    assert re.search(r"^gubernator_engine_busy_seconds 0\.0$", text, re.M)
    assert re.search(r"^gubernator_engine_clock_seconds \d", text, re.M)


@pytest.mark.parametrize("case", ["columnar", "object"])
def test_flush_stages_once_a_flush_and_busy_within_clock(daemon, channel, case):
    before = scrape(daemon)
    for _ in range(40):
        send(channel, *CASES[case][0]())
    after = scrape(daemon)
    for s in ("hash", "waves", "keydict", "lock_wait", "dispatch",
              "readback", "post", "assemble"):
        n = delta(after, before,
                  f'gubernator_engine_stage_duration_count{{stage="{s}"}}')
        assert n >= 40, s
    busy = delta(after, before, "gubernator_engine_busy_seconds")
    clock = delta(after, before, "gubernator_engine_clock_seconds")
    assert 0.0 < busy <= clock
    # dispatch is observed once a flush on either path, never twice
    assert delta(
        after, before, 'gubernator_engine_stage_duration_count{stage="dispatch"}'
    ) == delta(
        after, before, 'gubernator_engine_stage_duration_count{stage="lock_wait"}'
    )


@pytest.mark.parametrize("case,path", [("columnar", "columnar"), ("object", "object")])
def test_flight_recorder_record_carries_call_and_stages(daemon, channel, case, path):
    send(channel, *CASES[case][0]())
    rec = [
        r for r in daemon.svc.engine.metrics.recorder.snapshot()
        if r.get("path") == path
    ][-1]
    assert rec["call"] > 0 and rec["ticket"] > 0
    assert set(rec["stages_us"]) >= {
        "hash", "waves", "keydict", "lock_wait", "dispatch", "readback", "post"
    }
    assert all(v >= 0 for v in rec["stages_us"].values())


def test_call_ids_rise_and_flush_ids_are_unique(daemon, channel):
    for _ in range(3):
        send(channel, *columnar_call())
    recs = [r for r in daemon.svc.engine.metrics.recorder.snapshot()
            if r.get("path") == "columnar"][-3:]
    calls = [r["call"] for r in recs]
    assert calls == sorted(calls) and len(set(calls)) == 3
    assert len({r["ticket"] for r in recs}) == 3
