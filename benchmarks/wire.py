"""The benchmark's own stub of the public API: gRPC ``V1/GetRateLimits``.

The message descriptors are built here from the published proto contract
(gubernator.proto: ``RateLimitReq`` fields 1-8 and 10, ``RateLimitResp``
fields 1-5), so neither the load generators nor the checker import the
program's ``service.pb`` or ``client``. Calls go through a bytes-mode
``unary_unary`` method: requests are encoded once, before the window.
"""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

METHOD = "/pb.gubernator.V1/GetRateLimits"
TOKEN_BUCKET, LEAKY_BUCKET = 0, 1
UNDER_LIMIT, OVER_LIMIT = 0, 1
BEHAVIOR = {
    "BATCHING": 0, "NO_BATCHING": 1, "GLOBAL": 2, "DURATION_IS_GREGORIAN": 4,
    "RESET_REMAINING": 8, "MULTI_REGION": 16, "DRAIN_OVER_LIMIT": 32,
}
MAX_ITEMS_PER_CALL = 1000  # the API's cap on one GetRateLimits call
CHANNEL_OPTIONS = [
    ("grpc.max_receive_message_length", 64 << 20),
    ("grpc.max_send_message_length", 64 << 20),
]


def _build():
    f = descriptor_pb2.FileDescriptorProto()
    f.name = "benchmarks_gubernator_stub.proto"
    f.package = "benchstub"
    f.syntax = "proto3"
    T = descriptor_pb2.FieldDescriptorProto

    def message(name, fields):
        m = f.message_type.add()
        m.name = name
        for fname, num, ftype, extra in fields:
            fd = m.field.add()
            fd.name, fd.number, fd.type = fname, num, ftype
            fd.label = extra.get("label", T.LABEL_OPTIONAL)
            if "type_name" in extra:
                fd.type_name = extra["type_name"]
            if extra.get("presence"):
                # proto3 `optional`: presence through a synthetic oneof
                m.oneof_decl.add().name = "_" + fname
                fd.oneof_index = len(m.oneof_decl) - 1
                fd.proto3_optional = True

    message("Req", [
        ("name", 1, T.TYPE_STRING, {}),
        ("unique_key", 2, T.TYPE_STRING, {}),
        ("hits", 3, T.TYPE_INT64, {}),
        ("limit", 4, T.TYPE_INT64, {}),
        ("duration", 5, T.TYPE_INT64, {}),
        ("algorithm", 6, T.TYPE_INT32, {}),
        ("behavior", 7, T.TYPE_INT64, {}),
        ("burst", 8, T.TYPE_INT64, {}),
        ("created_at", 10, T.TYPE_INT64, {"presence": True}),
    ])
    message("Resp", [
        ("status", 1, T.TYPE_INT32, {}),
        ("limit", 2, T.TYPE_INT64, {}),
        ("remaining", 3, T.TYPE_INT64, {}),
        ("reset_time", 4, T.TYPE_INT64, {}),
        ("error", 5, T.TYPE_STRING, {}),
    ])
    message("GetReq", [
        ("requests", 1, T.TYPE_MESSAGE,
         {"label": T.LABEL_REPEATED, "type_name": ".benchstub.Req"}),
    ])
    message("GetResp", [
        ("responses", 1, T.TYPE_MESSAGE,
         {"label": T.LABEL_REPEATED, "type_name": ".benchstub.Resp"}),
    ])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return (
        message_factory.GetMessageClass(pool.FindMessageTypeByName("benchstub.GetReq")),
        message_factory.GetMessageClass(pool.FindMessageTypeByName("benchstub.GetResp")),
    )


GetReq, GetResp = _build()


def encode_call(items) -> bytes:
    """One GetRateLimitsReq from items (reference.Request or anything with
    its fields)."""
    msg = GetReq()
    for it in items:
        r = msg.requests.add()
        r.name = it.name
        r.unique_key = it.unique_key
        r.hits = it.hits
        r.limit = it.limit
        r.duration = it.duration
        r.algorithm = it.algorithm
        r.behavior = it.behavior
        if it.burst:
            r.burst = it.burst
        if it.created_at is not None:
            r.created_at = it.created_at
    return msg.SerializeToString()


def decode_call(data: bytes) -> list:
    """[(status, limit, remaining, reset_time, error), ...] of one
    GetRateLimitsResp."""
    msg = GetResp.FromString(data)
    return [
        (r.status, r.limit, r.remaining, r.reset_time, r.error)
        for r in msg.responses
    ]


def open_channel(target: str):
    """(channel, bytes-mode callable) for the sync gRPC API."""
    import grpc

    ch = grpc.insecure_channel(target, options=CHANNEL_OPTIONS)
    return ch, ch.unary_unary(
        METHOD, request_serializer=None, response_deserializer=None
    )
