"""Peaks of the chips the benchmark knows, and the bytes the decide step
needs, so that a kernel's roofline share is computed here and not by the
program.

The decide step is bound by memory traffic (integer arithmetic on a
handful of columns per lane), so its least time is bytes over peak HBM
bandwidth; the bound is named ``hbm``.
"""

from __future__ import annotations

# Per chip. Source: Google Cloud documentation, "TPU v5e" system
# architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
# 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}

WAYS = 8  # slots of one group, all read to find or place a key
# Columns a lane carries in and out, 8 bytes each (gubernator.proto's
# int64 fields as the engine packs them): in: key hash hi/lo, hits, limit,
# duration, burst, algorithm+behaviour flags, created_at; out: status,
# limit, remaining, reset_time.
REQUEST_COLUMNS = 8
RESPONSE_COLUMNS = 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add it to "
            "benchmarks/roofline.py with its source")
    return PEAKS[device_kind]


def decide_bytes(lanes_with_item: int, slot_bytes: int, ways: int = WAYS) -> int:
    """Bytes the algorithm needs for one dispatch: for each lane that
    carried an item, the `ways` slots of its group read, one slot written,
    and its request and response columns. Padding lanes need nothing."""
    per_lane = (ways * slot_bytes + slot_bytes
                + 8 * (REQUEST_COLUMNS + RESPONSE_COLUMNS))
    return lanes_with_item * per_lane


def decide_least_seconds(lanes_with_item: int, slot_bytes: int,
                         device_kind: str, ways: int = WAYS) -> float:
    return decide_bytes(lanes_with_item, slot_bytes, ways) / peaks(
        device_kind)["hbm_bytes_per_s"]
