"""GL014 fixture: a registry surface wiring decide entry points that
have no KERNEL_PARITY_CASES coverage.

Scanned only when passed explicitly; the path maps to
gubernator_tpu/ops/gl014_kernel_parity.py, which is listed in
_KERNEL_REGISTRY_FILES so the registry-surface predicate fires. The
parity map itself is the REAL tests/test_kernel_fuzz.py one, so
covered names (decide, decide_fused, ...) must stay quiet here while
invented variants fire.
"""


class _FakeOps:
    decide_turbo = None
    decide_scan_turbo = None
    decide_hyper = None
    decide = None
    decide_fused = None


def build_registry(ops):
    # VIOLATION: decide_turbo has no KERNEL_PARITY_CASES entry
    turbo = ops.decide_turbo
    # VIOLATION: scan variant is its own entry point
    turbo_scan = ops.decide_scan_turbo
    # VIOLATION: pragma without a reason still fails (requires_reason)
    hyper = ops.decide_hyper  # guberlint: allow-kernel-parity
    # ok: covered by the real parity map
    base = ops.decide
    fused = ops.decide_fused
    return turbo, turbo_scan, hyper, base, fused


# ok: reasoned pragma — witnessed-intentional uncovered reference
def wire_experimental(ops):
    return ops.decide_probe_only  # guberlint: allow-kernel-parity -- fixture: probe-only variant shares no policy arithmetic
