"""service edge: the part of a closed-loop call's time in flight that no
handler stage covers.

Little's law on the two scrapes: ``callers`` calls are in flight at every
instant of a closed loop, so a call is in flight ``callers`` x D clock /
D calls seconds (D ``gubernator_engine_clock_seconds``, the server's own
clock at each scrape; D calls = D ``gubernator_edge_calls`` over all its
labels, one a GetRateLimits / GetPeerRateLimits handler exit). Of that
the handler saw the sum of every ``gubernator_call_stage_duration_sum``
series over the same calls (a call's stages partition its handler's
time). The rest, in ms a call, is before the handler's first line
(gRPC's completion queue, the event loop, the wait for the interpreter
lock), after its last (the response's way out), in the socket and in the
load generator. All three series predate this reader, so it reads on any
program that has PR 24's timeline; without them it gives nothing.
"""

CLOCK = "gubernator_engine_clock_seconds"
CALLS = "gubernator_edge_calls{"
STAGES = "gubernator_call_stage_duration_sum{"


def read(ctx):
    callers = ctx.traffic.get("callers")
    clock = ctx.delta(CLOCK)
    calls = sum(ctx.delta(s) for s in ctx.after if s.startswith(CALLS))
    staged = [ctx.delta(s) for s in ctx.after if s.startswith(STAGES)]
    if not callers or not clock or calls <= 0 or not staged:
        return None
    return 1000.0 * (float(callers) * clock - sum(staged)) / calls
