"""Device time of the round-chunk program (``DeviceFLSim``'s jitted
``chunk_fn``) in the traced window, per round trained in it."""
from bench import trace

PROGRAM = "jit_chunk_fn"


def read(ctx):
    rounds = ctx.counters.get("rounds", 0)
    seconds, n = trace.module_seconds(ctx.events, PROGRAM)
    if n == 0 or rounds == 0:
        return None
    return 1e3 * seconds / rounds
