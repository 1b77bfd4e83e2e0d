"""Device busy time of the chunk program per simulated tick, per device:
the leaf-op time of the program that ran in the traced slice over the
ticks it simulated there (the calls of the switch kernel, one per tier
and tick)."""
from chipbench import devtrace


def read(ctx):
    tr = ctx["trace"]
    ticks = devtrace.ticks(tr)
    if not ticks or not tr["program_busy_s"]:
        return None
    return 1e6 * max(tr["program_busy_s"].values()) / ticks
