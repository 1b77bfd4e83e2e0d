"""Device time of the step's flow table (admission, AIMD, emission) per
simulated tick, per device: the leaf-op time of the ops under the
program's ``flows`` scope, less its draws, in the traced slice over the
ticks it simulated there. Nothing is read from a program without the
scope."""
from chipbench import progtrace


def read(ctx):
    return progtrace.us_per_tick(ctx, "flows")
