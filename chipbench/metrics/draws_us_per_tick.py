"""Device time of the step's random draws per simulated tick, per
device: the leaf-op time of the ops under the program's ``draws`` scope
(chipbench/progtrace.py) in the traced slice over the ticks it simulated
there (devtrace.ticks). Nothing is read from a program without the
scope."""
from chipbench import progtrace


def read(ctx):
    return progtrace.us_per_tick(ctx, progtrace.DRAWS)
