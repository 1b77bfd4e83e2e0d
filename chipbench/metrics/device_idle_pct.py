"""Share of a sweep's period in which no operation ran on the device.

A sweep is many chunk ticks and then one boundary, where the host
fetches the sweep's totals, finalizes them and builds the next sweep.
The traced slice holds steady ticks, a boundary and more ticks, but not
at their weights. So the whole ticks before the boundary give the
device's time and busy time per tick, and the whole slice gives what
the boundary adds to each, beyond the ticks it holds. One period is the
cell's ticks per sweep at the steady rate plus that boundary. (A chunk
boundary inside a sweep is taken as steady: all the chunks of a sweep
are dispatched before the first ends.) Nothing is read when the slice
holds no boundary.
"""
from chipbench import devtrace


def read(ctx):
    tr = ctx["trace"]
    st = tr["steady"]
    ticks = devtrace.ticks(tr)
    if st is None or not ticks:
        return None
    window_tick = st["window_s"] / st["ticks"]
    busy_tick = st["busy_s"] / st["ticks"]
    n = ctx["cell"].n_ticks
    period = n * window_tick + (tr["window_s"] - ticks * window_tick)
    busy = n * busy_tick + (tr["busy_s"] - ticks * busy_tick)
    return 100.0 * (1.0 - busy / period)
