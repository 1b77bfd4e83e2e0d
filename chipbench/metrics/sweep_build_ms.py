"""Host time to build a sweep's operands: the summed duration of the
``lcdc.batch`` (the scenario batch) and ``lcdc.init`` (state, keys and
fold buffers) spans of the sweep whose first chunk is dispatched in the
traced slice. Nothing is read unless the slice holds every one of those
spans whole, or from a program without the spans."""
from chipbench import progtrace


def read(ctx):
    rec = progtrace.record()
    n = None if rec is None else progtrace.n_buckets(ctx)
    if not n:
        return None
    build = progtrace.sweep_build(rec, n)
    return None if build is None else 1e3 * build["build_s"]
