"""Device programs that a sweep's build runs: the ``XLA Modules`` runs
on device 0 that start between the first ``lcdc.batch`` span of the
sweep whose first chunk is dispatched in the traced slice and the start
of that sweep's first chunk program. An exact count; a build traced
into one program would read 1 (plus the chunk's live mask). Nothing is
read unless the slice holds the whole build, or from a program without
the spans."""
from chipbench import progtrace


def read(ctx):
    rec = progtrace.record()
    n = None if rec is None else progtrace.n_buckets(ctx)
    if not n:
        return None
    build = progtrace.sweep_build(rec, n)
    return None if build is None else build["programs"]
