"""Device time per simulated tick, per device, of the ops the program
names ``faults``: the step's section 9a (the hard-fault arrivals and
repair of both tiers, the fault-drop moves, the faulted link-ticks) and
section 9c (the connectivity audit). It is not the whole fault model:
the fault draws count for ``draws_us_per_tick``, and the failing and
jittered wakes and the fallback run inside ``gating.gate_step`` under
``gating`` alone. The time is the leaf-op time, inside the chunk
program's runs in the traced slice, of the ops whose ``op_name`` path
holds ``faults`` and not ``draws``, over the ticks the slice simulated.
The op names are read from the newest profile with
``progtrace.op_names``; nothing is read from a program without the
scope."""
import glob
import os

from chipbench import devtrace, progtrace

SCOPE = "faults"


def holds_faults(op_name: str) -> bool:
    """Whether an op's path holds ``faults`` and not ``draws``, each
    component unwrapped from its transforms."""
    path = [progtrace.unwrap(c) for c in op_name.split("/")]
    return SCOPE in path and progtrace.DRAWS not in path


def fault_seconds(rec: dict, names: dict, n_devices: int) -> float | None:
    """Leaf-op seconds of the fault ops inside the chunk program per
    device (over the first ``n_devices``): ``rec`` is a progtrace record
    and ``names`` is ``{device index: {op event name: op_name}}``. None
    when no op holds the scope."""
    marked = {k: dict(d, parts=[
        SCOPE if holds_faults(names.get(int(k), {}).get(n, "")) else "rest"
        for n in d["names"]]) for k, d in rec["devices"].items()}
    return progtrace.part_seconds({"devices": marked},
                                  n_devices).get(SCOPE)


def newest_profile(directory=progtrace.TRACE_DIR) -> str | None:
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def read(ctx):
    rec = progtrace.record()
    path = newest_profile()
    ticks = devtrace.ticks(ctx["trace"])
    if rec is None or path is None or not ticks:
        return None
    t = fault_seconds(rec, progtrace.op_names(path), ctx["cell"].chips)
    return None if t is None else 1e6 * t / ticks
