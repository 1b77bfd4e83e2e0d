"""The program's own names in a traced run: the part of the step that
every device op belongs to, and the executor's host spans.

The chunk program names the parts of its step with ``jax.named_scope``
(``edge``, ``flows``, ``rsw``, ``csw``, ``down``, ``stats``, ``gating``,
``fold``, ``guard``; a ``draws`` scope nests inside a part around its
random draws), and the executor marks its phases with
``jax.profiler.TraceAnnotation`` spans named ``lcdc.*``. A ``--trace 1``
run's profile holds both, on one clock: a scope in each device op's
framework name (its ``op_name``, the ``tf_op`` stat of the op's event
metadata), a span as an event of a host thread with its keyword stats.
The
profiler records a span only when it opened and closed inside the
traced slice, so a span cut by either end of the slice is absent.

Attribution of an op: its own ``op_name`` (for a fusion, that of its
root instruction), split at ``/``, each component unwrapped from
transform wrappers (``vmap(edge)`` is ``edge``). An op whose path holds
``draws`` counts for ``draws``; otherwise for the first part name in its
path; otherwise it is ``unscoped``. So the parts partition the chunk
program's device time.

``record`` reads the newest ``.xplane.pb`` under ``TRACE_DIR`` once per
file; the per-layer readers share it. A record is plain JSON, devtrace's
record with two more keys, so a trimmed slice of a real trace is kept
in ``chipbench/data`` and reduced again by a test.
"""
from __future__ import annotations

import glob
import os
import re
from pathlib import Path

import numpy as np

from chipbench import devtrace

#: where a ``--trace 1`` run writes its profile (``run.TRACE_DIR``)
TRACE_DIR = Path(__file__).resolve().parent / "out" / "trace"

PARTS = ("edge", "flows", "rsw", "csw", "down", "stats", "gating", "fold",
         "guard")
DRAWS = "draws"
UNSCOPED = "unscoped"
SPAN_PREFIX = "lcdc."
#: the stat of an op's event metadata that holds its framework name
#: (``op_name:op_type``)
OP_NAME_STAT = "tf_op"

_WRAPPED = re.compile(r"^[A-Za-z_][\w.\-]*\((.*)\)$")


def unwrap(component: str) -> str:
    """A path component without its transform wrappers:
    ``vmap(jit(take_along_axis))`` -> ``take_along_axis``."""
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def part_of(op_name: str) -> str:
    """The part an op counts for, by the rule of the module docstring."""
    path = [unwrap(c) for c in op_name.split("/")]
    if DRAWS in path:
        return DRAWS
    return next((c for c in path if c in PARTS), UNSCOPED)


def _varint(b: bytes, i: int):
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one protobuf message in ``b[i:end]``: an
    int for a varint, (start, end) for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif kind == 1:
            v, i = None, i + 8
        elif kind == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def op_names(path) -> dict:
    """{device index: {op event name: op_name}} from the ``tf_op`` stat
    of each op's event metadata in the device planes. ``ProfileData``
    shows event stats but not event metadata, so this reads the
    ``XSpace`` wire format: XSpace.planes (1); XPlane name (2),
    event_metadata (4, a map of XEventMetadata: name 2, stats 5) and
    stat_metadata (5, a map of XStatMetadata: id 1, name 2); XStat
    metadata_id (1), str_value (5) or ref_value (7)."""
    b = Path(path).read_bytes()
    out = {}
    for f, plane in _fields(b, 0, len(b)):
        if f != 1:
            continue
        name, evm, stm = None, [], []
        for pf, v in _fields(b, *plane):
            if pf == 2:
                name = b[v[0]:v[1]].decode()
            elif pf == 4:
                evm.append(v)
            elif pf == 5:
                stm.append(v)
        m = devtrace.DEVICE_PLANE.match(name or "")
        if not m:
            continue
        stat_name = {}
        for entry in stm:
            for ef, v in _fields(b, *entry):
                if ef == 2:
                    got = dict(_fields(b, *v))
                    if 2 in got:
                        stat_name[got.get(1, 0)] = \
                            b[got[2][0]:got[2][1]].decode()
        want = {k for k, v in stat_name.items() if v == OP_NAME_STAT}
        names = {}
        for entry in evm:
            for ef, v in _fields(b, *entry):
                if ef != 2:
                    continue
                ev_name, op = None, None
                for xf, xv in _fields(b, *v):
                    if xf == 2:
                        ev_name = b[xv[0]:xv[1]].decode(errors="replace")
                    elif xf == 5:
                        st = dict(_fields(b, *xv))
                        if st.get(1) in want:
                            if 5 in st:
                                op = b[st[5][0]:st[5][1]].decode()
                            elif 7 in st:
                                op = stat_name.get(st[7])
                if ev_name is not None and op:
                    names[ev_name] = op.rsplit(":", 1)[0]
        out[int(m.group(1))] = names
    return out


def load_file(path) -> dict:
    """The record of one profile: for each TPU device, devtrace's
    ``names``, ``ops`` and ``modules`` and the part of every op name;
    the ``lcdc.*`` host spans as [start_ns, end_ns, name, {stats}];
    and an empty ``host`` (devtrace's gap labels are not read here)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    framework = op_names(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m:
            index, names, parts, ops, mods = {}, [], [], [], []
            op_name = framework.get(int(m.group(1)), {})
            for line in plane.lines:
                if line.name == devtrace.OP_LINE:
                    for ev in line.events:
                        k = index.get(ev.name)
                        if k is None:
                            k = index[ev.name] = len(names)
                            names.append(ev.name)
                            parts.append(part_of(op_name.get(ev.name, "")))
                        ops.append([ev.start_ns, ev.end_ns, k])
                elif line.name == devtrace.MODULE_LINE:
                    mods.extend([ev.start_ns, ev.end_ns, ev.name]
                                for ev in line.events)
            devices[int(m.group(1))] = {"names": names, "parts": parts,
                                        "ops": ops, "modules": mods}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans.extend([ev.start_ns, ev.end_ns, ev.name,
                              {k: _plain(v) for k, v in ev.stats}]
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda s: (s[0], -s[1]))
    return {"devices": devices, "host": [], "spans": spans}


def _plain(v):
    return v if isinstance(v, (int, float, str)) else str(v)


_CACHE: dict = {}


def record(directory=TRACE_DIR) -> dict | None:
    """The record of the newest profile under ``directory``, read once
    per file; None when there is none."""
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = load_file(path)
    return _CACHE[key]


def devices(rec: dict, n_devices: int) -> list:
    return [rec["devices"][k]
            for k in sorted(rec["devices"], key=int)[:n_devices]]


def chunk_program(dev: dict) -> str | None:
    """The program that ran longest on a device: the chunk program."""
    busy = {}
    for s, e, name in dev["modules"]:
        busy[name] = busy.get(name, 0.0) + (e - s)
    return max(busy, key=busy.get) if busy else None


def part_seconds(rec: dict, n_devices: int) -> dict:
    """Leaf-op seconds of each part inside the chunk program's runs, per
    device (averaged over the first ``n_devices``); with ``unscoped``
    they sum to the program's busy time. A part no op belongs to is
    absent."""
    devs = devices(rec, n_devices)
    total: dict = {}
    for d in devs:
        ops = np.asarray(d["ops"], np.float64).reshape(-1, 3)
        if not len(ops):
            continue
        leaf = devtrace.leaves(ops)
        name = chunk_program(d)
        keep = np.zeros(len(leaf), bool)
        for s, e, mname in d["modules"]:
            if mname == name:
                keep |= (leaf[:, 0] >= s) & (leaf[:, 1] <= e)
        leaf = leaf[keep]
        dur = leaf[:, 1] - leaf[:, 0]
        for k, t in enumerate(np.bincount(leaf[:, 2].astype(int),
                                          weights=dur,
                                          minlength=len(d["names"]))):
            if t > 0:
                part = d["parts"][k]
                total[part] = total.get(part, 0.0) + t
    return {p: t / len(devs) / 1e9 for p, t in total.items()}


def us_per_tick(ctx: dict, part: str) -> float | None:
    """Leaf-op time of ``part`` per device over the ticks of the slice,
    in us; None when no op of the slice belongs to the part (a program
    without the scopes) or no tick was seen."""
    rec = record()
    ticks = devtrace.ticks(ctx["trace"])
    if rec is None or not ticks:
        return None
    t = part_seconds(rec, ctx["cell"].chips).get(part)
    return None if t is None else 1e6 * t / ticks


def spans(rec: dict, name: str) -> list:
    return [s for s in rec["spans"] if s[2] == name]


def sweep_build(rec: dict, n_buckets: int) -> dict | None:
    """The build of the first sweep whose planning and first chunk
    dispatch both lie in the slice: its ``lcdc.batch`` and ``lcdc.init``
    spans (one each per bucket; those of a retry do not count) and the
    device programs that ran on device 0 from the first batch's start
    to its first chunk program's. None unless the slice holds the
    whole build."""
    plans = spans(rec, "lcdc.plan")
    retries = spans(rec, "lcdc.retry")
    ends = [p[0] for p in plans[1:]] + [np.inf]
    for plan, end in zip(plans, ends):
        def inside(s):
            return plan[0] <= s[0] and s[1] <= end and not any(
                r[0] <= s[0] and s[1] <= r[1] for r in retries)
        chunk = [s for s in spans(rec, "lcdc.chunk") if inside(s)]
        if not chunk:
            continue
        batch = [s for s in spans(rec, "lcdc.batch") if inside(s)]
        init = [s for s in spans(rec, "lcdc.init") if inside(s)]
        if len(batch) != n_buckets or len(init) != n_buckets:
            return None
        build_s = sum(s[1] - s[0] for s in batch + init) / 1e9
        dev = devices(rec, 1)
        if not dev:
            return None
        name = chunk_program(dev[0])
        mods = sorted(dev[0]["modules"])
        first = next((m[0] for m in mods
                      if m[2] == name and m[0] >= chunk[0][0]), None)
        if first is None:
            return None
        t0 = batch[0][0]
        programs = sum(1 for m in mods if t0 <= m[0] < first)
        return {"build_s": build_s, "programs": programs}
    return None


def n_buckets(ctx: dict) -> int | None:
    """Hull buckets per sweep of the window, from the results' own
    ``plan_bucket`` (every sweep of a cell has the same plan)."""
    sweeps = ctx["window"]["sweeps"]
    results = sweeps[0].get("results") if sweeps else None
    if not results:
        return None
    return len({r["plan_bucket"] for r in results})


def idle_by_span(rec: dict) -> dict | None:
    """Device 0's idle time in the sweep boundary of the slice, split by
    the outermost ``lcdc.*`` span open on the host at each instant
    ("outside the program" where none is): {label: seconds}. The
    boundary runs from the last switch-kernel call before the widest
    space between two kernel calls to the first after it. None when the
    slice holds fewer than three kernel calls."""
    dev = devices(rec, 1)
    if not dev:
        return None
    d = dev[0]
    ops = np.asarray(d["ops"], np.float64).reshape(-1, 3)
    leaf = devtrace.leaves(ops)
    kern = np.asarray([devtrace.is_kernel(n) for n in d["names"]])
    starts = leaf[kern[leaf[:, 2].astype(int)], 0]
    if len(starts) < 3:
        return None
    j = int(np.argmax(np.diff(starts)))
    b0, b1 = starts[j], starts[j + 1]
    _, gaps = devtrace.union_gaps(leaf[:, :2])
    gaps = np.clip(gaps, b0, b1)
    gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    outer = []             # spans nest, and come sorted by start
    for s in rec["spans"]:
        if not outer or s[1] > outer[-1][1]:
            outer.append(s)
    out: dict = {}
    for g0, g1 in gaps:
        covered = 0.0
        for s0, s1, name, _ in outer:
            t = min(g1, s1) - max(g0, s0)
            if t > 0:
                out[name] = out.get(name, 0.0) + t / 1e9
                covered += t
        rest = (g1 - g0) - covered
        if rest > 0:
            out["outside the program"] = (
                out.get("outside the program", 0.0) + rest / 1e9)
    return out
