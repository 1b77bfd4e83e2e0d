"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the newest ``.xplane.pb`` under a directory into a small
record; ``reduce`` turns a record into the numbers the per-layer readers
and the result line use. A record is plain JSON, so a trimmed slice of a
real trace is kept in ``chipbench/data`` and reduced again by a test.

A device plane is one named ``/device:TPU:<n>``. Its op line (``XLA
Ops``) holds one event per operation the device ran, and nests them: a
``conditional`` or a fusion that calls others spans its children. Only
the innermost events (leaves) are work; an enclosing event also spans
the gaps between its children. So the device is busy in the union of
the leaf intervals, and the traced window of a device is the span from
its first op to its last.

A kernel is an op the compiler emits as a ``tpu_custom_call``: how a
Pallas kernel reaches the chip. Calls of one kernel are told apart by
their result shapes (the text between ``=`` and ``custom-call(``), so a
copy of the same call, as an unrolled loop makes, counts with it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


def is_kernel(name: str) -> bool:
    return "custom-call(" in name and "tpu_custom_call" in name


def kernel_signature(name: str) -> str:
    return name.split(" = ", 1)[-1].split(" custom-call(", 1)[0]


def short(name: str, n: int = 160) -> str:
    """An op's name for a report: the HLO text, cut."""
    return name if len(name) <= n else name[:n] + "..."


def load(directory) -> dict:
    """The record of the newest trace under ``directory``:
    {"devices": {n: {"names": [...], "ops": [[start_ns, end_ns, name
    index], ...], "modules": [[start_ns, end_ns, name], ...]}},
    "host": [[start_ns, end_ns, name], ...]} (host spans of the python
    threads)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {"devices": {}, "host": []}
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            index, names, ops, mods = {}, [], [], []
            for line in plane.lines:
                if line.name == OP_LINE:
                    for ev in line.events:
                        name = ev.name
                        k = index.get(name)
                        if k is None:
                            k = index[name] = len(names)
                            names.append(name)
                        ops.append([ev.start_ns, ev.end_ns, k])
                elif line.name == MODULE_LINE:
                    mods.extend([ev.start_ns, ev.end_ns, ev.name]
                                for ev in line.events)
            devices[int(m.group(1))] = {"names": names, "ops": ops,
                                        "modules": mods}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([ev.start_ns, ev.end_ns, ev.name]
                            for ev in line.events)
    return {"devices": devices, "host": host}


def leaves(ops: np.ndarray) -> np.ndarray:
    """Rows of ``ops`` (start, end, name) that hold no other op, sorted by
    start. Ops on one line nest properly, so after sorting by start (the
    longer first on a tie) an op holds another exactly when the next op
    starts before it ends."""
    order = np.lexsort((-ops[:, 1], ops[:, 0]))
    ops = ops[order]
    parent = np.zeros(len(ops), bool)
    parent[:-1] = ops[1:, 0] < ops[:-1, 1]
    return ops[~parent]


def union_gaps(iv: np.ndarray):
    """(covered length, gaps as rows (start, end)) of intervals sorted by
    start."""
    if len(iv) == 0:
        return 0.0, np.zeros((0, 2))
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = np.append(reach[np.flatnonzero(new)[1:] - 1], reach[-1])
    covered = float(np.sum(ends - starts))
    gaps = np.stack([ends[:-1], starts[1:]], axis=1)
    return covered, gaps


#: gaps shorter than this are labelled by the next op alone
HOST_LABEL_MIN_NS = 10_000


class HostSpans:
    """The host spans of a trace, to ask what the host was doing."""

    def __init__(self, host):
        self.names = [h[2] for h in host]
        iv = np.asarray([h[:2] for h in host], np.float64).reshape(-1, 2)
        self.start, self.end = iv[:, 0], iv[:, 1]

    def label(self, t) -> str:
        """The innermost host span that holds time ``t``."""
        hold = np.flatnonzero((self.start <= t) & (t <= self.end))
        if not len(hold):
            return "no host span"
        k = hold[np.argmin(self.end[hold] - self.start[hold])]
        return self.names[int(k)]


def steady_ticks(leaf: np.ndarray, starts: np.ndarray):
    """The steady ticks before a sweep boundary, from the start times of
    one kernel that runs once a tick: the boundary is the widest space
    between two of its calls, and counts only when it is more than ten
    times their median spacing with a call on each side. Returns
    (busy, window, ticks) over the whole ticks from the first call to
    the last before the boundary, or None."""
    if len(starts) < 3:
        return None
    space = np.diff(starts)
    j = int(np.argmax(space))
    if j < 1 or space[j] <= 10.0 * np.median(space):
        return None
    t0, t1 = starts[0], starts[j]
    inside = leaf[(leaf[:, 0] >= t0) & (leaf[:, 0] < t1), :2].copy()
    inside[:, 1] = np.minimum(inside[:, 1], t1)
    return union_gaps(inside)[0], float(t1 - t0), j


def reduce(rec: dict, n_devices: int = 1) -> dict:
    """Device numbers of a trace, averaged over the first ``n_devices``
    devices (those a cell uses). Times in seconds.

    busy_s          union of leaf op intervals
    window_s        first op start to last op end
    kernel_s        leaf time of kernel ops
    kernel_calls    {result-shape signature: calls}
    program_busy_s  {program: leaf busy time inside its module events}
    steady          for a slice across a sweep boundary (None on any
                    device without one): {"busy_s", "window_s",
                    "ticks"} of the whole ticks before the boundary,
                    told by the calls of the kernel called most
    device_ops      top 10 [op, seconds] by leaf time
    idle_gaps       top 10 [what came next on the device and what the
                    host was doing, seconds]: the gaps between leaves,
                    summed by label (the host's part for gaps of 10 us
                    and more)
    """
    keys = sorted(rec["devices"], key=int)[:n_devices]
    devs = [rec["devices"][k] for k in keys]
    empty = {"busy_s": 0.0, "window_s": 0.0, "kernel_s": 0.0,
             "kernel_calls": {}, "program_busy_s": {}, "steady": None,
             "device_ops": [], "idle_gaps": [], "devices": 0}
    if not devs or not any(d["ops"] for d in devs):
        return empty
    busy = window = kern = 0.0
    steady = np.zeros(3)
    calls, prog, by_op, by_gap = {}, {}, {}, {}
    host = HostSpans(rec["host"])
    for d in devs:
        names = d["names"]
        ops = np.asarray(d["ops"], np.float64).reshape(-1, 3)
        if not len(ops):
            steady = None
            continue
        leaf = leaves(ops)
        covered, gaps = union_gaps(leaf[:, :2])
        busy += covered
        window += float(ops[:, 1].max() - ops[:, 0].min())
        dur = leaf[:, 1] - leaf[:, 0]
        ids = leaf[:, 2].astype(int)
        per_name = np.bincount(ids, weights=dur, minlength=len(names))
        n_calls = np.bincount(ids, minlength=len(names))
        sig_ids = {}
        for k, name in enumerate(names):
            if per_name[k] > 0:
                by_op[name] = by_op.get(name, 0.0) + per_name[k]
            if is_kernel(name):
                kern += per_name[k]
                sig = kernel_signature(name)
                calls[sig] = calls.get(sig, 0) + int(n_calls[k])
                sig_ids.setdefault(sig, []).append(k)
        if steady is not None:
            tick_ids = max(sig_ids.values(), default=[],
                           key=lambda ks: int(n_calls[ks].sum()))
            got = steady_ticks(leaf, leaf[np.isin(ids, tick_ids), 0])
            steady = None if got is None else steady + np.asarray(got)
        for ms, me, mname in d["modules"]:
            inside = leaf[(leaf[:, 0] >= ms) & (leaf[:, 1] <= me)]
            prog[mname] = prog.get(mname, 0.0) + union_gaps(inside[:, :2])[0]
        # label each gap by the op that ends it and by the host
        nxt = np.searchsorted(leaf[:, 0], gaps[:, 1])
        for (g0, g1), i in zip(gaps, nxt):
            name = names[int(leaf[min(i, len(leaf) - 1), 2])]
            label = f"before {short(name, 60)}"
            if g1 - g0 >= HOST_LABEL_MIN_NS:
                label += f" | host: {host.label((g0 + g1) / 2)}"
            by_gap[label] = by_gap.get(label, 0.0) + (g1 - g0)
    n = len(devs)

    def top(table):
        return [[k, v / n / 1e9] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy / n / 1e9, "window_s": window / n / 1e9,
        "kernel_s": kern / n / 1e9,
        "kernel_calls": {k: v / n for k, v in calls.items()},
        "program_busy_s": {k: v / n / 1e9 for k, v in prog.items()},
        "steady": None if steady is None else {
            "busy_s": steady[0] / n / 1e9, "window_s": steady[1] / n / 1e9,
            "ticks": steady[2] / n},
        "device_ops": [[short(k), t] for k, t in top(by_op)],
        "idle_gaps": top(by_gap), "devices": n,
    }


def read(path) -> dict:
    """A record kept as gzipped JSON (the test's recorded slice)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def ticks(tr: dict):
    """Ticks simulated in the traced window: the calls of the kernel
    called most, which the step calls once per tier and tick. None when
    no kernel ran."""
    return max(tr["kernel_calls"].values()) if tr["kernel_calls"] else None
