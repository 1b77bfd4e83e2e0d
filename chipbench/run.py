#!/usr/bin/env python3
"""Chip benchmark of the LC/DC sweep engine.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
      --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the TPU it is started on and
prints one JSON object as the last line of standard output. In order:

1. Look for a TPU and the chips the cell asks for; turn on JAX's
   persistent compilation cache inside the checkout.
2. Build the cell from its files: the configuration
   (``chipbench/configs/<config>.json``) and the traffic mix
   (``chipbench/traffic/<traffic>.json``).
3. Warm up: one sweep of one chunk through ``run_sweep_planned`` at the
   cell's own shapes. Set-up ends here.
4. The window: a closed loop of whole sweeps through
   ``run_sweep_planned`` with its defaults (device fold, pipeline,
   ``validate=False``; ``shard=False`` on one chip, automatic sharding
   on four). Sweep i takes seeds drawn from ``--seed`` and i. Sweeps
   start until ``--seconds`` have passed; the one in flight finishes.
5. Check the window's answers against the plain reference
   (``chipbench/reference/<reference>.py``, named by the configuration)
   on a sample drawn from the seed, and print the result.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles a slice of the window across the boundary between its second
and third sweeps (so it runs three sweeps at the least) and reports the
per-layer metrics, each read by ``chipbench/metrics/<name>.py``.

The run exits nonzero and prints no result when JAX finds no TPU or
fewer chips than the cell asks for, when the repository's sources are
missing, when anything compiles inside the window, and when a bucket
of a sweep fails or is retried.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: the traced slice spans the end of the second sweep and the start of
#: the third: it starts TRACE_LEAD_S before the second sweep is due to
#: return (as long as the first took) and stops TRACE_TAIL_S after the
#: third sweep's first chunk is dispatched
TRACE_LEAD_S = 0.15
TRACE_TAIL_S = 0.1
TRACE_DIR = BENCH / "out" / "trace"
#: JAX's event for a backend compile, or a load from the persistent
#: cache (eager ops record trace events on every call, and no compile)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RunFailure(Exception):
    """A rule of the run was broken: exit nonzero, print no result."""


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    with open("/proc/uptime") as f:
        return float(f.read().split()[0]) - start


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---- the cell's files -----------------------------------------------------

class Cell:
    """One entry of BENCHMARK.json's workloads, with its files."""

    def __init__(self, name: str, root: Path = ROOT):
        bench = json.loads((root / "BENCHMARK.json").read_text())
        self.bench = bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise RunFailure(f"no workload {name!r} in BENCHMARK.json")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.cfg = json.loads((root / conf["file"]).read_text())
        self.traffic = json.loads(
            (BENCH / "traffic" / f"{self.entry['traffic']}.json").read_text())
        self.n_ticks = int(self.traffic["ticks"])
        self.chunk = int(self.traffic["chunk_ticks"])
        self.rows = build_rows(self.traffic, self.cfg)

    def reference(self):
        name = self.cfg["reference"]
        if name not in _REFERENCES:
            _REFERENCES[name] = load_module(
                BENCH / "reference" / f"{name}.py",
                f"chipbench_reference_{name}")
        return _REFERENCES[name]


_REFERENCES: dict = {}


def build_rows(traffic: dict, cfg: dict) -> list:
    """The rows of a traffic mix: an explicit list, or the grid traces x
    gating x rate scales x replicas (in that nesting order). A row that
    gives a flow ``load`` gets the arrival rate that offers it."""
    if "rows" in traffic:
        rows = [dict(r) for r in traffic["rows"]]
    else:
        g = traffic["grid"]
        rows = [{"trace": t, "gating": gate, "rate_scale": rs,
                 "replica": k}
                for t in g["traces"] for gate in g["gating"]
                for rs in g["rate_scales"] for k in range(g["replicas"])]
    for r in rows:
        if "load" in r:
            r["flow_arrival_rate"] = arrival_rate(cfg, r)
    return rows


def mean_flow_pkts(cdf, n: int = 1 << 20) -> float:
    """Mean flow size of a CDF table ``[[size_pkts, prob], ...]`` as the
    engine samples it: log-linear between anchors, rounded up to whole
    packets; the midpoint rule over ``n`` quantiles."""
    import numpy as np
    s = np.asarray([a for a, _ in cdf], np.float64)
    p = np.asarray([b for _, b in cdf], np.float64)
    u = (np.arange(n) + 0.5) / n
    seg = np.clip(np.sum(u[:, None] >= p, axis=1) - 1, 0, len(p) - 2)
    frac = np.clip((u - p[seg]) / np.maximum(p[seg + 1] - p[seg], 1e-9),
                   0.0, 1.0)
    size = np.maximum(np.ceil(s[seg] * (s[seg + 1] / s[seg]) ** frac), 1.0)
    return float(np.mean(size))


def arrival_rate(cfg: dict, row: dict) -> float:
    """Flow arrivals per rack and tick that offer ``row["load"]`` of the
    rack's access links (one per server, each carrying the flows' line
    rate): load x servers x line rate / mean flow size."""
    fl = cfg["flows"]
    capacity = cfg["site"]["servers_per_rack"] * fl["line_rate_pkts_per_tick"]
    return float(row["load"]) * capacity / mean_flow_pkts(
        fl["size_cdfs"][row["flow_size_dist"]])


def row_seeds(seed: int, sweep: int, n: int) -> list:
    """Seeds of the rows of sweep ``sweep`` of a run with ``--seed``:
    distinct streams for every (seed, sweep, row), fixed by the two."""
    import numpy as np
    ss = np.random.SeedSequence([seed & (2 ** 64 - 1), sweep])
    return [int(x) for x in ss.generate_state(n, np.uint32)]


def sim_params(S, cfg: dict, row: dict):
    """The program's SimParams for one row, every knob from the files."""
    from repro.core.topology import FBSite
    from repro.core.traffic import TrafficSpec
    sw, op = cfg["switch"], cfg["optics"]
    kw = {k: row[k] for k in ("flow_mode", "flow_size_dist",
                              "flow_arrival_rate", "incast_degree",
                              "flow_table_cap") if k in row}
    return S.SimParams(
        spec=TrafficSpec(row["trace"], **cfg["traces"][row["trace"]]),
        site=FBSite(**cfg["site"]), gating_enabled=bool(row["gating"]),
        rate_scale=float(row.get("rate_scale", 1.0)),
        queue_cap=float(sw["queue_cap_pkts"]), hi=float(sw["hi_watermark"]),
        lo=float(sw["lo_watermark"]), dwell=int(sw["dwell_ticks"]),
        wake_fail_prob=op["wake_fail_prob"],
        wake_jitter_frac=op["wake_jitter_frac"],
        link_mtbf_ticks=op["link_mtbf_ticks"],
        repair_ticks=op["repair_ticks"],
        plane_fail_prob=op["plane_fail_prob"],
        fault_fallback=op["fault_fallback"], **kw)


# ---- the timed path -------------------------------------------------------

class Engine:
    """The system under test, driven as its users drive it."""

    def __init__(self, cell: Cell):
        from repro.core import simulator as S
        self.S = S
        self.cell = cell
        self.shard = False if cell.chips == 1 else None
        self.params = [sim_params(S, cell.cfg, r) for r in cell.rows]
        self.watch = CompileWatch()

    def runs(self, seed: int, sweep: int):
        seeds = row_seeds(seed, sweep, len(self.params))
        return list(zip(self.params, seeds))

    def sweep(self, runs, n_ticks: int):
        """One planned sweep to finalized metrics; a failed or retried
        bucket fails the run."""
        S = self.S
        retried = []
        S.BUCKET_FAIL_HOOK = lambda k, stage: (
            retried.append(k) if stage == "retry" else None)
        try:
            res = S.run_sweep_planned(runs, n_ticks,
                                      chunk_ticks=self.cell.chunk,
                                      shard=self.shard)
        finally:
            S.BUCKET_FAIL_HOOK = None
        errs = [(r["label"], r["error"]) for r in res if "error" in r]
        if errs:
            raise RunFailure(f"failed buckets: {errs}")
        if retried:
            raise RunFailure(f"buckets {sorted(set(retried))} were retried")
        return res


class CompileWatch:
    """Counts JAX's backend compiles and compile-cache loads."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self.on)

    def on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


def run_window(eng: Engine, seed: int, seconds: float, tracer=None):
    """Sweeps until ``seconds`` have passed (and, when traced, until the
    tracer has its slices). Returns the window's record: every sweep's
    seeds and results, its time, and its counters."""
    S = eng.S
    watch = eng.watch
    tc0, hc0, cc0 = S.TRACE_COUNT, S.HOST_TRANSFER_COUNT, watch.n
    sweeps = []
    t0 = t = time.perf_counter()
    if tracer is not None:
        tracer.arm(t0)
    while True:
        runs = eng.runs(seed, len(sweeps) + 1)
        if tracer is not None:
            tracer.sweep_called(len(sweeps) + 1, t,
                                sweeps[0]["seconds"] if sweeps else None)
        res = eng.sweep(runs, eng.cell.n_ticks)
        t1 = time.perf_counter()
        sweeps.append({"seeds": [s for _, s in runs], "results": res,
                       "seconds": t1 - t})
        t = t1
        if t1 - t0 >= seconds and (tracer is None or tracer.done.is_set()):
            break
    if tracer is not None:
        tracer.join()
    if S.TRACE_COUNT != tc0 or watch.n != cc0:
        raise RunFailure(
            f"compiled inside the window: {S.TRACE_COUNT - tc0} step "
            f"traces, {watch.n - cc0} backend compiles")
    return {"sweeps": sweeps, "seconds": t1 - t0,
            "host_transfers": S.HOST_TRANSFER_COUNT - hc0}


class Tracer:
    """Profiles one slice of the window in a thread of its own: from
    shortly before the second sweep's device work ends to shortly after
    the third sweep's first chunk is dispatched (the program's
    ``CHUNK_HOOK`` tells when). So it holds steady ticks on both sides
    of a boundary, where the host fetches one sweep's totals, finalizes
    them and builds the next sweep. The Python tracer stays off, so the
    host's work runs at its own speed."""

    def __init__(self, directory: Path, S):
        self.dir = directory
        self.S = S
        self.thread = None
        self.error = None
        self.done = threading.Event()
        self.calls = {}                  # sweep -> (call time, first's s)
        self.chunk0 = {}                 # sweep -> first chunk dispatched
        self.cond = threading.Condition()
        self.times = {}                  # profiler calls, from the start

    def sweep_called(self, i: int, t: float, first_s):
        with self.cond:
            self.calls[i] = (t, first_s)
            self.cond.notify_all()

    def chunk_hook(self, ci: int):
        if ci == 0:
            with self.cond:
                self.chunk0[max(self.calls)] = time.perf_counter()
                self.cond.notify_all()

    def _wait(self, table: dict, i: int):
        with self.cond:
            self.cond.wait_for(lambda: i in table)
            return table[i]

    def arm(self, t0):
        import jax

        def mark(name):
            self.times[name] = time.perf_counter() - t0

        def go():
            try:
                t2, first_s = self._wait(self.calls, 2)
                time.sleep(max(0.0, t2 + first_s - TRACE_LEAD_S
                               - time.perf_counter()))
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                mark("start_call")
                jax.profiler.start_trace(str(self.dir),
                                         profiler_options=opts)
                mark("started")
                try:
                    t3 = self._wait(self.chunk0, 3)
                    mark("third_dispatched")
                    time.sleep(max(0.0, t3 + TRACE_TAIL_S
                                   - time.perf_counter()))
                finally:
                    mark("stop_call")
                    jax.profiler.stop_trace()
                    mark("stopped")
            except Exception as e:   # noqa: BLE001 — reported after
                self.error = e
            finally:
                self.done.set()
        self.S.CHUNK_HOOK = self.chunk_hook
        self.thread = threading.Thread(target=go, daemon=True)
        self.thread.start()

    def join(self):
        self.thread.join()
        self.S.CHUNK_HOOK = None
        if self.error is not None:
            raise RunFailure(f"profiler: {self.error!r}")


# ---- correctness ----------------------------------------------------------

def sample(cell: Cell, window: dict, seed: int):
    """The sweep and rows to check, drawn from the seed."""
    import numpy as np
    rng = np.random.default_rng([seed & (2 ** 64 - 1), 0x5EED])
    k = int(rng.integers(len(window["sweeps"])))
    n = len(cell.rows)
    m = min(int(cell.traffic["check"]["sample_rows"]), n)
    rows = sorted(int(i) for i in rng.choice(n, size=m, replace=False))
    return k, rows


def check_window(cell: Cell, window: dict, seed: int):
    """Compare the sampled answers of the window with the reference."""
    from chipbench import check
    k, idx = sample(cell, window, seed)
    sw = window["sweeps"][k]
    rows = [cell.rows[i] for i in idx]
    seeds = [sw["seeds"][i] for i in idx]
    got = [sw["results"][i] for i in idx]
    want = cell.reference().reference_metrics(cell.cfg, rows, seeds,
                                              cell.n_ticks)
    nums = check.numbers(cell.traffic["check"], got, want)
    return check.verdict(cell.traffic["check"], nums)


# ---- the run --------------------------------------------------------------

def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def layer_metrics(cell: Cell, window: dict, trace: dict, peaks: dict,
                  device_kind: str) -> dict:
    """Every per-layer metric of BENCHMARK.json that lists this cell (or
    lists none), each from its own reader; a reader that finds nothing
    to read returns None and the metric is left out."""
    ctx = {"cell": cell, "window": window, "trace": trace, "peaks": peaks,
           "device_kind": device_kind}
    out = {}
    for m in cell.bench["per_layer"]:
        if "workloads" in m and cell.name not in m["workloads"]:
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"chipbench_metric_{m['name']}")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except RunFailure as e:
        print(f"chipbench: FAIL: {e}", file=sys.stderr, flush=True)
        return 1


def run(args, cell: Cell | None = None, look_for_chip: bool = True) -> int:
    """One run. Tests pass a cell cut to CPU size and skip the look for
    a chip; everything after it is what runs on the chip."""
    cell = cell or Cell(args.workload)
    import jax

    devs = jax.devices()
    if look_for_chip and devs[0].platform != "tpu":
        raise RunFailure(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                         "device(s)")
    if len(devs) < cell.chips:
        raise RunFailure(f"{cell.name} needs {cell.chips} chips, JAX sees "
                         f"{len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if look_for_chip and devs[0].device_kind not in peaks:
        raise RunFailure(f"chipbench/peaks.json has no peaks for "
                         f"{devs[0].device_kind!r}")
    if jax.config.jax_enable_x64:
        raise RunFailure("x64 is on; the benchmark runs the float32 engine")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        raise RunFailure(f"the repository's sources are missing ({e})")
    cache = use_compile_cache()
    # small programs (batch build, state init) are cached too, so a warm
    # run compiles nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"chipbench: jax={jax.__version__} device_kind="
          f"{devs[0].device_kind} device_count={len(devs)} x64=False "
          f"compile_cache={cache}", file=sys.stderr, flush=True)

    eng = Engine(cell)
    if cell.chips > 1:
        mode = eng.S.execution_mode(shard=None, n_scenarios=len(cell.rows))
        if mode["devices"] != cell.chips:
            raise RunFailure(f"the scenario axis would run on "
                             f"{mode['devices']} devices, not {cell.chips}")
    # warm-up: one chunk at the cell's own shapes (seeds of sweep 0)
    eng.sweep(eng.runs(args.seed, 0), cell.chunk)
    setup_s = process_age_s()

    tracer = Tracer(TRACE_DIR, eng.S) if args.trace else None
    if tracer is not None:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    window = run_window(eng, args.seed, args.seconds, tracer)
    if any(m.split(".")[-1] == "simcache" for m in sys.modules):
        raise RunFailure("a result cache (simcache) was imported: the "
                         "window must simulate every sweep")
    n_sweeps = len(window["sweeps"])
    work = n_sweeps * len(cell.rows) * cell.n_ticks
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": peak_bytes(devs[:cell.chips])}

    if tracer is not None:
        from chipbench import devtrace as T
        tr = T.reduce(T.load(TRACE_DIR), n_devices=cell.chips)
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        metrics = layer_metrics(cell, window, tr, peaks,
                                devs[0].device_kind)
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
        print(f"trace: profiler {tracer.times!r} s into the window; slice "
              f"{tr['window_s']!r} s, busy {tr['busy_s']!r} s, "
              f"{T.ticks(tr)} ticks; steady ticks before the boundary "
              f"{tr['steady']!r}", file=sys.stderr, flush=True)
    else:
        metrics = {
            "scenario_ticks_per_s": {"value": work / window["seconds"],
                                     "unit": "scen-ticks/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        breakdown = None

    correct, table = check_window(cell, window, args.seed)
    for name, t in table.items():
        print(f"check {name}={t['value']!r} limit={t['limit']!r} "
              f"at={t['at']}", file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": n_sweeps * len(cell.rows),
           "failed": 0, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # each sweep's seconds, to tell a slow sweep from a slow set-up
    out["sweep_s"] = [sw["seconds"] for sw in window["sweeps"]]
    out["checks"] = {k: {"value": t["value"], "limit": t["limit"]}
                     for k, t in table.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
