"""Smoke run of the LC/DC sweep engine on a TPU, through its user entry
points, at the paper's own evaluation size.

  python chip_smoke.py            # one chip: phases (a), (b), (c)
  python chip_smoke.py --chips 4  # four chips: phase (b) sharded over
                                  # all four vs its rows on one chip

(a) The Pallas switch kernel (``interpret=False``) against its jnp
    oracle at both tier shapes of the Fig 2 site, vmapped over a batch,
    with per-link valid masks and draining ports.
(b) The paper's evaluation end to end: ``run_sweep_planned`` on
    ``FBSite()`` x the five traces x gating {on, off} for 100,000 ticks
    with ``validate=True`` and x64 off. The compiled chunk program must
    hold the kernel (``tpu_custom_call``); no bucket may fail or be
    retried; every metric must be finite.
(c) Checkpoint/resume with the donated carry: the same grid, killed at
    a middle chunk and resumed from its newest checkpoint, must equal
    an uninterrupted run bit for bit.

Everything runs in this one process. Progress goes to stdout; the last
line is ``{"ok": true, "device": {...}}``. The script exits nonzero,
without that line, when JAX finds no TPU, when the repo's sources are
not next to it, or when any check fails.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: the paper's evaluation length (benchmarks/simcache.py TICKS), in
#: chunks of the engine's default length (simulator.CHUNK_TICKS)
TICKS, CHUNK_TICKS = 100_000, 10_000
#: phase (c): a sweep of RESUME_CHUNKS chunks, killed at the top of
#: chunk KILL_AT (so the newest checkpoint is boundary KILL_AT - 1)
RESUME_CHUNKS, KILL_AT = 4, 2
#: phase (a): scenarios per vmapped kernel call (phase (b)'s batch)
KERNEL_BATCH = 10
#: phase (a) tolerance on float outputs, relative and absolute. Kernel
#: and oracle are both float32 but are compiled separately (Mosaic vs
#: XLA): the divisions (room / arrivals, serve / backlog) and the lane
#: reductions (occupancy moments) may round differently, by a few ulp
#: of values up to ~1e3. 1e-5 is ~80 ulp: a real datapath error (a
#: wrong port, a lost arrival) is off by O(1).
KERNEL_TOL = 1e-5
CKPT_DIR = ROOT / "results" / "chip_smoke_ckpt"


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def numbers(v):
    """Every number inside a metric value (scalars, lists, dicts)."""
    if isinstance(v, dict):
        for x in v.values():
            yield from numbers(x)
    elif isinstance(v, (list, tuple, np.ndarray)):
        for x in np.asarray(v, dtype=np.float64).ravel():
            yield float(x)
    elif isinstance(v, (int, float, np.number)) and not isinstance(v, bool):
        yield float(v)


def same(a, b) -> bool:
    """Bit-for-bit equality of two metric values."""
    if isinstance(a, dict) or isinstance(b, dict):
        return (isinstance(a, dict) and isinstance(b, dict)
                and a.keys() == b.keys()
                and all(same(a[k], b[k]) for k in a))
    if isinstance(a, (list, tuple, np.ndarray)):
        x, y = np.asarray(a), np.asarray(b)
        return x.shape == y.shape and x.tobytes() == y.tobytes()
    return a == b or (a != a and b != b)


# ---- (a) kernel vs oracle ------------------------------------------------

def kernel_case(name, S_, L, K, serve_rate, seed):
    import jax
    import jax.numpy as jnp

    from repro.kernels import lcdc_switch, ref

    B = KERNEL_BATCH
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.uniform(ks[0], (B, S_, L, K)) * 15.0
    stage = jax.random.randint(ks[1], (B, S_), 1, L + 1)
    arr = jax.random.uniform(ks[2], (B, S_, K)) * 2.0
    drain = jax.random.bernoulli(ks[3], 0.4, (B, S_))
    # per-link faults, plus two whole-switch outages per scenario
    valid = jax.random.bernoulli(ks[4], 0.8, (B, S_, L)).at[:, :2].set(
        False)
    cap = jax.random.uniform(ks[5], (B,), minval=10.0, maxval=25.0)
    hi = jax.random.uniform(ks[6], (B,), minval=0.6, maxval=0.9)
    lo = jax.random.uniform(ks[7], (B,), minval=0.1, maxval=0.3)
    args = (q, stage, arr, drain, valid, cap, hi, lo)

    def call(fn, **kw):
        def one(q, st, a, d, v, c, h, lo_):
            return fn(q, st, a, d, valid=v, cap=c, hi=h, lo=lo_,
                      serve_rate=serve_rate, **kw)
        return jax.jit(jax.vmap(one))

    t0 = time.perf_counter()
    kern = call(lcdc_switch.switch_step, interpret=False).lower(
        *args).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in kern.as_text(),
          f"(a) {name}: the compiled kernel holds no tpu_custom_call")
    t0 = time.perf_counter()
    got = jax.block_until_ready(kern(*args))
    run_s = time.perf_counter() - t0
    want = jax.block_until_ready(call(ref.switch_step_ref)(*args))

    outs = ("queues", "served", "hi_trig", "lo_trig", "dropped",
            "enq_wait", "occ_m1", "occ_m2")
    worst = 0.0
    for out, g, w in zip(outs, got, want):
        g, w = np.asarray(g), np.asarray(w)
        check(g.shape == w.shape and g.dtype == w.dtype,
              f"(a) {name}.{out}: {g.dtype}{g.shape} vs oracle "
              f"{w.dtype}{w.shape}")
        if g.dtype.kind in "iub":
            # watermark triggers compare a backlog with hi/lo * cap:
            # random inputs land within an ulp of a threshold with
            # negligible probability, so they must agree exactly
            check(np.array_equal(g, w),
                  f"(a) {name}.{out}: {int(np.sum(g != w))} of {g.size} "
                  "trigger flags differ from the oracle")
            continue
        check(bool(np.all(np.isfinite(g))), f"(a) {name}.{out}: non-finite")
        err = np.abs(g - w)
        bad = err > KERNEL_TOL + KERNEL_TOL * np.abs(w)
        check(not bad.any(),
              f"(a) {name}.{out}: {int(bad.sum())} of {g.size} values off "
              f"the oracle by more than {KERNEL_TOL} (max {err.max():.3g})")
        worst = max(worst, float(err.max()))
    say(f"phase=a tier={name} shape=B{B}xS{S_}xL{L}xK{K} "
        f"compile_s={compile_s:.3f} run_s={run_s:.6f} "
        f"max_abs_err={worst:.3g} tol={KERNEL_TOL}")


def phase_kernel():
    # the Fig 2 site's two switch tiers: 128 RSWs with 4 uplinks and an
    # [intra, inter] split at 1 pkt/tick, 16 CSWs with 4 40G uplinks
    kernel_case("rsw", 128, 4, 2, 1.0, seed=1)
    kernel_case("csw", 16, 4, 1, 4.0, seed=2)


# ---- (b) the paper's evaluation ------------------------------------------

def compile_chunk_program(runs, n_ticks, shard):
    """AOT-compile the chunk program the sweep below dispatches (same
    seams, same operands); returns (compile seconds, HLO text)."""
    import jax.numpy as jnp

    from repro.core import simulator as S

    batch = S.make_multi_site_batch(runs)
    scen, state, fold, guard, tol = S._prepare_sweep_args(
        batch, validate=True, shard=shard)
    chunk = max(1, min(CHUNK_TICKS, n_ticks))
    t0 = time.perf_counter()
    comp = S._runner_for(scen).lower(
        batch.hull, scen, state, chunk, jnp.ones((chunk,), bool), fold,
        guard, jnp.asarray(0, jnp.int32), tol, True).compile()
    return time.perf_counter() - t0, comp.as_text()


def planned_sweep(runs, n_ticks, tag, **kw):
    """run_sweep_planned with every failure made loud: no error entry,
    no retried bucket, every metric finite."""
    from repro.core import simulator as S

    retried = []
    S.BUCKET_FAIL_HOOK = lambda k, stage: (
        retried.append(k) if stage == "retry" else None)
    try:
        t0 = time.perf_counter()
        res = S.run_sweep_planned(runs, n_ticks, validate=True,
                                  chunk_ticks=CHUNK_TICKS, **kw)
        run_s = time.perf_counter() - t0
    finally:
        S.BUCKET_FAIL_HOOK = None
    errs = [(r["label"], r["error"]) for r in res if "error" in r]
    check(not errs, f"{tag}: failed buckets: {errs}")
    check(not retried, f"{tag}: buckets {sorted(set(retried))} retried")
    for r in res:
        for k, v in r.items():
            check(all(math.isfinite(x) for x in numbers(v)),
                  f"{tag}: {r['label']}.{k} is not finite")
    return res, run_s


def phase_sweep(runs, shard, tag):
    from repro.analysis import hlo

    compile_s, txt = compile_chunk_program(runs, TICKS, shard)
    check(txt.count("tpu_custom_call") >= 2,
          f"({tag}) the chunk program holds {txt.count('tpu_custom_call')}"
          " tpu_custom_call ops, not the 2 switch kernels: the jnp oracle"
          " ran in place of the kernel")
    collectives = hlo.parse_collectives(txt).by_op()
    res, run_s = planned_sweep(runs, TICKS, f"({tag})", shard=shard)
    say(f"phase={tag} scenarios={len(runs)} ticks={TICKS} "
        f"compile_s={compile_s:.3f} run_s={run_s:.3f} "
        f"scenario_ticks_per_s={len(runs) * TICKS / run_s:.1f} "
        f"collectives={sorted(collectives)}")
    return res, collectives


def report_savings(res):
    for lc, base in zip(res[0::2], res[1::2]):
        check(lc["gating"] and not base["gating"]
              and lc["trace"] == base["trace"], "grid order changed")
        savings = lc["switch_energy_savings_frac"]
        penalty = lc["mean_latency_us"] / base["mean_latency_us"] - 1.0
        say(f"trace={lc['trace']} switch_energy_savings_frac={savings!r} "
            f"latency_penalty={penalty!r} "
            f"delivered_frac={lc['delivered_frac']!r}")
        check(0.0 < savings < 1.0,
              f"{lc['trace']}: LC/DC switch savings {savings} outside "
              "(0, 1)")


def phase_shard_parity(runs, sharded, n_dev):
    """Each device of the sharded sweep steps its own block of rows
    (the batch padded with copies of row 0 to a multiple of n_dev).
    The same block run on one chip (``shard=False``) is the same
    per-device program on the same rows, so the two must agree BIT FOR
    BIT. The whole batch on one chip is not the reference: a different
    per-device batch is a different compile (other layouts and
    fusions), and the watermark thresholds amplify its ulp-level
    rounding differences over 100k ticks into percent-level metric
    gaps."""
    per = -(-len(runs) // n_dev)
    padded = runs + [runs[0]] * (per * n_dev - len(runs))
    ref, run_s = [], 0.0
    for d in range(n_dev):
        res, s = planned_sweep(padded[d * per:(d + 1) * per], TICKS,
                               f"(b1 rows of device {d})", shard=False)
        ref += res
        run_s += s
    for got, want in zip(sharded, ref):
        diff = [k for k in got if not same(got[k], want[k])]
        check(not diff, f"(b4) {got['label']}: {diff} differ from the same "
              "rows on one chip")
    say(f"phase=b4_vs_rows_on_one_chip rows_per_device={per} "
        f"run_s={run_s:.3f} bit_identical=True")


# ---- (c) checkpoint/resume with the donated carry ------------------------

def phase_resume(runs):
    from repro.core import checkpoint as CK
    from repro.core import simulator as S

    n_ticks = RESUME_CHUNKS * CHUNK_TICKS
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    t_before = S.TRACE_COUNT
    ref, ref_s = planned_sweep(runs, n_ticks, "(c) uninterrupted")

    def kill(ci):
        if ci == KILL_AT:
            raise RuntimeError("smoke preemption")

    spec = CK.CheckpointSpec(directory=CKPT_DIR, every_chunks=1,
                             tag="smoke")
    S.CHUNK_HOOK = kill
    try:
        t0 = time.perf_counter()
        killed = S.run_sweep_planned(
            runs, n_ticks, validate=True, chunk_ticks=CHUNK_TICKS,
            checkpoint=spec, retry=S.BucketRetryPolicy(max_retries=0))
        killed_s = time.perf_counter() - t0
    finally:
        S.CHUNK_HOOK = None
    paths = {r.get("error", {}).get("checkpoint") for r in killed}
    check(len(paths) == 1 and None not in paths,
          f"(c) the killed sweep left checkpoints {paths}, not one")
    path = paths.pop()
    meta = CK.read_checkpoint(path)[0]
    check(meta["chunk_index"] == KILL_AT - 1,
          f"(c) newest checkpoint is chunk {meta['chunk_index']}, not "
          f"{KILL_AT - 1}")

    h0 = S.HOST_TRANSFER_COUNT
    t0 = time.perf_counter()
    resumed = S.resume_sweep(path)
    resume_s = time.perf_counter() - t0
    check(S.HOST_TRANSFER_COUNT - h0 == 1,
          f"(c) resume made {S.HOST_TRANSFER_COUNT - h0} host transfers")
    by_label = {r["label"]: r for r in ref}
    for r in resumed:
        want = by_label[r["label"]]
        diff = [k for k in r if not same(r[k], want[k])]
        check(not diff, f"(c) {r['label']}: resumed {diff} differ from "
              "the uninterrupted run")
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    say(f"phase=c scenarios={len(runs)} ticks={n_ticks} "
        f"killed_at_chunk={KILL_AT} resumed_from_chunk="
        f"{meta['chunk_index']} new_traces={S.TRACE_COUNT - t_before} "
        f"uninterrupted_s={ref_s:.3f} killed_s={killed_s:.3f} "
        f"resume_s={resume_s:.3f} bit_identical=True")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only phase (b), sharded over four chips, "
                    "vs each device's rows run on one chip")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {len(devs)} "
              f"{devs[0].platform} device(s))", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 1
    try:
        from repro.compile_cache import use_compile_cache
        from repro.core import simulator as S
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are not next to this "
              f"script ({e})", file=sys.stderr)
        return 1

    dev = devs[0]
    say(f"jax={jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind} device_count={len(devs)} "
        f"x64={bool(jax.config.jax_enable_x64)} "
        f"compile_cache={use_compile_cache()}")
    runs = S.grid_runs()          # FBSite() x 5 traces x gating on/off
    try:
        check(not jax.config.jax_enable_x64,
              "x64 is on: the chip runs the float32 Kahan fold")
        if args.chips == 4:
            check(S._should_shard(len(runs), None),
                  "the sweep would not shard over the four chips")
            sharded, coll = phase_sweep(runs, None, "b4")
            check(not coll, f"(b4) the sharded chunk program holds "
                  f"collectives {coll}")
            phase_shard_parity(runs, sharded, len(devs))
            report_savings(sharded)
        else:
            phase_kernel()
            say(f"peak_bytes_in_use={peak_bytes(dev)}")
            res, _ = phase_sweep(runs, None, "b")
            report_savings(res)
            say(f"peak_bytes_in_use={peak_bytes(dev)}")
            phase_resume(runs)
        say(f"peak_bytes_in_use={peak_bytes(dev)}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
