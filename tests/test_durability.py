"""Durable sweep execution (checkpoint/resume + retry policy):

* chunk-boundary snapshots are observation-only — a checkpointed run
  and a resume from a kill-at-chunk-k interruption are BIT-identical to
  the uninterrupted run (same PARITY_KEYS values, no new traces), with
  the host-transfer pin at exactly 1 + n_checkpoints;
* corrupt, truncated, or engine-mismatched checkpoints are rejected
  fail-fast with a structured ``CheckpointError`` naming the mismatch;
* ``BucketRetryPolicy`` sequences capped exponential backoff, the
  per-bucket deadline cuts retries (never finished work), and an
  exhausted bucket degrades to structured errors + a resumable salvage
  checkpoint while every other bucket's results come back intact;
* the whole resume contract holds under a sharded 4-device layout,
  including resuming a single-device checkpoint on four devices
  (subprocess leg; CI runs this file under both JAX_ENABLE_X64 modes).
"""
import dataclasses
import io
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import checkpoint as CK
from repro.core import simulator as S
from repro.core.topology import FBSite
from repro.core.traffic import TRAFFIC_SPECS
from tests._subproc import run_with_devices

TICKS, CHUNK = 240, 40          # 6 chunks; cadence-2 boundaries {2, 4}
SITE = FBSite(n_clusters=2, racks_per_cluster=3, servers_per_rack=4,
              csw_per_cluster=2, n_fc=2, csw_ring_links=2, fc_ring_links=4)
# every stateful mechanism rides the snapshot: fault timers, plane
# hazards, the flow table, plus a gating-off row and a knob-free row
KNOBS = dict(link_mtbf_ticks=400.0, repair_ticks=30, wake_fail_prob=0.05,
             plane_fail_prob=1e-3, flow_mode=1, rate_scale=1.5)


def _runs():
    spec = TRAFFIC_SPECS["fb_hadoop"]
    return [(S.SimParams(spec=spec, site=SITE, **KNOBS), 3),
            (S.SimParams(spec=spec, site=SITE, gating_enabled=False,
                         **KNOBS), 4),
            (S.SimParams(spec=spec, site=SITE), 5)]


def _batch():
    return S.make_batch(_runs())


def _spec(directory, **kw):
    kw.setdefault("every_chunks", 2)
    kw.setdefault("tag", "t")
    kw.setdefault("keep", 8)
    return CK.CheckpointSpec(directory=directory, **kw)


@pytest.fixture(scope="module")
def reference():
    """The uninterrupted run every parity test compares against
    (validate=True so the guard array rides the snapshots too)."""
    return S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK, validate=True)


@pytest.fixture(scope="module")
def ckpt_file(tmp_path_factory):
    """A real mid-run checkpoint (boundary 4 of 6) for the tamper and
    rejection tests to copy and mutate."""
    d = tmp_path_factory.mktemp("seed-ckpts")
    S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK, validate=True,
                checkpoint=_spec(d, tag="seed"))
    path = CK.latest_checkpoint(d, "seed")
    assert path is not None
    return path


# ---- checkpointed runs are observation-only -----------------------------

def test_checkpointed_run_bit_identical_with_pins(tmp_path, reference):
    """Cadenced snapshots change NOTHING about the run: bit-identical
    metrics, zero new traces, and exactly 1 + n_checkpoints transfers
    (cadence 2 over 6 chunks -> boundaries {2, 4}; the final boundary
    is never snapshotted)."""
    t0, h0 = S.TRACE_COUNT, S.HOST_TRANSFER_COUNT
    res = S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK, validate=True,
                      checkpoint=_spec(tmp_path))
    assert S.TRACE_COUNT - t0 == 0
    assert S.HOST_TRANSFER_COUNT - h0 == 1 + 2
    assert [c for c, _ in CK.list_checkpoints(tmp_path, "t")] == [2, 4]
    diff, key = S.worst_parity(reference, res)
    assert diff == 0.0, key


def test_kill_at_chunk_k_then_resume_bit_identical(tmp_path, reference):
    """Preemption at the top of chunk 4: the boundary-4 snapshot was
    stashed but not yet written (deferred-by-one), so only boundary 2
    survives — and resuming it replays chunks 2..5 bit-identically in
    ONE further transfer."""
    def hook(ci):
        if ci == 4:
            raise RuntimeError("preempted")

    S.CHUNK_HOOK = hook
    try:
        with pytest.raises(RuntimeError, match="preempted"):
            S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK,
                        validate=True, checkpoint=_spec(tmp_path))
    finally:
        S.CHUNK_HOOK = None
    found = CK.list_checkpoints(tmp_path, "t")
    assert [c for c, _ in found] == [2]
    h0 = S.HOST_TRANSFER_COUNT
    res = S.resume_sweep(found[0][1])
    assert S.HOST_TRANSFER_COUNT - h0 == 1
    diff, key = S.worst_parity(reference, res)
    assert diff == 0.0, key


def test_resume_keeps_checkpointing_at_cadence(tmp_path, reference):
    """Passing a CheckpointSpec to resume_sweep continues snapshotting
    at the same ABSOLUTE chunk cadence (boundary 4 here), still
    bit-identically."""
    def hook(ci):
        if ci == 4:
            raise RuntimeError("preempted")

    S.CHUNK_HOOK = hook
    try:
        with pytest.raises(RuntimeError, match="preempted"):
            S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK,
                        validate=True, checkpoint=_spec(tmp_path))
    finally:
        S.CHUNK_HOOK = None
    h0 = S.HOST_TRANSFER_COUNT
    res = S.resume_sweep(CK.latest_checkpoint(tmp_path, "t"),
                         checkpoint=_spec(tmp_path))
    assert S.HOST_TRANSFER_COUNT - h0 == 1 + 1
    assert [c for c, _ in CK.list_checkpoints(tmp_path, "t")] == [2, 4]
    diff, key = S.worst_parity(reference, res)
    assert diff == 0.0, key


def test_prune_bounds_retained_files(tmp_path, reference):
    """keep=1 with a cadence of 1 leaves exactly the newest resumable
    boundary (5 of 6) on disk — and it still resumes bit-identically."""
    S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK, validate=True,
                checkpoint=_spec(tmp_path, every_chunks=1, keep=1))
    found = CK.list_checkpoints(tmp_path, "t")
    assert [c for c, _ in found] == [5]
    diff, key = S.worst_parity(reference, S.resume_sweep(found[0][1]))
    assert diff == 0.0, key


def test_checkpoint_resume_with_donated_carry(tmp_path, reference,
                                             monkeypatch):
    """On an accelerator every chunk dispatch DONATES the carry it
    consumes, deleting those buffers. Donation is forced here on the
    CPU: cadenced snapshots, a kill + resume, the host-fold path and
    return_state must never read a donated buffer, and the metrics
    stay bit-identical (host fold: within its 1e-6 contract)."""
    import jax
    donating = jax.jit(S._sweep_chunk_impl,
                       static_argnames=("site", "length", "validate"),
                       donate_argnames=("state", "fold"))
    monkeypatch.setattr(S, "_runner_for", lambda scen: donating)

    h0 = S.HOST_TRANSFER_COUNT
    res, state = S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK,
                             validate=True, return_state=True,
                             checkpoint=_spec(tmp_path, every_chunks=1))
    assert S.HOST_TRANSFER_COUNT - h0 == 1 + 5
    assert state.rsw_q.shape[0] == 3
    diff, key = S.worst_parity(reference, res)
    assert diff == 0.0, key

    def hook(ci):
        if ci == 3:
            raise RuntimeError("preempted")

    monkeypatch.setattr(S, "CHUNK_HOOK", hook)
    with pytest.raises(RuntimeError, match="preempted"):
        S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK, validate=True,
                    checkpoint=_spec(tmp_path, tag="kill",
                                     every_chunks=1))
    monkeypatch.setattr(S, "CHUNK_HOOK", None)
    found = CK.list_checkpoints(tmp_path, "kill")
    assert [c for c, _ in found] == [1, 2]
    diff, key = S.worst_parity(reference, S.resume_sweep(found[-1][1]))
    assert diff == 0.0, key

    host = S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK, validate=True,
                       fold="host")
    assert S.worst_parity(reference, host)[0] <= 1e-6


def test_host_fold_checkpoint_rejected(tmp_path):
    """The host-fold path synchronizes per chunk already; checkpointing
    it would pin a second fetch discipline, so it is an upfront error
    on both entry points."""
    with pytest.raises(ValueError, match="fold='device'"):
        S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK, fold="host",
                    checkpoint=_spec(tmp_path))
    with pytest.raises(ValueError, match="fold='device'"):
        S.run_sweep_planned(_runs(), TICKS, chunk_ticks=CHUNK,
                            fold="host", checkpoint=_spec(tmp_path))


def test_checkpoint_spec_validation():
    for kw in (dict(every_chunks=0), dict(every_chunks=1.5),
               dict(keep=0), dict(tag="bad/tag"), dict(tag="")):
        with pytest.raises(ValueError, match="CheckpointSpec"):
            CK.CheckpointSpec(**kw)
    assert CK.CheckpointSpec(tag="a", every_chunks=3).path_for(7).name \
        == "a-00000007.ckpt.npz"


# ---- corrupt / mismatched checkpoints fail fast -------------------------

def _rewritten(src, dst, mutate):
    """Copy a checkpoint applying ``mutate(meta, arrays)``; the rewrite
    restamps the content checksum, so what's probed is the ENGINE-level
    rejection in resume_sweep, not the file integrity layer."""
    meta, arrays = CK.read_checkpoint(src)
    mutate(meta, arrays)
    return CK.write_checkpoint(dst, meta, arrays)


def _drop_state_leaf(meta, arrays):
    name = next(n for n in sorted(arrays) if n.startswith("state"))
    del arrays[name]


def _reshape_state_leaf(meta, arrays):
    name = next(n for n in sorted(arrays) if n.startswith("state"))
    arrays[name] = np.repeat(arrays[name], 2, axis=0)


@pytest.mark.parametrize("reason,mutate", [
    ("sim_schema", lambda m, a: m.update(sim_schema=999)),
    ("fingerprint", lambda m, a: m.update(fault_knobs=m["fault_knobs"][:-1])),
    ("fingerprint", lambda m, a: m.update(flow_knobs=m["flow_knobs"] + ["ghost"])),
    ("scenario_fields",
     lambda m, a: m.update(scenario_fields=m["scenario_fields"] + ["ghost"])),
    ("x64_mode",
     lambda m, a: m.update(fold_dtype="float64" if m["fold_dtype"] == "float32"
                           else "float32")),
    ("state_schema", _drop_state_leaf),
    ("state_schema", _reshape_state_leaf),
], ids=["sim_schema", "fault_knobs", "flow_knobs", "scenario_fields",
        "x64_mode", "missing_leaf", "reshaped_leaf"])
def test_mismatched_checkpoint_rejected(tmp_path, ckpt_file, reason, mutate):
    bad = _rewritten(ckpt_file, tmp_path / "bad.ckpt.npz", mutate)
    with pytest.raises(CK.CheckpointError) as ei:
        S.resume_sweep(bad)
    assert ei.value.reason == reason
    assert "checkpoint rejected" in str(ei.value)


def test_truncated_checkpoint_rejected(tmp_path, ckpt_file):
    data = ckpt_file.read_bytes()
    bad = tmp_path / "trunc.ckpt.npz"
    bad.write_bytes(data[: len(data) // 2])
    with pytest.raises(CK.CheckpointError) as ei:
        S.resume_sweep(bad)
    assert ei.value.reason == "format"


def test_bitflipped_checkpoint_rejected(tmp_path, ckpt_file):
    """A single flipped byte surfaces at whichever integrity layer sees
    it first (the zip container or the content checksum) — never as a
    silent resume."""
    data = bytearray(ckpt_file.read_bytes())
    data[len(data) // 2] ^= 0xFF
    bad = tmp_path / "flip.ckpt.npz"
    bad.write_bytes(bytes(data))
    with pytest.raises(CK.CheckpointError) as ei:
        S.resume_sweep(bad)
    assert ei.value.reason in ("checksum", "format")


def test_stale_checksum_rejected(tmp_path, ckpt_file):
    """Tampered array contents under a stale stored checksum is exactly
    the class the content hash exists for."""
    meta, arrays = CK.read_checkpoint(ckpt_file)
    name = next(n for n in sorted(arrays) if n.startswith("fold_sum"))
    arrays[name] = arrays[name] + 1
    blob = io.BytesIO()
    np.savez(blob, **{CK._META_MEMBER: np.frombuffer(
        json.dumps(meta, sort_keys=True).encode("utf-8"),
        dtype=np.uint8)}, **arrays)
    bad = CK.atomic_write_bytes(tmp_path / "stale.ckpt.npz",
                                blob.getvalue())
    with pytest.raises(CK.CheckpointError) as ei:
        CK.read_checkpoint(bad)
    assert ei.value.reason == "checksum"


def test_wrong_ckpt_schema_rejected(tmp_path, ckpt_file):
    meta, arrays = CK.read_checkpoint(ckpt_file)
    meta["ckpt_schema"] = 999
    blob = io.BytesIO()
    np.savez(blob, **{CK._META_MEMBER: np.frombuffer(
        json.dumps(meta, sort_keys=True).encode("utf-8"),
        dtype=np.uint8)}, **arrays)
    bad = CK.atomic_write_bytes(tmp_path / "old.ckpt.npz", blob.getvalue())
    with pytest.raises(CK.CheckpointError) as ei:
        S.resume_sweep(bad)
    assert ei.value.reason == "ckpt_schema"


def test_atomic_write_leaves_no_temp_files(tmp_path):
    p = CK.atomic_write_text(tmp_path / "x.json", "{}")
    assert p.read_text() == "{}"
    assert [f.name for f in tmp_path.iterdir()] == ["x.json"]


# ---- retry policy: backoff, deadline, graceful degradation --------------

def _two_bucket_runs():
    site_b = FBSite(n_clusters=2, racks_per_cluster=5, servers_per_rack=4,
                    csw_per_cluster=2, n_fc=2, csw_ring_links=2,
                    fc_ring_links=4)
    spec = TRAFFIC_SPECS["fb_hadoop"]
    return [(S.SimParams(spec=spec, site=SITE), 0),
            (S.SimParams(spec=spec, site=site_b), 1),
            (S.SimParams(spec=spec, site=SITE, gating_enabled=False), 2)]


def test_backoff_schedule_and_policy_validation():
    p = S.BucketRetryPolicy(max_retries=4, backoff_base_s=0.25,
                            backoff_mult=2.0, backoff_max_s=0.6)
    assert [p.backoff_s(a) for a in (1, 2, 3, 4)] == [0.25, 0.5, 0.6, 0.6]
    # the default policy IS the original contract: one immediate retry
    d = S.BucketRetryPolicy()
    assert (d.max_retries, d.backoff_s(1), d.deadline_s) == (1, 0.0, None)
    for kw in (dict(max_retries=-1), dict(backoff_base_s=-0.1),
               dict(backoff_mult=0.5), dict(backoff_max_s=-1.0),
               dict(deadline_s=-2.0)):
        with pytest.raises(ValueError, match="BucketRetryPolicy"):
            S.BucketRetryPolicy(**kw)


def test_retry_backoff_sequence_and_structured_error(monkeypatch):
    """A permanently failing bucket is retried max_retries times with
    the capped exponential sleeps, then degrades to structured error
    entries while the other bucket's results return untouched."""
    sleeps, calls = [], []
    monkeypatch.setattr(S, "RETRY_SLEEP", sleeps.append)

    def hook(k, phase):
        calls.append((k, phase))
        if k == 0:
            raise RuntimeError("perma")

    monkeypatch.setattr(S, "BUCKET_FAIL_HOOK", hook)
    policy = S.BucketRetryPolicy(max_retries=3, backoff_base_s=0.25,
                                 backoff_mult=2.0, backoff_max_s=0.6)
    res = S.run_sweep_planned(_two_bucket_runs(), 160, max_compiles=2,
                              chunk_ticks=80, retry=policy)
    assert sleeps == [0.25, 0.5, 0.6]
    bad = [r for r in res if "error" in r]
    good = [r for r in res if "error" not in r]
    assert bad and good
    for r in bad:
        assert r["error"] == {"type": "RuntimeError", "message": "perma",
                              "stage": "dispatch", "retried": True}
    assert [c for c in calls if c[1] == "retry"] == [(0, "retry")] * 3
    assert all(r["injected_pkts"] > 0 for r in good)


def test_deadline_cuts_retries_not_results(monkeypatch):
    """deadline_s=0 abandons every retry (the bucket already spent its
    budget failing) but the OTHER bucket's finished work still comes
    back — deadlines bound retries, never completed results."""
    calls = []

    def hook(k, phase):
        calls.append((k, phase))
        if k == 0:
            raise RuntimeError("slow")

    monkeypatch.setattr(S, "BUCKET_FAIL_HOOK", hook)
    policy = S.BucketRetryPolicy(max_retries=5, deadline_s=0.0)
    res = S.run_sweep_planned(_two_bucket_runs(), 160, max_compiles=2,
                              chunk_ticks=80, retry=policy)
    bad = [r for r in res if "error" in r]
    assert bad
    for r in bad:
        assert r["error"]["retried"] is False
        # without checkpointing the error contract is exactly PR 6's
        assert sorted(r["error"]) == ["message", "retried", "stage", "type"]
    assert not [c for c in calls if c[1] == "retry"]
    assert [r for r in res if "error" not in r]


def test_degraded_bucket_leaves_resumable_salvage(tmp_path, monkeypatch):
    """With checkpointing on, an exhausted bucket that never reached a
    chunk boundary still leaves a chunk-0 salvage snapshot whose resume
    reproduces the bucket's clean results bit-identically."""
    runs = _two_bucket_runs()

    def hook(k, phase):
        if k == 0:
            raise RuntimeError("perma")

    monkeypatch.setattr(S, "BUCKET_FAIL_HOOK", hook)
    res = S.run_sweep_planned(
        runs, 160, max_compiles=2, chunk_ticks=80,
        checkpoint=_spec(tmp_path, tag="plan", every_chunks=1))
    bad = [r for r in res if "error" in r]
    good = [r for r in res if "error" not in r]
    assert bad and good
    ck = bad[0]["error"]["checkpoint"]
    assert ck is not None and Path(ck).name.endswith("-00000000.ckpt.npz")
    meta = CK.read_checkpoint(ck)[0]
    assert meta["plan"]["bucket"] == 0 and meta["plan"]["fingerprint"]
    # hook off: compare the salvage resume against a clean planned run
    monkeypatch.setattr(S, "BUCKET_FAIL_HOOK", None)
    resumed = S.resume_sweep(ck)
    clean = S.run_sweep_planned(runs, 160, max_compiles=2, chunk_ticks=80)
    by_label = {r["label"]: r for r in clean}
    ref = [by_label[r["label"]] for r in resumed]
    diff, key = S.worst_parity(ref, resumed)
    assert diff == 0.0, key


# ---- sharded layout (4 fake devices, subprocess) ------------------------

def test_resume_parity_under_sharding(tmp_path, reference):
    """The full kill/resume contract under a 4-device NamedSharding
    (3 real rows padded to 4), PLUS cross-layout portability: the
    single-device checkpoint written above resumes on four devices to
    the same bit-identical metrics."""
    # a 1-device-layout checkpoint + the reference metrics for it
    def hook(ci):
        if ci == 4:
            raise RuntimeError("preempted")

    S.CHUNK_HOOK = hook
    try:
        with pytest.raises(RuntimeError, match="preempted"):
            S.run_sweep(_batch(), TICKS, chunk_ticks=CHUNK,
                        validate=True, checkpoint=_spec(tmp_path))
    finally:
        S.CHUNK_HOOK = None
    one_dev_ckpt = CK.latest_checkpoint(tmp_path, "t")
    ref_path = tmp_path / "ref.json"
    ref_path.write_text(json.dumps(
        [{"label": r["label"],
          **{k: float(r[k]) for k in S.PARITY_KEYS}} for r in reference]))

    code = f"""
import json
from pathlib import Path
import jax
import pytest
from repro.core import checkpoint as CK
from repro.core import simulator as S
from repro.core.topology import FBSite
from repro.core.traffic import TRAFFIC_SPECS

assert jax.local_device_count() == 4
TICKS, CHUNK = {TICKS}, {CHUNK}
SITE = FBSite(**{dataclasses.asdict(SITE)!r})
KNOBS = dict({KNOBS!r})
spec = TRAFFIC_SPECS["fb_hadoop"]
runs = [(S.SimParams(spec=spec, site=SITE, **KNOBS), 3),
        (S.SimParams(spec=spec, site=SITE, gating_enabled=False,
                     **KNOBS), 4),
        (S.SimParams(spec=spec, site=SITE), 5)]
batch = S.make_batch(runs)
reference = json.loads(Path({str(ref_path)!r}).read_text())

# leg 1: cross-layout — resume the 1-device checkpoint on 4 devices
res = S.resume_sweep({str(one_dev_ckpt)!r})
diff, key = S.worst_parity(reference, res)
assert diff == 0.0, ("cross-layout", key)

# leg 2: kill + checkpoint + resume entirely under the sharded layout
d = Path({str(tmp_path)!r}) / "sharded"
spec4 = CK.CheckpointSpec(directory=d, every_chunks=2, tag="s4", keep=8)
def hook(ci):
    if ci == 4:
        raise RuntimeError("preempted")
S.CHUNK_HOOK = hook
try:
    with pytest.raises(RuntimeError, match="preempted"):
        S.run_sweep(batch, TICKS, chunk_ticks=CHUNK, validate=True,
                    checkpoint=spec4)
finally:
    S.CHUNK_HOOK = None
found = CK.list_checkpoints(d, "s4")
assert [c for c, _ in found] == [2], found
h0 = S.HOST_TRANSFER_COUNT
res4 = S.resume_sweep(found[0][1])
assert S.HOST_TRANSFER_COUNT - h0 == 1
diff, key = S.worst_parity(reference, res4)
assert diff == 0.0, ("sharded", key)
print("SHARDED RESUME PARITY OK")
"""
    out = run_with_devices(code, n_devices=4)
    assert "SHARDED RESUME PARITY OK" in out
