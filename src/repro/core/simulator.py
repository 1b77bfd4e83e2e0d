"""LC/DC network simulator: 1 us-slotted, fully vectorized, lax.scan-jitted.

Models the Fig 2 Facebook-style site end to end:

  server NICs --(node-gated links)--> RSW --(stage-gated uplinks)--> CSW
      --(stage-gated 40G uplinks)--> FC --> CSW --> RSW --> server

Edge traffic is stochastic (per-rack flow slots driven by core/traffic.py:
lognormal sizes, ON/OFF bursts); the aggregation tiers are fluid (float
packet counts) which preserves the queue dynamics that drive the
watermark controller while keeping the whole site one dense-array state.

Down-routing honours the stage invariant: packets that land on a CSW/FC
whose downlink to the destination is gated off migrate over the cluster /
FC load-balancing rings (the rings exist for exactly this in Fig 2) to
the always-on stage-1 path, paying ring latency. Connectivity is never
lost because stage >= 1 everywhere (the paper's core invariant).

The wiring invariants are FBSite's (topology.py): RSW uplink c IS the
link to cluster-CSW c (the stage-c plane, ``rsw_uplinks ==
csw_per_cluster``) and CSW uplink f IS the link to fabric core f
(``csw_uplinks == n_fc``). The hot loop's down-plane reshapes are
written against the semantically correct axes (csw_per_cluster for the
plane axis, csw_uplinks for the FC-uplink axis), so topology-general
sites — any n_clusters / racks_per_cluster / csw_per_cluster / n_fc —
route correctly.

Latency is measured with Little's law per queue group (mean delay =
mean backlog / delivered rate) plus fixed per-hop wire/pipeline/stack
latencies; the paper reports mean packet delivery latency, which this
estimates directly.

In-scan packet-delay distributions
----------------------------------
The paper's headline tradeoff ("60% power saved at the cost of 6%
higher delay") is a statement about *distributions*, not just means:
the laser/CDR wake stall behind ``STAGE_UP_DELAY`` shows up in the
latency TAIL. Every tick the step therefore draws one delay sample per
rack and destination class (intra-cluster / inter-cluster), weighted by
the packets injected there that tick:

    d = STACK_US + hops * WIRE_HOP_US        (fixed path cost)
      + enq_wait(RSW) + down_wait(CSW->rack) (queueing, kernel-fed)
      [+ enq_wait(CSW up) + fc_wait]         (inter-cluster only)
      + wake_stall(RSW) [+ wake_stall(CSW)]  (gating-attributed)

The queue-wait terms come from the SAME oracle-checked kernel as the
datapath: ``ops.switch_step`` emits per-switch backlog-age (``enq_wait``,
what an arrival queues behind) and post-serve occupancy moments. The
wake-stall terms are ``gating.wake_stall_ticks`` — the remaining ticks
of an in-flight stage-up — so with gating disabled the attribution is
exactly zero. Ring-detour hops are attributed separately in
``_finalize`` from the ring counters (a scalar mean, not in the
histogram).

Samples are binned into a fixed log-spaced histogram
(``constants.DELAY_HIST_BINS`` = 48 bins; bin 0 is
[0, DELAY_HIST_MIN_US); bin i covers [MIN * 2**((i-1)/BPO),
MIN * 2**(i/BPO)) with BPO = DELAY_HIST_BINS_PER_OCTAVE = 6; the last
bin absorbs overflow; edges in ``DELAY_BIN_EDGES_US``). The histogram
is an ordinary accumulator: folded into the device-resident fold
buffer at chunk boundaries like every other one (see
"Device-resident execution" below), so memory stays bounded for
arbitrarily long runs. ``_finalize`` extracts log-interpolated ``delay_p50_us`` /
``delay_p95_us`` / ``delay_p99_us``, the normalized ``delay_hist``,
and the attribution split ``delay_queue_us`` (queueing) /
``delay_wake_stall_us`` (STAGE_UP_DELAY stalls) / ``delay_ring_us``
(ring-detour hops), plus ``wake_stall_frac`` (fraction of sampled
packets that arrived during a stage-up) and per-tier occupancy
mean/variance from the kernel's moment outputs.

``delay_mean_sampled_us`` (the histogram's own mean) and
``mean_latency_us`` (Little's law) are different estimators of the same
quantity and deliberately both reported: the first carries attribution
and tails, the second is the paper's original headline metric.

Flow-level workload engine (flow_mode=1)
----------------------------------------
``flow_mode=1`` replaces the rate-based edge with a flow abstraction
inside the same jitted scan: a fixed-capacity per-rack flow table
(``C.FLOW_TABLE_SLOTS`` static slots; the traced ``flow_table_cap``
knob bounds the usable prefix) holding arrival tick, remaining
packets, destination class and an AIMD congestion window per flow.
Flow sizes are sampled in-scan from the heavy-tailed
websearch/datamining CDFs of core/workloads.py (``flow_size_dist``);
arrivals are per-rack Bernoulli events (``flow_arrival_rate``, default
derived from the trace so both modes offer comparable load) spawning
``incast_degree`` same-destination flows at once; table overflow is
EVICTION, counted so started == completed + evicted + in-flight stays
exact (the ``validate=True`` guard checks it per chunk). Completions
bin into per-size-class (short/medium/long) FCT and FCT-slowdown
histograms riding the same log-spaced machinery as the delay
histogram; the path-delay part of each FCT is the tick's d_i/d_x
sample, so wake/fault stalls attribute into FCT through the one
``gating.stall_attribution`` seam. All of it is jnp.where-selected
against the rate-based path — zero new compile sites, and
``flow_mode=0`` is BIT-IDENTICAL to the pre-flow engine (the fault
knobs' zero-knob discipline: dedicated fold_in branches, fixed draw
widths, masked accumulator adds; tests/test_flows.py pins it against
committed goldens).

Batched multi-scenario sweeps
-----------------------------
Every per-scenario knob — the TrafficSpec fields, ``gating_enabled``,
``rate_scale``, the watermarks, the anti-flap dwell, the seed — is an
array-valued leaf of a :class:`Scenario` pytree, so one jitted
``lax.scan`` step is ``vmap``-ped over an arbitrary batch of scenarios:

    batch = sweep_grid(traces=("fb_hadoop", "fb_web"), seeds=(0, 1))
    results = run_sweep(batch, n_ticks=100_000)   # list of metric dicts

Multi-site batches
------------------
The scenario's site SHAPE is itself a set of traced knobs: ``Scenario``
carries each scenario's real (n_clusters, racks_per_cluster,
csw_per_cluster, n_fc, servers_per_rack), and the step runs on a static
padded hull (the per-axis max over the batch) with validity masks
derived in-step. ``make_multi_site_batch`` stacks runs on ARBITRARY
FBSite variants — the Fig 1 design-comparison axis — into one batch
that compiles ONCE. Racks and CSWs occupy blocked (cluster-major)
positions in the hull, padded entries are provably inert (no spawns, no
arrivals, stage pinned to 1, masked out of every accumulator), and all
per-rack randomness is keyed by the rack's logical id, so a site's
metrics are identical whether it runs alone at exact dims or padded
inside a heterogeneous batch.

Padding to ONE hull wastes compute once site sizes diverge (every
scenario steps the worst site's state). ``run_sweep_planned`` fixes
that: it partitions the runs into a few hull buckets via the
cost-model planner in core/planner.py (``max_compiles`` budget), runs
each bucket as its own tight-hull batch, and merges results back in
caller order with per-bucket padding-waste stats — same metrics
(1e-3-pinned parity), a fraction of the padded compute.

One-compile contract: ``run_sweep`` compiles exactly once per
(hull topology, batch size, chunk length) — re-running the same-shaped
sweep with different knob values (traces, watermarks, seeds, sites
fitting the same hull, ...) reuses the cached executable;
``TRACE_COUNT`` counts step traces so tests can pin this. Long runs are
chunked (``chunk_ticks``, default 10k): the jitted chunk donates its
carry on accelerator backends. A remainder (``n_ticks % chunk_ticks !=
0``) does NOT compile a second program: the tail runs the same
fixed-length chunk with a live mask, dead ticks passing the carry
through unchanged.

Device-resident execution
-------------------------
The per-chunk accumulator fold happens ON DEVICE, inside the same
jitted chunk program as the scan: a per-scenario fold buffer (float64
where the backend enables x64, otherwise a compensated Kahan float32
``(sum, comp)`` pair) absorbs each chunk's accumulators and the in-scan
accumulators are re-zeroed, all without leaving the device. The chunk
loop is therefore pure async dispatch — no host synchronization at
chunk boundaries — and the entire run performs exactly ONE host
transfer (the final fold fetch; ``HOST_TRANSFER_COUNT`` counts these:
the tests pin it, and chipbench/metrics/host_transfers_per_sweep.py
reads it per sweep). Kahan compensation bounds the cross-chunk float32
accumulation error at O(eps) independent of chunk count, so
device-fold metrics match the legacy host-fold path
(``fold="host"``: per-chunk ``device_get`` + float64 numpy fold, kept
for parity pinning) to <= 1e-6 relative.

The scenario batch axis additionally shards across all local devices
(``shard=None`` auto-enables when >1 device is visible; CPU CI
exercises it with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
Scenario knobs, sim state and fold buffers are placed with a
``NamedSharding`` over the batch axis; batches that don't divide the
device count are padded with copies of scenario 0 and the pad rows
dropped before finalization — scenarios are independent vmap lanes, so
padding and sharding are bit-inert for every real scenario's metrics
(tests/test_sharding.py pins this on 4 fake devices). The sharded chunk
program is a ``shard_map`` over that axis: each device runs the plain
chunk program on its own rows, so no collective is ever inserted, and
the Pallas switch kernel (which Mosaic cannot auto-partition) runs
per device.

``run_sweep_planned`` pipelines its hull buckets: every bucket's chunk
programs are DISPATCHED first (largest padded cost first, the planner's
``dispatch_order``, so tracing/compiling bucket k+1 overlaps device
execution of bucket k), and results are fetched afterwards — one
blocking transfer per bucket, no interleaved blocking. Caller-order
results, ``plan_bucket``/``plan_hull`` annotation and the
one-trace-per-(hull, batch-shape, chunk) contract are preserved
(``pipeline=False`` recovers strictly serial bucket execution,
bit-identically).

The per-switch scheduling/enqueue/serve/watermark block of the hot loop
runs through ``ops.switch_step`` — the Pallas kernel on TPU, its
pure-jnp oracle (kernels/ref.py) on CPU — so the simulator and the
kernel share one switch-tick definition (including the multi-site
``valid`` padding mask).

``run_sim`` (one scenario) is kept for unit runs and ablations; it
re-traces per call exactly like the pre-sweep engine, so serial loops
over scenarios pay compile each time — use ``run_sweep`` for sweeps.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import checkpoint as _ckpt
from repro.core import constants as C
from repro.core import gating
from repro.core import workloads
from repro.core.checkpoint import (CheckpointError,  # noqa: F401 — re-export
                                   CheckpointSpec)
from repro.core.topology import (FBSite, full_site_tag, pad_hull,
                                 site_tag)
from repro.core.traffic import (TRAFFIC_SPECS, TrafficSpec,
                                flow_arrival_rate_per_tick,
                                rack_flow_rate_per_tick, stack_specs)
from repro.kernels import ops

F_SLOTS = 64              # concurrent flow slots per rack
MAX_FAULT_LINKS = 16      # fixed per-switch fault-draw width: hull link
#                           axes must fit so the uniform block's shape
#                           (and thus every draw) is padding-invariant
NODE_IDLE_TICKS = 50      # server-link idle timeout (us)
# ring migration budgets are per-site (1 pkt/tick per 10G ring link):
# scen.csw_ring / scen.fc_ring, from FBSite.csw_ring_links/fc_ring_links
WIRE_HOP_US = 0.5         # fiber + switch pipeline per hop
STACK_US = 3.75           # TCP/IP + NIC (Sec IV-C)

CHUNK_TICKS = 10_000      # default scan chunk (accumulator fold period)

#: bump when the step semantics change — cached results keyed on an
#: older version (benchmarks/simcache.py) are invalidated
#: (v3: in-scan delay histograms + wake-stall attribution, corrected
#: half-open on_frac_hist buckets; v4: hull-bucketed planned sweeps —
#: results carry plan_bucket/plan_hull, caches carry the plan
#: fingerprint; v5: device-resident accumulator fold + scenario-axis
#: sharding — caches additionally carry the execution mode; v6: optical
#: fault-injection subsystem — fault knobs are Scenario leaves, results
#: gain delivered/fault-drop/retry/connectivity metrics, and cache meta
#: carries the fault fingerprint + validate flag so fault-free cached
#: results never alias faulted runs; v7: flow-level workload engine —
#: flow knobs are Scenario leaves, results gain flow/FCT metrics, and
#: cache meta carries the flow fingerprint so flow-free cached results
#: never alias flow runs; v8: correlated failure domains — the
#: per-plane hard-fault hazard ``plane_fail_prob`` is a Scenario leaf
#: joined into the fault fingerprint, so plane-fault-free cached
#: results never alias correlated-fault runs)
SIM_SCHEMA_VERSION = 8

#: number of times the sweep step has been traced (the one-compile probe)
TRACE_COUNT = 0

#: optional per-trace attribution seam: when set to a callable it is
#: invoked with the static ``site`` hull at every sweep-step trace
#: (same trace-time-only side effect as TRACE_COUNT). The runtime
#: sanitizer (repro.analysis.sanitizer.TraceLedger) uses it to pin the
#: planner pipeline's one-trace-per-bucket contract per hull tag.
TRACE_HOOK = None

#: number of accumulator host transfers the sweep engine has performed
#: (``device_get`` of fold buffers / in-scan accumulators). The
#: device-resident fold path does exactly ONE per run_sweep (one per
#: planned bucket); the legacy ``fold="host"`` path does one per chunk.
#: tests/test_sweep.py and tests/test_durability.py pin it, and
#: chipbench/metrics/host_transfers_per_sweep.py reads it per sweep.
HOST_TRANSFER_COUNT = 0

#: scalar metrics that must agree between run_sim and run_sweep — the
#: shared contract checked by tests/test_sweep.py and the
#: benchmarks/bench_sweep.py parity canary
PARITY_KEYS = (
    "mean_latency_us", "injected_pkts", "delivered_pkts", "drop_frac",
    "switch_energy_savings_frac", "rsw_link_on_frac", "csw_link_on_frac",
    "node_link_on_frac", "transceiver_power_w", "half_off_frac",
    "delay_p50_us", "delay_p99_us", "delay_queue_us",
    "delay_wake_stall_us", "delivered_frac", "fault_drop_frac",
    "delay_fault_stall_us", "flows_completed", "flow_evicted_frac",
    "fct_slowdown_p99",
)


def worst_parity(ref_results, new_results):
    """Worst relative PARITY_KEYS divergence between two result lists
    (zipped pairwise); returns (diff, "label:key"). The one scan every
    parity canary shares."""
    worst_key, worst = None, 0.0
    for r_a, r_b in zip(ref_results, new_results):
        for k in PARITY_KEYS:
            a, b = r_a[k], r_b[k]
            d = abs(a - b) / max(abs(a), abs(b), 1e-9)
            if d > worst:
                worst_key, worst = f"{r_b['label']}:{k}", d
    return worst, worst_key

#: histogram bin edges in us (len DELAY_HIST_BINS + 1; see module
#: docstring). Bin i covers [edge[i], edge[i+1]); the last bin also
#: absorbs anything beyond the final edge.
def _log_bin_edges(min_val: float, bins: int, bpo: float) -> np.ndarray:
    """Edges of a log-spaced histogram frame (len bins + 1): bin 0 is
    linear [0, min_val); bin i >= 1 covers [min * 2**((i-1)/bpo),
    min * 2**(i/bpo)); the last bin absorbs overflow."""
    return np.concatenate([
        [0.0],
        min_val * 2.0 ** (np.arange(bins, dtype=np.float64) / bpo)])


DELAY_BIN_EDGES_US = _log_bin_edges(
    C.DELAY_HIST_MIN_US, C.DELAY_HIST_BINS, C.DELAY_HIST_BINS_PER_OCTAVE)
#: the flow engine's FCT / FCT-slowdown frames (same machinery, wider
#: dynamic range; see constants.py)
FCT_BIN_EDGES_US = _log_bin_edges(
    C.FCT_HIST_MIN_US, C.FCT_HIST_BINS, C.FCT_HIST_BINS_PER_OCTAVE)
FCT_SLOWDOWN_BIN_EDGES = _log_bin_edges(
    C.FCT_SLOWDOWN_HIST_MIN, C.FCT_SLOWDOWN_HIST_BINS,
    C.FCT_SLOWDOWN_HIST_BINS_PER_OCTAVE)


def _delay_hist_add(hist, d, w, *, min_val=C.DELAY_HIST_MIN_US,
                    bpo=C.DELAY_HIST_BINS_PER_OCTAVE,
                    bins=C.DELAY_HIST_BINS):
    """Bin weighted delay samples into a log-spaced histogram.

    d, w: (N,) sample values (us) and packet weights. Dense one-hot
    accumulation (no scatter, same trick as on_frac_hist); zero-weight
    rows contribute nothing, so padded hull rows are inert by
    construction. The keyword frame (min/bins-per-octave/bin count)
    defaults to the packet-delay histogram; the flow engine reuses the
    same machinery for its FCT and slowdown frames.
    """
    # the 1e-4 nudge keeps exact edge values in their own (half-open)
    # bin under f32 log2 rounding; it shifts edges by ~0.001%, far
    # below the ~12% bin resolution
    idx = jnp.clip(
        jnp.floor(jnp.log2(jnp.maximum(d, 1e-9) / min_val)
                  * bpo + 1e-4),
        -1, bins - 2).astype(jnp.int32) + 1
    onehot = jnp.arange(bins)[None, :] == idx[:, None]
    return hist + jnp.sum(w[:, None] * onehot, axis=0)


def on_frac_bucket(frac_on):
    """Quartile bucket of an on-fraction: (0,25], (25,50], (50,75],
    (75,100] — half-open-LEFT intervals, matching the on_frac_hist
    labels (an exact 25% boundary belongs to the lower bucket; 0 falls
    into the first)."""
    return jnp.clip(jnp.ceil(frac_on * 4.0).astype(jnp.int32) - 1, 0, 3)


class Scenario(NamedTuple):
    """Per-scenario knobs as array leaves (vmap axis 0 = scenario).

    Scalars per scenario; the batch builders stack them to (B,) arrays
    so the whole batch is one pytree the jitted step closes over. The
    last block is the scenario's REAL site shape inside the padded hull
    (equal to the hull for a single-site batch).
    """
    # traffic (TrafficSpec fields; p_spawn folds iat + rate_scale)
    p_spawn: jax.Array          # f32: P(new flow)/rack/tick while ON
    p_on_off: jax.Array         # f32
    p_off_on: jax.Array         # f32
    size_w: jax.Array           # f32 lognormal mixture weight
    size_mu1: jax.Array         # f32
    size_s1: jax.Array          # f32
    size_mu2: jax.Array         # f32
    size_s2: jax.Array          # f32
    p_intra_rack: jax.Array     # f32
    p_intra_cluster: jax.Array  # f32
    pace: jax.Array             # f32
    burst_pace_boost: jax.Array  # f32
    elephant_pkts: jax.Array    # int32
    elephant_pace: jax.Array    # f32
    # controller / datapath
    gating_enabled: jax.Array   # bool
    queue_cap: jax.Array        # f32
    hi: jax.Array               # f32
    lo: jax.Array               # f32
    dwell: jax.Array            # int32
    # optical fault model (all zero => bit-identical to the fault-free
    # path; sweepable with zero new compile sites)
    wake_fail_prob: jax.Array   # f32 P(stage-up firing fails)
    wake_jitter_frac: jax.Array  # f32 turn-on delay jitter (+- fraction)
    fault_prob: jax.Array       # f32 per-tick hard-fault hazard (1/MTBF)
    repair_ticks: jax.Array     # int32 hard-fault repair delay
    fault_fallback: jax.Array   # bool min-connectivity force-wake on/off
    plane_fail_prob: jax.Array  # f32 per-tick correlated whole-plane
    #                             hazard (one draw per laser comb)
    # flow-level workload engine (flow_mode=0 => the rate-based path
    # above, bit-identical; sweepable with zero new compile sites)
    flow_mode: jax.Array        # int32 0=rate-based, 1=flow engine
    flow_rate: jax.Array        # f32 P(arrival event)/rack/tick
    flow_dist: jax.Array        # int32 index into workloads.FLOW_DIST_NAMES
    incast: jax.Array           # int32 flows per arrival event (fan-in)
    flow_cap: jax.Array         # int32 usable flow-table slots (<= static)
    # site shape (real dims; <= the hull's static dims)
    ncl: jax.Array              # int32 n_clusters
    rpc: jax.Array              # int32 racks_per_cluster
    cpc: jax.Array              # int32 csw_per_cluster (= rsw uplinks)
    nfc: jax.Array              # int32 n_fc (= csw uplinks)
    spr: jax.Array              # f32 servers_per_rack
    csw_ring: jax.Array         # f32 cluster-ring pkts/tick budget
    fc_ring: jax.Array          # f32 FC-ring pkts/tick budget


class SimState(NamedTuple):
    key: jax.Array
    burst_on: jax.Array        # (R,) bool
    flow_rem: jax.Array        # (R, F) int32 remaining packets
    flow_dest: jax.Array       # (R, F) int32 0=rack 1=cluster 2=inter
    flow_fast: jax.Array       # (R, F) bool: line-rate elephant
    # flow engine (flow_mode=1): the fixed-capacity per-rack flow table
    # (FT = C.FLOW_TABLE_SLOTS static slots; a slot is live while
    # ft_rem > 0). All-zero and bit-inert at flow_mode=0.
    tick: jax.Array            # () int32 tick counter (arrival stamps)
    ft_start: jax.Array        # (R, FT) int32 arrival tick
    ft_rem: jax.Array          # (R, FT) f32 remaining packets
    ft_size: jax.Array        # (R, FT) int32 total flow size (pkts)
    ft_dst: jax.Array          # (R, FT) int32 0=rack 1=cluster 2=inter
    ft_cwnd: jax.Array         # (R, FT) f32 AIMD window (pkts/tick)
    rsw_q: jax.Array           # (R, P, 2) float [intra, inter]
    csw_up_q: jax.Array        # (NC, CUP) float
    csw_down_q: jax.Array      # (NC, RPC) float
    fc_down_q: jax.Array       # (NF, NC) float
    rsw_gate: gating.GateState
    csw_gate: gating.GateState
    rsw_fault: gating.FaultState   # per-uplink hard-fault carries
    csw_fault: gating.FaultState
    node_on: jax.Array         # (R,) float servers-links held on
    acc: dict                  # accumulators


#: SimParams fields forming the fault model's cache/meta fingerprint
FAULT_KNOBS = ("wake_fail_prob", "wake_jitter_frac", "link_mtbf_ticks",
               "repair_ticks", "fault_fallback", "plane_fail_prob")

#: SimParams fields forming the flow engine's cache/meta fingerprint
FLOW_KNOBS = ("flow_mode", "flow_arrival_rate", "flow_size_dist",
              "incast_degree", "flow_table_cap")


@dataclass(frozen=True)
class SimParams:
    spec: TrafficSpec
    site: FBSite = FBSite()
    gating_enabled: bool = True
    rate_scale: float = 1.0
    queue_cap: float = C.QUEUE_CAP_PKTS
    hi: float = C.HI_WATERMARK
    lo: float = C.LO_WATERMARK
    dwell: int = C.STAGE_DWELL_TICKS
    # optical fault model (defaults = the paper's perfect plane)
    wake_fail_prob: float = 0.0    # P(a stage-up firing fails), [0, 1)
    wake_jitter_frac: float = 0.0  # turn-on delay jitter fraction [0, 1]
    link_mtbf_ticks: float = 0.0   # mean ticks between hard faults per
    #                                powered link; 0 disables hard faults
    repair_ticks: int = 0          # hard-fault repair delay (>= 1 when
    #                                link_mtbf_ticks > 0)
    fault_fallback: bool = True    # min-connectivity force-wake
    plane_fail_prob: float = 0.0   # per-tick correlated whole-plane
    #                                hazard (shared laser comb dies ->
    #                                every link it feeds faults at
    #                                once); 0 disables plane faults
    # flow-level workload engine (default = the legacy rate-based path)
    flow_mode: int = 0             # 0=rate-based, 1=flow engine
    flow_arrival_rate: float = 0.0  # P(arrival event)/rack/tick; 0 =>
    #                                 derive from spec * rate_scale
    #                                 (traffic.flow_arrival_rate_per_tick)
    flow_size_dist: str = "websearch"  # workloads.FLOW_DIST_NAMES
    incast_degree: int = 1         # flows per arrival event (fan-in),
    #                                [1, C.MAX_INCAST_DEGREE]
    flow_table_cap: int = C.FLOW_TABLE_SLOTS  # usable slots per rack

    def __post_init__(self):
        """Reject out-of-range knobs with a clear error instead of
        silent NaN/garbage downstream (satellite of the fault PR)."""
        def bad(msg):
            raise ValueError(f"SimParams: {msg}")
        if not self.rate_scale >= 0.0:
            bad(f"rate_scale must be >= 0, got {self.rate_scale}")
        if not self.queue_cap > 0.0:
            bad(f"queue_cap must be > 0, got {self.queue_cap}")
        if not 0.0 < self.hi <= 1.0:
            bad(f"hi watermark must be in (0, 1], got {self.hi}")
        if not self.lo >= 0.0:
            bad(f"lo watermark must be >= 0, got {self.lo}")
        if self.lo >= self.hi:
            bad(f"inverted watermarks: lo ({self.lo}) >= hi ({self.hi})")
        if self.dwell < 0:
            bad(f"dwell must be >= 0, got {self.dwell}")
        if not 0.0 <= self.wake_fail_prob < 1.0:
            bad("wake_fail_prob must be in [0, 1), got "
                f"{self.wake_fail_prob}")
        if not 0.0 <= self.wake_jitter_frac <= 1.0:
            bad("wake_jitter_frac must be in [0, 1], got "
                f"{self.wake_jitter_frac}")
        if self.link_mtbf_ticks < 0.0:
            bad(f"link_mtbf_ticks must be >= 0 (0 disables hard "
                f"faults), got {self.link_mtbf_ticks}")
        if 0.0 < self.link_mtbf_ticks < 1.0:
            bad(f"link_mtbf_ticks must be >= 1 tick when nonzero, got "
                f"{self.link_mtbf_ticks}")
        if self.repair_ticks < 0:
            bad(f"repair_ticks must be >= 0, got {self.repair_ticks}")
        if self.link_mtbf_ticks > 0.0 and self.repair_ticks < 1:
            bad("repair_ticks must be >= 1 when hard faults are "
                f"enabled (link_mtbf_ticks={self.link_mtbf_ticks})")
        if not 0.0 <= self.plane_fail_prob < 1.0:
            bad("plane_fail_prob must be in [0, 1), got "
                f"{self.plane_fail_prob}")
        if self.plane_fail_prob > 0.0 and self.repair_ticks < 1:
            bad("repair_ticks must be >= 1 when plane faults are "
                f"enabled (plane_fail_prob={self.plane_fail_prob})")
        if self.flow_mode not in (0, 1):
            bad(f"flow_mode must be 0 (rate-based) or 1 (flow "
                f"engine), got {self.flow_mode}")
        if not 0.0 <= self.flow_arrival_rate <= 1.0:
            bad("flow_arrival_rate must be in [0, 1] (per-tick "
                f"Bernoulli; 0 derives from the trace), got "
                f"{self.flow_arrival_rate}")
        if self.flow_size_dist not in workloads.FLOW_DIST_NAMES:
            bad(f"flow_size_dist must be one of "
                f"{workloads.FLOW_DIST_NAMES}, got "
                f"{self.flow_size_dist!r}")
        if not 1 <= self.incast_degree <= C.MAX_INCAST_DEGREE:
            bad(f"incast_degree must be in [1, "
                f"{C.MAX_INCAST_DEGREE}] (the fixed draw width), got "
                f"{self.incast_degree}")
        if not 1 <= self.flow_table_cap <= C.FLOW_TABLE_SLOTS:
            bad(f"flow_table_cap must be in [1, "
                f"{C.FLOW_TABLE_SLOTS}] (the static table width), got "
                f"{self.flow_table_cap}")


def fault_fingerprint(p: "SimParams | None" = None) -> dict:
    """The fault-knob dict joined into result-cache keys / metadata
    (benchmarks/simcache.py) so fault-free cached results never alias
    faulted runs. With no argument, returns the defaults (the perfect
    optical plane)."""
    if p is None:
        import dataclasses
        return {f.name: f.default for f in dataclasses.fields(SimParams)
                if f.name in FAULT_KNOBS}
    return {k: getattr(p, k) for k in FAULT_KNOBS}


def flow_fingerprint(p: "SimParams | None" = None) -> dict:
    """The flow-knob dict joined into result-cache keys / metadata
    (benchmarks/simcache.py) so flow-free cached results never alias
    flow runs — the flow engine's ``fault_fingerprint`` analogue. With
    no argument, returns the defaults (the rate-based path)."""
    if p is None:
        import dataclasses
        return {f.name: f.default for f in dataclasses.fields(SimParams)
                if f.name in FLOW_KNOBS}
    return {k: getattr(p, k) for k in FLOW_KNOBS}


@dataclass(frozen=True)
class ScenarioBatch:
    """A stack of scenarios sharing one padded hull (one compile).

    ``hull`` is the static shape the step compiles against (the per-axis
    max over ``sites``); ``sites`` holds each scenario's real FBSite for
    metric normalization. For a single-site batch hull == sites[i].
    """
    scen: Scenario             # leaves shape (B,)
    hull: FBSite
    sites: tuple               # FBSite per scenario
    names: tuple               # trace name per scenario
    labels: tuple              # unique human label per scenario
    gating: tuple              # python bools (for metric finalization)
    seeds: tuple

    def __len__(self) -> int:
        return len(self.labels)


# hull/tag helpers live in topology.py now (the planner shares them);
# the old private names stay as aliases for existing callers
_pad_hull = pad_hull
_site_tag = site_tag


def _run_label(p: SimParams, seed: int, *, tag_site: bool) -> str:
    """THE scenario label format — shared by batch construction and the
    planned executor's structured error entries, so a failed bucket's
    placeholders carry the same label its metrics dict would have."""
    return (f"{p.spec.name}|{'lcdc' if p.gating_enabled else 'base'}"
            f"|x{p.rate_scale:g}|s{seed}"
            + (f"|{_site_tag(p.site)}" if tag_site else ""))


def _build_batch(runs: Sequence[tuple[SimParams, int]],
                 tag_sites: bool) -> ScenarioBatch:
    assert runs, "empty scenario batch"
    params = [p for p, _ in runs]
    sites = tuple(p.site for p in params)
    tf = stack_specs([p.spec for p in params])

    def f32(xs):
        return jnp.asarray(xs, jnp.float32)

    def i32(xs):
        return jnp.asarray(xs, jnp.int32)

    scen = Scenario(
        p_spawn=f32([min(rack_flow_rate_per_tick(p.spec,
                                                 p.site.servers_per_rack)
                         * p.rate_scale, 1.0) for p in params]),
        p_on_off=f32(tf["p_on_off"]), p_off_on=f32(tf["p_off_on"]),
        size_w=f32(tf["size_w"]),
        size_mu1=f32(tf["size_mu1"]), size_s1=f32(tf["size_s1"]),
        size_mu2=f32(tf["size_mu2"]), size_s2=f32(tf["size_s2"]),
        p_intra_rack=f32(tf["p_intra_rack"]),
        p_intra_cluster=f32(tf["p_intra_cluster"]),
        pace=f32(tf["pace"]),
        burst_pace_boost=f32(tf["burst_pace_boost"]),
        elephant_pkts=jnp.asarray(tf["elephant_pkts"], jnp.int32),
        elephant_pace=f32(tf["elephant_pace"]),
        gating_enabled=jnp.asarray([p.gating_enabled for p in params],
                                   bool),
        queue_cap=f32([p.queue_cap for p in params]),
        hi=f32([p.hi for p in params]), lo=f32([p.lo for p in params]),
        dwell=jnp.asarray([p.dwell for p in params], jnp.int32),
        wake_fail_prob=f32([p.wake_fail_prob for p in params]),
        wake_jitter_frac=f32([p.wake_jitter_frac for p in params]),
        # per-tick hazard: 1/MTBF (0 disables hard faults)
        fault_prob=f32([1.0 / p.link_mtbf_ticks
                        if p.link_mtbf_ticks > 0 else 0.0
                        for p in params]),
        repair_ticks=i32([p.repair_ticks for p in params]),
        fault_fallback=jnp.asarray([p.fault_fallback for p in params],
                                   bool),
        plane_fail_prob=f32([p.plane_fail_prob for p in params]),
        flow_mode=i32([p.flow_mode for p in params]),
        # explicit rate wins; 0 derives the legacy generator's expected
        # spawn rate so the two modes offer comparable load
        flow_rate=f32([p.flow_arrival_rate if p.flow_arrival_rate > 0.0
                       else flow_arrival_rate_per_tick(
                           p.spec, p.site.servers_per_rack,
                           p.rate_scale) for p in params]),
        flow_dist=i32([workloads.FLOW_DIST_NAMES.index(p.flow_size_dist)
                       for p in params]),
        incast=i32([p.incast_degree for p in params]),
        flow_cap=i32([p.flow_table_cap for p in params]),
        ncl=i32([p.site.n_clusters for p in params]),
        rpc=i32([p.site.racks_per_cluster for p in params]),
        cpc=i32([p.site.csw_per_cluster for p in params]),
        nfc=i32([p.site.n_fc for p in params]),
        spr=f32([p.site.servers_per_rack for p in params]),
        # 1 pkt/tick per 10G ring link
        csw_ring=f32([p.site.csw_ring_links for p in params]),
        fc_ring=f32([p.site.fc_ring_links for p in params]))
    labels = tuple(_run_label(p, seed, tag_site=tag_sites)
                   for p, seed in runs)
    return ScenarioBatch(
        scen=scen, hull=_pad_hull(sites), sites=sites,
        names=tuple(p.spec.name for p, _ in runs), labels=labels,
        gating=tuple(bool(p.gating_enabled) for p, _ in runs),
        seeds=tuple(int(s) for _, s in runs))


def make_batch(runs: Sequence[tuple[SimParams, int]]) -> ScenarioBatch:
    """Stack (SimParams, seed) pairs sharing ONE site into a batch."""
    assert runs, "empty scenario batch"
    site = runs[0][0].site
    assert all(p.site == site for p, _ in runs), \
        "make_batch takes one site topology; heterogeneous sites go " \
        "through make_multi_site_batch (padded hull, one compile)"
    return _build_batch(runs, tag_sites=False)


def make_multi_site_batch(
        runs: Sequence[tuple[SimParams, int]]) -> ScenarioBatch:
    """Stack (SimParams, seed) pairs on ARBITRARY FBSite variants into
    one batch that runs as ONE vmapped compile (the Fig 1
    design-comparison axis).

    Every scenario is padded to the batch hull (per-axis max) with
    validity masks; labels gain a ``|<ncl>x<rpc>c<cpc>f<nfc>`` site tag
    so same-spec runs on different sites stay distinguishable. Each
    scenario's metrics match its single-site ``run_sweep`` result
    (tests/test_topology_general.py pins this).
    """
    return _build_batch(runs, tag_sites=True)


def grid_runs(traces=None, gating=(True, False), seeds=(0,),
              rate_scales=(1.0,), site: FBSite = FBSite(),
              **params_kw) -> list:
    """(SimParams, seed) pairs for the standard scenario grid: traces x
    {LC/DC, always-on} x utilization (rate) scales x seeds — the
    Fig 9/10 evaluation matrix. The single definition of that grid,
    shared by sweep_grid and the serial/batched benchmark."""
    if traces is None:       # explicit () stays empty (make_batch rejects)
        traces = tuple(TRAFFIC_SPECS)
    return [(SimParams(spec=TRAFFIC_SPECS[t], site=site, gating_enabled=g,
                       rate_scale=rs, **params_kw), s)
            for t in traces
            for g in gating for rs in rate_scales for s in seeds]


def sweep_grid(traces=None, gating=(True, False), seeds=(0,),
               rate_scales=(1.0,), site: FBSite = FBSite(),
               **params_kw) -> ScenarioBatch:
    """The standard scenario grid as one vmappable batch."""
    return make_batch(grid_runs(traces, gating, seeds, rate_scales, site,
                                **params_kw))


def _site_masks(hull: FBSite, scen: Scenario):
    """Validity masks + logical rack ids of a real site inside the hull.

    Racks and CSWs occupy blocked cluster-major hull positions — rack r
    of cluster k sits at row k*hull.racks_per_cluster + r — so the
    step's reshapes to (n_clusters, ...) stay static while the REAL
    dims ride in as traced scenario knobs. Returns (rack_valid (R,),
    csw_valid (NC,), rack_uid (R,), rsw_max_stage (R,), csw_max_stage
    (NC,)); invalid switches get max stage 1 (they idle at the floor).
    """
    kk = jnp.arange(hull.n_clusters)
    rr = jnp.arange(hull.racks_per_cluster)
    cc = jnp.arange(hull.csw_per_cluster)
    cl_valid = kk < scen.ncl
    rack_valid = (cl_valid[:, None] & (rr[None, :] < scen.rpc)).reshape(-1)
    csw_valid = (cl_valid[:, None] & (cc[None, :] < scen.cpc)).reshape(-1)
    # logical id: position in the site's OWN (unpadded) rack order; the
    # PRNG is keyed on this, making traffic independent of hull padding
    rack_uid = (kk[:, None] * scen.rpc + rr[None, :]).reshape(-1)
    rsw_max = jnp.where(rack_valid, scen.cpc, 1).astype(jnp.int32)
    csw_max = jnp.where(csw_valid, scen.nfc, 1).astype(jnp.int32)
    return rack_valid, csw_valid, rack_uid, rsw_max, csw_max


def _init_state(hull: FBSite, scen: Scenario, key) -> SimState:
    s = hull
    R, P = s.n_racks, s.csw_per_cluster
    NC, RPC, NF = s.n_csw, s.racks_per_cluster, s.n_fc
    g = scen.gating_enabled
    rack_valid, csw_valid, _, rsw_max, csw_max = _site_masks(hull, scen)

    def tier_gate(n, links, pin):
        # gating on: stage floor 1; off: every REAL link up, pinned
        # there (padded links beyond the site's own never power on)
        base = gating.gate_init(n, links)
        stage = jnp.where(g, base.stage, pin)
        powered = jnp.where(g, base.powered,
                            jnp.arange(links)[None, :] < pin[:, None])
        return base._replace(stage=stage, powered=powered)

    acc = {
        "rsw_backlog": jnp.zeros(()), "rsw_served": jnp.zeros(()),
        "csw_up_backlog": jnp.zeros(()), "csw_up_served": jnp.zeros(()),
        "csw_down_backlog": jnp.zeros(()), "csw_down_served": jnp.zeros(()),
        "fc_backlog": jnp.zeros(()), "fc_served": jnp.zeros(()),
        "ring_pkts": jnp.zeros(()), "fc_ring_pkts": jnp.zeros(()),
        "injected": jnp.zeros(()), "intra_rack": jnp.zeros(()),
        "drops": jnp.zeros(()),
        "rsw_powered": jnp.zeros(()), "csw_powered": jnp.zeros(()),
        "node_on": jnp.zeros(()),
        "half_off_ticks": jnp.zeros(()),
        "on_frac_hist": jnp.zeros((4,)),   # (0-25,25-50,50-75,75-100]% on
        # in-scan packet-delay distribution (log-spaced bins, see module
        # docstring) + the attribution split feeding _finalize
        "delay_hist": jnp.zeros((C.DELAY_HIST_BINS,)),
        "delay_sum": jnp.zeros(()),        # sum w * d (us-packets)
        "delay_wt": jnp.zeros(()),         # total sampled packets
        "delay_wt_inter": jnp.zeros(()),   # inter-cluster sampled packets
        "delay_queue_sum": jnp.zeros(()),  # queue-wait part of delay_sum
        "delay_stall_sum": jnp.zeros(()),  # wake-stall part of delay_sum
        "wake_stall_pkts": jnp.zeros(()),  # packets arriving mid stage-up
        # optical fault model (all exactly 0 with zero fault knobs)
        "fault_drops": jnp.zeros(()),      # pkts lost to dying links
        "delay_fault_sum": jnp.zeros(()),  # fault_stall part of delay_sum
        "fault_stall_pkts": jnp.zeros(()),  # pkts arriving mid force-wake
        "wake_retries": jnp.zeros(()),     # failed stage-up firings
        "forced_wakes": jnp.zeros(()),     # min-connectivity fallbacks
        "fault_link_ticks": jnp.zeros(()),  # hard-faulted link-ticks
        "conn_loss_rack_ticks": jnp.zeros(()),   # valid RSWs with a
        "conn_loss_csw_ticks": jnp.zeros(()),    # healthy-but-unusable
        #                                          uplink set (ticks)
        # post-serve occupancy moments from the switch kernel
        "rsw_occ_m1": jnp.zeros(()), "rsw_occ_m2": jnp.zeros(()),
        "csw_occ_m1": jnp.zeros(()), "csw_occ_m2": jnp.zeros(()),
        # flow engine (all exactly 0 at flow_mode=0: no flow is ever
        # admitted, every add below is masked to +0.0)
        "flows_started": jnp.zeros(()),    # includes evicted arrivals
        "flows_completed": jnp.zeros(()),
        "flows_evicted": jnp.zeros(()),    # table-overflow rejections
        "fct_sum": jnp.zeros(()),          # sum FCT (us) over completions
        "fct_slow_sum": jnp.zeros(()),     # sum FCT/ideal slowdown
        # per-size-class (short/medium/long) completion histograms:
        # FCT in the FCT_BIN_EDGES_US frame, slowdown in the
        # FCT_SLOWDOWN_BIN_EDGES frame
        "fct_hist": jnp.zeros((3, C.FCT_HIST_BINS)),
        "fct_slow_hist": jnp.zeros((3, C.FCT_SLOWDOWN_HIST_BINS)),
    }
    return SimState(
        key=key,
        burst_on=jnp.ones((R,), bool),
        flow_rem=jnp.zeros((R, F_SLOTS), jnp.int32),
        flow_dest=jnp.zeros((R, F_SLOTS), jnp.int32),
        flow_fast=jnp.zeros((R, F_SLOTS), bool),
        tick=jnp.zeros((), jnp.int32),
        ft_start=jnp.zeros((R, C.FLOW_TABLE_SLOTS), jnp.int32),
        ft_rem=jnp.zeros((R, C.FLOW_TABLE_SLOTS), jnp.float32),
        ft_size=jnp.zeros((R, C.FLOW_TABLE_SLOTS), jnp.int32),
        ft_dst=jnp.zeros((R, C.FLOW_TABLE_SLOTS), jnp.int32),
        ft_cwnd=jnp.zeros((R, C.FLOW_TABLE_SLOTS), jnp.float32),
        rsw_q=jnp.zeros((R, P, 2)),
        csw_up_q=jnp.zeros((NC, s.csw_uplinks)),
        csw_down_q=jnp.zeros((NC, RPC)),
        fc_down_q=jnp.zeros((NF, NC)),
        rsw_gate=tier_gate(R, P, rsw_max),
        csw_gate=tier_gate(NC, s.csw_uplinks, csw_max),
        rsw_fault=gating.fault_init(R, P),
        csw_fault=gating.fault_init(NC, s.csw_uplinks),
        node_on=jnp.zeros((R,)),
        acc=acc,
    )


def _spawn_flows(scen: Scenario, k_u, k_z, rack_uid, rack_valid,
                 burst_on, flow_rem, flow_dest, flow_fast):
    """Per-rack flow arrivals: Bernoulli spawn into the first free slot.

    All per-rack randomness is keyed by fold_in(tick key, rack_uid) —
    the rack's LOGICAL id within its own site, not its row in the
    padded hull — so a site's traffic is bit-identical whether it runs
    at exact dims or padded inside a heterogeneous multi-site batch.
    Returns the updated flow state plus this tick's per-flow pace
    uniforms (R, F_SLOTS).
    """
    with jax.named_scope("draws"):
        ku = jax.vmap(lambda i: jax.random.fold_in(k_u, i))(rack_uid)
        kz = jax.vmap(lambda i: jax.random.fold_in(k_z, i))(rack_uid)
        u = jax.vmap(lambda k: jax.random.uniform(k, (5 + F_SLOTS,)))(ku)
        z = jax.vmap(lambda k: jax.random.normal(k, (2,)))(kz)

    # ON/OFF burst Markov
    stay_on = u[:, 0] > scen.p_on_off
    wake = u[:, 1] < scen.p_off_on
    burst_on = jnp.where(burst_on, stay_on, wake)

    # padded hull rows never spawn: they stay empty forever; with the
    # flow engine selected (flow_mode=1) the legacy table never fills
    # (the mask is a scalar True at flow_mode=0, so the rate-based
    # path's draws and spawns are bit-untouched)
    spawn = (u[:, 2] < scen.p_spawn) & burst_on & rack_valid \
        & (scen.flow_mode == 0)

    with jax.named_scope("draws"):
        # lognormal mixture sizes -> packets (1250 B per packet)
        pick_mix = u[:, 3] < scen.size_w
        size_b = jnp.where(pick_mix,
                           jnp.exp(scen.size_mu1 + scen.size_s1 * z[:, 0]),
                           jnp.exp(scen.size_mu2 + scen.size_s2 * z[:, 1]))
        size_p = jnp.maximum(jnp.ceil(size_b / 1250.0),
                             1.0).astype(jnp.int32)
        ud = u[:, 4]
        dest = jnp.where(
            ud < scen.p_intra_rack, 0,
            jnp.where(ud < scen.p_intra_rack + scen.p_intra_cluster,
                      1, 2)).astype(jnp.int32)

    free = flow_rem == 0
    first_free = jnp.argmax(free, axis=1)               # (R,)
    has_free = jnp.any(free, axis=1)
    do = spawn & has_free
    # dense one-hot slot update instead of a scatter: vmapped scatters
    # are slow on CPU XLA and this keeps the sweep engine's batched
    # per-tick cost near the serial path's
    slot = do[:, None] & (jnp.arange(F_SLOTS)[None, :]
                          == first_free[:, None])       # (R,F)
    flow_rem = flow_rem + jnp.where(slot, size_p[:, None], 0)
    flow_dest = jnp.where(slot, dest[:, None], flow_dest)
    fast = size_p >= scen.elephant_pkts
    flow_fast = jnp.where(slot, fast[:, None], flow_fast)
    return burst_on, flow_rem, flow_dest, flow_fast, u[:, 5:]


def make_sim_step(hull: FBSite):
    """One tick for ONE scenario on the static padded ``hull``; every
    scenario knob — including the scenario's real site dims — is a
    traced scalar, so jax.vmap(step) batches arbitrarily many scenarios
    (on heterogeneous sites fitting the hull) per compile.

    Each part of the step runs under a ``jax.named_scope``: ``edge``,
    ``flows``, ``rsw``, ``csw``, ``down``, ``stats`` and ``gating``,
    with a nested ``draws`` around the random draws and the transforms
    of uniforms into samples, and a nested ``faults`` in ``gating``
    around the hard-fault arrivals, the fault-drop moves and the
    connectivity audit. The scopes change only the ops' metadata
    (``op_name``); chipbench/progtrace.py reads them from a profile to
    split the step's device time by part."""
    s = hull
    NCL, RPC = s.n_clusters, s.racks_per_cluster
    P = s.csw_per_cluster     # plane axis: RSW uplink c IS cluster-CSW c
    NF = s.n_fc
    CUP = s.csw_uplinks       # == NF (FBSite invariant: uplink f -> FC f)
    R, NC = s.n_racks, s.n_csw
    assert P <= MAX_FAULT_LINKS and CUP <= MAX_FAULT_LINKS, \
        f"hull link axes ({P}, {CUP}) exceed the fixed fault-draw " \
        f"width MAX_FAULT_LINKS={MAX_FAULT_LINKS}"

    def step(scen: Scenario, state: SimState) -> SimState:
        acc = dict(state.acc)
        rack_valid, csw_valid, rack_uid, rsw_max, csw_max = \
            _site_masks(hull, scen)
        rpcf = scen.rpc.astype(jnp.float32)
        nclf = scen.ncl.astype(jnp.float32)
        with jax.named_scope("draws"):
            key, k_u, k_z = jax.random.split(state.key, 3)

        with jax.named_scope("gating"):
            # fault-model randomness: dedicated fold_in branches of the tick
            # key (constants far above any logical switch id) so the
            # existing traffic streams are bit-untouched, then one
            # FIXED-width uniform block per switch keyed by its LOGICAL id —
            # identical draws whether a site runs at exact dims or padded
            # inside a heterogeneous hull. Layout: [0]=wake jitter,
            # [1]=wake-failure, [2+l]=hard-fault hazard of link l.
            with jax.named_scope("draws"):
                k_fr = jax.random.fold_in(k_u, 0x7F000001)
                k_fc = jax.random.fold_in(k_u, 0x7F000002)
            csw_uid = ((jnp.arange(NC) // P) * scen.cpc
                       + jnp.arange(NC) % P).astype(jnp.int32)

            def fault_draws(base, uids):
                ks = jax.vmap(lambda i: jax.random.fold_in(base, i))(uids)
                return jax.vmap(
                    lambda k: jax.random.uniform(k, (2 + MAX_FAULT_LINKS,))
                )(ks)

            with jax.named_scope("draws"):
                u_fr = fault_draws(k_fr, rack_uid)              # (R, 2+16)
                u_fc = fault_draws(k_fc, csw_uid)               # (NC, 2+16)

            # correlated failure domains (plane_fail_prob): ONE hazard draw
            # per shared laser comb, broadcast to every link it feeds, so a
            # comb death takes the whole plane down in one tick. RSW tier:
            # plane p of cluster k is fed by cluster-CSW (k, p) — all of
            # cluster k's rack uplinks p share one draw. CSW tier: FC f's
            # comb feeds csw uplink f site-wide — one draw per FC. New
            # dedicated fold_in branches + the fixed MAX_FAULT_LINKS draw
            # width keep every existing stream bit-untouched and the draws
            # padding-invariant (cluster/plane ids are logical hull
            # positions; real dims are prefix slices of the fixed block).
            with jax.named_scope("draws"):
                k_pr = jax.random.fold_in(k_u, 0x7F000005)
                k_pc = jax.random.fold_in(k_u, 0x7F000006)
                u_plane_cl = jax.vmap(
                    lambda k: jax.random.uniform(k, (MAX_FAULT_LINKS,)))(
                    jax.vmap(lambda i: jax.random.fold_in(k_pr, i))(
                        jnp.arange(NCL, dtype=jnp.int32)))          # (NCL, 16)
                u_plane_r = jnp.broadcast_to(
                    u_plane_cl[:, None, :P], (NCL, RPC, P)).reshape(R, P)
                u_plane_c = jnp.broadcast_to(
                    jax.random.uniform(k_pc, (MAX_FAULT_LINKS,))[None, :CUP],
                    (NC, CUP))

            rsw_ok = state.rsw_fault.timer == 0                 # (R, P)
            csw_ok = state.csw_fault.timer == 0                 # (NC, CUP)
            link_idx_p = jnp.arange(P)[None, :]
            link_idx_c = jnp.arange(CUP)[None, :]
            rsw_link_real = rack_valid[:, None] & (link_idx_p
                                                   < rsw_max[:, None])
            csw_link_real = csw_valid[:, None] & (link_idx_c
                                                  < csw_max[:, None])

        with jax.named_scope("edge"):
            # 1. traffic edge ------------------------------------------------
            burst_on, flow_rem, flow_dest, flow_fast, pace_u = _spawn_flows(
                scen, k_u, k_z, rack_uid, rack_valid, state.burst_on,
                state.flow_rem, state.flow_dest, state.flow_fast)
            active = flow_rem > 0                                   # (R,F)
            # paced emission: mice trickle below line rate (boosted during
            # bursts); elephants transmit at line rate -- overlapping
            # elephants are what push queues over the high watermark.
            pace_eff = jnp.minimum(
                scen.pace * jnp.where(burst_on, scen.burst_pace_boost, 1.0),
                1.0)[:, None]
            pace_flow = jnp.where(flow_fast, scen.elephant_pace, pace_eff)
            emit = active & (pace_u < pace_flow)
            n_holding = jnp.sum(active, axis=1).astype(jnp.float32)  # (R,)
            by_dest = jnp.stack(
                [jnp.sum(emit & (flow_dest == d), axis=1) for d in (0, 1, 2)],
                axis=1).astype(jnp.float32)                          # (R,3)
            flow_rem = jnp.maximum(flow_rem - emit.astype(jnp.int32), 0)

        with jax.named_scope("flows"):
            # 1b. flow-level workload engine (flow_mode=1): fixed-capacity
            # per-rack flow table, pFabric-style heavy-tailed sizes
            # (core/workloads.py), AIMD cwnd — all array ops selected
            # against the rate-based path above by jnp.where, so both modes
            # share ONE compiled program and flow_mode=0 stays bit-identical
            # to the pre-flow engine (the fault-knob discipline: dedicated
            # fold_in branches, fixed draw widths, masked accumulator adds).
            flow_on = scen.flow_mode > 0
            tick_now = state.tick + 1
            FT = C.FLOW_TABLE_SLOTS
            with jax.named_scope("draws"):
                k_fa = jax.random.fold_in(k_u, 0x7F000003)   # arrival + dst
                k_fs = jax.random.fold_in(k_u, 0x7F000004)   # flow sizes
                ka = jax.vmap(lambda i: jax.random.fold_in(k_fa, i))(rack_uid)
                ua = jax.vmap(lambda k: jax.random.uniform(k, (2,)))(ka)
                ks = jax.vmap(lambda i: jax.random.fold_in(k_fs, i))(rack_uid)
                us = jax.vmap(lambda k: jax.random.uniform(
                    k, (C.MAX_INCAST_DEGREE,)))(ks)          # fixed draw width
            # one arrival EVENT spawns `incast` flows converging on the
            # same destination class (the fan-in pattern that stresses the
            # table and the watermark controller together)
            arrive = (ua[:, 0] < scen.flow_rate) & rack_valid & flow_on
            n_new = jnp.where(arrive, scen.incast, 0)            # (R,)
            with jax.named_scope("draws"):
                sizes = workloads.sample_flow_size_pkts(
                    us, scen.flow_dist)                          # (R,W) f32
                ud2 = ua[:, 1]
                fdst = jnp.where(
                    ud2 < scen.p_intra_rack, 0,
                    jnp.where(ud2 < scen.p_intra_rack + scen.p_intra_cluster,
                              1, 2)).astype(jnp.int32)               # (R,)
            # admission: rank the usable free slots (traced flow_cap caps
            # the static FT axis) and match candidate k to the k-th free
            # slot — a whole incast burst admits in one tick, overflow is
            # EVICTION (counted; started == completed + evicted + in-flight
            # stays exact)
            slot_i = jnp.arange(FT)[None, :]
            usable_slot = slot_i < scen.flow_cap                 # (1,FT)
            pre_live = (state.ft_rem > 0.0) & usable_slot        # (R,FT)
            free = ~pre_live & usable_slot
            rank = jnp.cumsum(free.astype(jnp.int32), axis=1) - 1
            cand = jnp.arange(C.MAX_INCAST_DEGREE)
            want = cand[None, :] < n_new[:, None]                # (R,W)
            place = (free[:, :, None]
                     & (rank[:, :, None] == cand[None, None, :])
                     & want[:, None, :])                         # (R,FT,W)
            admitted = jnp.any(place, axis=1)                    # (R,W)
            placed = jnp.any(place, axis=2)                      # (R,FT)
            new_sz = jnp.sum(jnp.where(place, sizes[:, None, :], 0.0),
                             axis=2)                             # (R,FT)
            # AIMD on the PREVIOUS tick's live flows: halve on the rack's
            # hi-watermark congestion signal (previous tick's RSW queues —
            # the 1-tick feedback delay of a real rack-local signal),
            # additive increase toward line rate otherwise
            cong, _ = gating.watermark_triggers(
                jnp.sum(state.rsw_q, axis=2), state.rsw_gate.stage,
                cap=scen.queue_cap, hi=scen.hi, lo=scen.lo)
            ft_cwnd = jnp.where(
                pre_live,
                jnp.where(cong[:, None],
                          jnp.maximum(state.ft_cwnd * C.FLOW_AIMD_DECREASE,
                                      C.FLOW_CWND_MIN_PPT),
                          jnp.minimum(state.ft_cwnd
                                      + C.FLOW_AIMD_INCREASE_PPT,
                                      C.FLOW_LINE_RATE_PPT)),
                state.ft_cwnd)
            ft_start = jnp.where(placed, tick_now, state.ft_start)
            ft_rem = jnp.where(placed, new_sz, state.ft_rem)
            ft_size = jnp.where(placed, new_sz.astype(jnp.int32),
                                state.ft_size)
            ft_dst = jnp.where(placed, fdst[:, None], state.ft_dst)
            ft_cwnd = jnp.where(placed, C.FLOW_CWND_INIT_PPT, ft_cwnd)
            # emission: every live flow sends min(rem, cwnd) this tick
            # (fluid, like the aggregation tiers); the last fraction
            # completes the flow
            ft_live = (ft_rem > 0.0) & usable_slot
            emit_f = jnp.where(ft_live, jnp.minimum(ft_rem, ft_cwnd), 0.0)
            ft_rem = ft_rem - emit_f
            done = ft_live & (ft_rem <= 0.0)                     # (R,FT)
            flow_by_dest = jnp.stack(
                [jnp.sum(jnp.where(ft_dst == d, emit_f, 0.0), axis=1)
                 for d in (0, 1, 2)], axis=1)                    # (R,3)
            # select the traffic edge the datapath sees; at flow_mode=0
            # every flow accumulator add below is exactly +0.0
            by_dest = jnp.where(flow_on, flow_by_dest, by_dest)
            n_holding = jnp.where(
                flow_on, jnp.sum(ft_live, axis=1).astype(jnp.float32),
                n_holding)
            acc["flows_started"] += jnp.sum(n_new).astype(jnp.float32)
            acc["flows_evicted"] += (jnp.sum(n_new)
                                     - jnp.sum(admitted)).astype(jnp.float32)

            acc["injected"] += jnp.sum(by_dest[:, 1:])
            acc["intra_rack"] += jnp.sum(by_dest[:, 0])

        with jax.named_scope("rsw"):
            # 2+3. RSW datapath tick: min-backlog enqueue of the [intra,
            # inter] arrival split + 1 pkt/tick serve per active uplink —
            # the shared switch-step kernel (Pallas on TPU, ref on CPU).
            # The valid mask is per-LINK: hull padding AND hard-faulted
            # transceivers (a dead port neither accepts nor serves).
            (rsw_q, served_split, _, _, rsw_drop, rsw_wait, rsw_m1,
             rsw_m2) = ops.switch_step(
                state.rsw_q, state.rsw_gate.stage, by_dest[:, 1:],
                state.rsw_gate.draining, valid=rack_valid[:, None] & rsw_ok,
                cap=scen.queue_cap, hi=scen.hi, lo=scen.lo, serve_rate=1.0)
            acc["drops"] += jnp.sum(rsw_drop)
            acc["rsw_backlog"] += jnp.sum(rsw_q) + jnp.sum(served_split)
            acc["rsw_served"] += jnp.sum(served_split)
            acc["rsw_occ_m1"] += jnp.sum(rsw_m1)
            acc["rsw_occ_m2"] += jnp.sum(rsw_m2)

        with jax.named_scope("csw"):
            # uplink c of rack r lands on CSW (cluster(r), c): the uplink
            # axis IS the csw_per_cluster plane axis (FBSite invariant)
            srv_rc = served_split.reshape(NCL, RPC, P, 2)
            to_csw = jnp.sum(srv_rc, axis=1)                     # (NCL,P,2)
            inter_in = to_csw[..., 1].reshape(NC)

            # Stage-aware down-plane weights (the per-stage CAM tables of
            # Sec III-B): traffic for rack r rides plane c with weight
            # active(r,c)/stage(r); dest racks are uniform within the
            # cluster. Padded hull rows carry zero weight.
            rsw_stage_f = state.rsw_gate.stage.astype(jnp.float32)
            plane_w = (jnp.arange(P)[None, :]
                       < state.rsw_gate.stage[:, None]) \
                / rsw_stage_f[:, None] * rack_valid[:, None]         # (R,P)
            plane_w_c = plane_w.reshape(NCL, RPC, P)

            # 4. CSW: intra-cluster traffic -> down queues. A packet for rack
            # r arriving UP at csw c may have to cross to plane c' active for
            # r; within a cluster that crossing is the CSW ring. We charge the
            # ring for the mismatch between arrival plane and dest plane.
            intra_cl = jnp.sum(to_csw[..., 0], axis=1)               # (NCL,)
            dest_share = intra_cl[:, None, None] / rpcf * \
                plane_w_c.transpose(0, 2, 1)                     # (NCL,P,RPC)
            csw_down_q = state.csw_down_q + dest_share.reshape(NC, RPC)
            # ring charge: fraction of intra traffic whose up-plane !=
            # down-plane
            up_share = to_csw[..., 0] / jnp.maximum(intra_cl[:, None], 1e-9)
            # per-plane mean dest weight over the cluster's REAL racks
            mean_down = jnp.sum(plane_w_c, axis=1) / rpcf            # (NCL,P)
            same_plane = jnp.sum(jnp.minimum(up_share, mean_down), axis=1)
            acc["ring_pkts"] += jnp.sum(intra_cl * (1.0 - same_plane))

            # 5. CSW uplink datapath tick (40G: 4 pkt/tick) -> FC, through
            # the same shared switch-step kernel (single component).
            (csw_up_q, cserve, _, _, csw_drop, csw_wait, csw_m1,
             csw_m2) = ops.switch_step(
                state.csw_up_q, state.csw_gate.stage, inter_in,
                state.csw_gate.draining, valid=csw_valid[:, None] & csw_ok,
                cap=scen.queue_cap, hi=scen.hi, lo=scen.lo, serve_rate=4.0)
            acc["drops"] += jnp.sum(csw_drop)
            acc["csw_up_backlog"] += jnp.sum(state.csw_up_q)
            acc["csw_up_served"] += jnp.sum(cserve)
            acc["csw_occ_m1"] += jnp.sum(csw_m1)
            acc["csw_occ_m2"] += jnp.sum(csw_m2)

            # uplink f of csw c lands on FC f (the csw_uplinks axis; == n_fc
            # by the FBSite invariant). The FC routes traffic for cluster k
            # down an ACTIVE (f, c') plane of that cluster (per-stage CAMs):
            # weight by the cluster's csw-uplink activity and by the dest
            # rack's active planes.
            fc_in = jnp.sum(cserve, axis=0)                          # (CUP,)
            csw_stage_f = state.csw_gate.stage.astype(jnp.float32)
            fc_w = (jnp.arange(CUP)[None, :]
                    < state.csw_gate.stage[:, None]) / csw_stage_f[:, None]
            # csw c's share of its cluster's down traffic = how much of the
            # cluster's REAL racks ride plane (c mod csw_per_cluster)
            csw_share = (jnp.sum(plane_w_c, axis=1) / rpcf).reshape(NC)
            # total inter-cluster down traffic splits uniformly over the
            # REAL clusters
            down_cl = jnp.sum(fc_in) / nclf                          # scalar
            fc_down_add = down_cl * csw_share[None, :] * fc_w.T      # (NF,NC)
            fc_down_q = state.fc_down_q + fc_down_add

        with jax.named_scope("down"):
            # 6. FC down serve: link (f,c) active iff csw stage[c] > f AND
            #    csw c's uplink-f transceiver is healthy (it is the same
            #    fiber); any residual on an inactive/dead plane rides the
            #    FC ring to the always-on f=0 plane.
            fc_active = (jnp.arange(NF)[:, None]
                         < state.csw_gate.stage[None, :]) & csw_ok.T  # (NF,NC)
            fserve = jnp.minimum(fc_down_q, 4.0) * fc_active
            fc_down_q = fc_down_q - fserve
            stranded = jnp.where(~fc_active, fc_down_q, 0.0)
            mig = jnp.minimum(jnp.sum(stranded), scen.fc_ring)
            # <= 1 under exact division; clamped because a TPU's is not (an
            # ulp over 1 moves more than is stranded and leaves a negative
            # backlog, see kernels/ref.py) — a no-op on the CPU
            mfrac = jnp.minimum(mig / jnp.maximum(jnp.sum(stranded), 1e-9),
                                1.0)
            fc_down_q = fc_down_q - stranded * mfrac
            fc_down_q = fc_down_q.at[0, :].add(
                jnp.sum(stranded * mfrac, axis=0))
            acc["fc_ring_pkts"] += mig
            acc["fc_backlog"] += jnp.sum(state.fc_down_q)
            acc["fc_served"] += jnp.sum(fserve)

            # FC-served packets land on csw c -> its down queues, weighted by
            # each rack's active planes (stage-aware, as above)
            per_csw_down = jnp.sum(fserve, axis=0)                   # (NC,)
            pw_cr = plane_w_c.transpose(0, 2, 1).reshape(NC, RPC)    # (NC,RPC)
            row_w = jnp.sum(pw_cr, axis=1)                           # (NC,)
            pw_norm = pw_cr / jnp.maximum(row_w[:, None], 1e-9)
            routable = row_w > 0.0
            csw_down_q = csw_down_q + \
                jnp.where(routable, per_csw_down, 0.0)[:, None] * pw_norm
            # a csw can still drain FC backlog for a plane no rack currently
            # rides (every rack staged below it after the queue built up);
            # that traffic rides the cluster ring to the always-on plane 0
            # rather than vanishing (conservation: injected == delivered +
            # in-flight + drops)
            orphan = jnp.where(routable, 0.0, per_csw_down)          # (NC,)
            orphan_cl = jnp.sum(orphan.reshape(NCL, P), axis=1)      # (NCL,)
            dest0 = pw_norm.reshape(NCL, P, RPC)[:, 0, :]        # (NCL,RPC)
            csw_down_q = (csw_down_q.reshape(NCL, P, RPC)
                          .at[:, 0, :].add(orphan_cl[:, None] * dest0)
                          .reshape(NC, RPC))
            acc["ring_pkts"] += jnp.sum(orphan_cl)

            # 7. CSW down serve: link (r, c) active iff rsw stage[r] > c AND
            #    rack r's uplink-c transceiver is healthy (same fiber) —
            #    the plane axis is csw_per_cluster; stranded traffic rides
            #    the cluster ring to c=0.
            rsw_stage = state.rsw_gate.stage.reshape(NCL, RPC)
            rsw_ok_pl = rsw_ok.reshape(NCL, RPC, P) \
                .transpose(0, 2, 1)                              # (NCL,P,RPC)
            cidx = jnp.arange(P)[None, :, None]                  # plane pos
            down_act = (cidx < rsw_stage[:, None, :]) & rsw_ok_pl # (NCL,P,RPC)
            dq = csw_down_q.reshape(NCL, P, RPC)
            dserve = jnp.minimum(dq, 1.0) * down_act
            dq = dq - dserve
            stranded_d = jnp.where(~down_act, dq, 0.0)           # (NCL,P,RPC)
            tot_str = jnp.sum(stranded_d, axis=(1, 2))               # (NCL,)
            migd = jnp.minimum(tot_str, scen.csw_ring)
            dfrac = jnp.minimum(migd / jnp.maximum(tot_str, 1e-9),
                                1.0)[:, None, None]   # clamped as mfrac
            moved = stranded_d * dfrac
            dq = dq - moved
            dq = dq.at[:, 0, :].add(jnp.sum(moved, axis=1))
            csw_down_q = dq.reshape(NC, RPC)
            acc["ring_pkts"] += jnp.sum(migd)
            acc["csw_down_backlog"] += jnp.sum(state.csw_down_q)
            delivered_r = jnp.sum(dserve, axis=1).reshape(R)         # (R,)
            acc["csw_down_served"] += jnp.sum(dserve)

        with jax.named_scope("stats"):
            # 8. node-level link gating (OS intercept: zero latency cost).
            # A server link is held on while its server has active flows (tx)
            # or receives traffic, with an idle timeout.
            need = jnp.minimum(n_holding + delivered_r, scen.spr)
            node_on = jnp.maximum(
                need, state.node_on - scen.spr / NODE_IDLE_TICKS)
            acc["node_on"] += jnp.sum(node_on)

            # 8.5 in-scan delay sampling (see module docstring): one sample
            # per rack per destination class for the packets injected THIS
            # tick, fed by the kernel's backlog-age taps plus the
            # gating-attributed wake stall. (R, planes) view of the CSW down
            # queues each rack faces — shared with the step-9 RSW trigger.
            down_rc = csw_down_q.reshape(NCL, P, RPC) \
                .transpose(0, 2, 1).reshape(R, P)                # (R, planes)
            # queue waits: RSW enqueue (kernel), CSW down plane-weighted
            # (1 pkt/tick links), CSW uplink arrival-weighted per cluster,
            # FC capacity-normalized (4 pkt/tick per active real link)
            down_wait = jnp.sum(plane_w * down_rc, axis=1)           # (R,)
            win = inter_in.reshape(NCL, P)

            def cl_avg(x):
                # arrival-weighted per-cluster mean over the cluster's CSWs
                return jnp.sum(win * x.reshape(NCL, P), axis=1) \
                    / jnp.maximum(jnp.sum(win, axis=1), 1e-9)        # (NCL,)

            w_csw_cl = cl_avg(csw_wait)
            fc_cap = 4.0 * jnp.sum((fc_active & csw_valid[None, :])
                                   .astype(jnp.float32))
            fc_wait = jnp.sum(fc_down_q) / jnp.maximum(fc_cap, 1e-9)
            # wake stalls: remaining STAGE_UP_DELAY ticks of an in-flight
            # stage-up at the switches this rack's packets traverse; exactly
            # zero with gating disabled (up_timer never leaves 0, and the
            # attribution is masked besides)
            # wake + fault-forced stalls through the ONE attribution seam
            # (gating.stall_attribution): the same pair feeds the delay
            # histogram below AND the flow FCT samples, so gating stalls
            # attribute into flow completion times by construction; both
            # are EXACTLY 0 when gating is off
            g_on = scen.gating_enabled
            stall_rsw, fstall_rsw = gating.stall_attribution(
                state.rsw_gate, state.rsw_fault, g_on)               # (R,)
            stall_csw, fstall_csw = gating.stall_attribution(
                state.csw_gate, state.csw_fault, g_on)               # (NC,)
            stall_csw_cl = cl_avg(stall_csw)
            fstall_csw_cl = cl_avg(fstall_csw)

            def per_rack(x_cl):                                  # (NCL,)->(R,)
                return jnp.broadcast_to(x_cl[:, None], (NCL, RPC)).reshape(R)

            wt_i, wt_x = by_dest[:, 1], by_dest[:, 2]      # intra-cl / inter
            q_i = rsw_wait + down_wait                     # queue-wait parts
            q_x = q_i + per_rack(w_csw_cl) + fc_wait
            s_i = stall_rsw                                # wake-stall parts
            s_x = stall_rsw + per_rack(stall_csw_cl)
            f_i = fstall_rsw                               # fault-stall parts
            f_x = fstall_rsw + per_rack(fstall_csw_cl)
            base_i = STACK_US + 4.0 * WIRE_HOP_US
            d_i = base_i + q_i + s_i + f_i
            d_x = base_i + 2.0 * WIRE_HOP_US + q_x + s_x + f_x
            hist = _delay_hist_add(acc["delay_hist"], d_i, wt_i)
            acc["delay_hist"] = _delay_hist_add(hist, d_x, wt_x)
            acc["delay_sum"] += jnp.sum(wt_i * d_i) + jnp.sum(wt_x * d_x)
            acc["delay_wt"] += jnp.sum(wt_i) + jnp.sum(wt_x)
            acc["delay_wt_inter"] += jnp.sum(wt_x)
            acc["delay_queue_sum"] += jnp.sum(wt_i * q_i) + jnp.sum(wt_x * q_x)
            acc["delay_stall_sum"] += jnp.sum(wt_i * s_i) + jnp.sum(wt_x * s_x)
            acc["delay_fault_sum"] += jnp.sum(wt_i * f_i) + jnp.sum(wt_x * f_x)
            acc["wake_stall_pkts"] += jnp.sum(wt_i * (s_i > 0)) \
                + jnp.sum(wt_x * (s_x > 0))
            acc["fault_stall_pkts"] += jnp.sum(wt_i * (f_i > 0)) \
                + jnp.sum(wt_x * (f_x > 0))

            # 8.6 flow completion times (flow_mode=1; every weight below is
            # exactly 0 at flow_mode=0). FCT = table residence + THIS
            # tick's sampled path delay for the flow's class — d_i/d_x
            # already carry queue waits plus the wake/fault stalls, so
            # gating stalls attribute into FCT through the same seam as the
            # delay histogram. Slowdown is vs the ideal-bandwidth baseline
            # (line-rate serialization + unloaded path); residence >= size
            # (per-tick emission <= line rate) and path >= the unloaded
            # path, so slowdown >= 1 by construction.
            wdone = done.astype(jnp.float32)                     # (R,FT)
            residence = (tick_now - ft_start + 1).astype(jnp.float32)
            path_us = jnp.where(
                ft_dst == 2, d_x[:, None],
                jnp.where(ft_dst == 1, d_i[:, None], STACK_US))
            fct_us = residence * C.TICK_US + path_us
            ideal_base = jnp.where(
                ft_dst == 2, base_i + 2.0 * WIRE_HOP_US,
                jnp.where(ft_dst == 1, base_i, STACK_US))
            ideal_us = workloads.ideal_fct_us(ft_size, ideal_base)
            slow = fct_us / ideal_us
            cls = workloads.flow_size_class(ft_size)             # (R,FT)
            fct_flat = fct_us.reshape(-1)
            slow_flat = slow.reshape(-1)
            for c in range(3):
                wc = (wdone * (cls == c)).reshape(-1)
                acc["fct_hist"] = acc["fct_hist"].at[c].set(
                    _delay_hist_add(
                        acc["fct_hist"][c], fct_flat, wc,
                        min_val=C.FCT_HIST_MIN_US,
                        bpo=C.FCT_HIST_BINS_PER_OCTAVE,
                        bins=C.FCT_HIST_BINS))
                acc["fct_slow_hist"] = acc["fct_slow_hist"].at[c].set(
                    _delay_hist_add(
                        acc["fct_slow_hist"][c], slow_flat, wc,
                        min_val=C.FCT_SLOWDOWN_HIST_MIN,
                        bpo=C.FCT_SLOWDOWN_HIST_BINS_PER_OCTAVE,
                        bins=C.FCT_SLOWDOWN_HIST_BINS))
            acc["flows_completed"] += jnp.sum(wdone)
            acc["fct_sum"] += jnp.sum(fct_us * wdone)
            acc["fct_slow_sum"] += jnp.sum(slow * wdone)

        with jax.named_scope("gating"):
            # 9. watermark controllers. Per Sec III-B the backlog monitor
            # watches ALL output queues of a switch: the RSW trigger combines
            # its uplink queues with the CSW down-queue pressure on each
            # plane-to-rack link, and the CSW trigger combines its FC uplink
            # queues with the FC down-queue pressure per plane (a saturated
            # 40G down plane must open the next stage). gating_enabled is a
            # traced scenario knob: the controller always steps and the
            # result is selected, so LC/DC and always-on scenarios share one
            # compiled program. max_stage caps each switch at its REAL link
            # count (padded hull links never activate).
            #
            # 9a. hard-fault evolution FIRST (applies to LC/DC and always-on
            # scenarios alike: transceivers die regardless of the
            # controller): Bernoulli arrivals on powered healthy real links,
            # repair countdown, and the dying link's queued packets move to
            # the fault-drop conservation bin (a dead laser transmits
            # nothing; injected == delivered + in-flight + drops +
            # fault_drops stays exact).
            with jax.named_scope("faults"):
                rsw_timer, rsw_new_f = gating.fault_arrivals(
                    state.rsw_fault.timer, u_fr[:, 2:2 + P],
                    state.rsw_gate.powered, rsw_link_real,
                    scen.fault_prob, scen.repair_ticks,
                    plane_u=u_plane_r, plane_fail_prob=scen.plane_fail_prob)
                csw_timer, csw_new_f = gating.fault_arrivals(
                    state.csw_fault.timer, u_fc[:, 2:2 + CUP],
                    state.csw_gate.powered, csw_link_real,
                    scen.fault_prob, scen.repair_ticks,
                    plane_u=u_plane_c, plane_fail_prob=scen.plane_fail_prob)
                acc["fault_drops"] += \
                    jnp.sum(jnp.where(rsw_new_f[..., None], rsw_q, 0.0)) \
                    + jnp.sum(jnp.where(csw_new_f, csw_up_q, 0.0))
                rsw_q = jnp.where(rsw_new_f[..., None], 0.0, rsw_q)
                csw_up_q = jnp.where(csw_new_f, 0.0, csw_up_q)
                acc["fault_link_ticks"] += jnp.sum(rsw_timer > 0) \
                    + jnp.sum(csw_timer > 0)

            # 9b. the controllers, fault-aware: jittered/failing wakes plus
            # the min-connectivity fallback (force-wake the cheapest healthy
            # link when the usable prefix died; stall charged to the
            # fault_stall carry). All knobs zero => bit-identical GateState.
            rsw_gated, rsw_fwake, rsw_diag = gating.gate_step(
                state.rsw_gate, jnp.maximum(jnp.sum(rsw_q, axis=2), down_rc),
                cap=scen.queue_cap, hi=scen.hi, lo=scen.lo, dwell=scen.dwell,
                max_stage=rsw_max, link_ok=rsw_timer == 0,
                link_real=rsw_link_real, u_jitter=u_fr[:, 0],
                u_fail=u_fr[:, 1], wake_fail_prob=scen.wake_fail_prob,
                wake_jitter_frac=scen.wake_jitter_frac,
                fault_wake=state.rsw_fault.wake,
                fallback=scen.fault_fallback)
            csw_gated, csw_fwake, csw_diag = gating.gate_step(
                state.csw_gate, jnp.maximum(csw_up_q, fc_down_q.T),
                cap=scen.queue_cap, hi=scen.hi, lo=scen.lo, dwell=scen.dwell,
                max_stage=csw_max, link_ok=csw_timer == 0,
                link_real=csw_link_real, u_jitter=u_fc[:, 0],
                u_fail=u_fc[:, 1], wake_fail_prob=scen.wake_fail_prob,
                wake_jitter_frac=scen.wake_jitter_frac,
                fault_wake=state.csw_fault.wake,
                fallback=scen.fault_fallback)

            def sel(new, old):
                return jax.tree.map(
                    lambda a, b: jnp.where(scen.gating_enabled, a, b),
                    new, old)

            rsw_gate = sel(rsw_gated, state.rsw_gate)
            csw_gate = sel(csw_gated, state.csw_gate)
            # the fallback (and its stall) only exists under gating: an
            # always-on scenario's links are already all up, so the carry is
            # pinned to 0 — fault_stall attribution exactly 0, as pinned by
            # the acceptance tests
            rsw_fwake = jnp.where(g_on, rsw_fwake, 0)
            csw_fwake = jnp.where(g_on, csw_fwake, 0)
            acc["wake_retries"] += jnp.where(
                g_on, jnp.sum(rsw_diag["retries"]) +
                jnp.sum(csw_diag["retries"]), 0)
            acc["forced_wakes"] += jnp.where(
                g_on, jnp.sum(rsw_diag["forced"]) +
                jnp.sum(csw_diag["forced"]), 0)

            # 9c. min-connectivity audit on the END-of-tick state: a valid
            # switch that still HAS a healthy real link but zero usable
            # ones records a connectivity-loss tick — loss attributable to
            # the gating policy (links powered off), which the fallback
            # must drive to exactly 0. A switch whose real links are ALL
            # simultaneously hard-faulted is excluded: no routing/gating
            # policy can preserve its connectivity, and that hardware
            # unavailability is already visible in link_fault_frac /
            # delivered_frac.
            with jax.named_scope("faults"):
                rsw_healthy = (rsw_timer == 0) & rsw_link_real
                csw_healthy = (csw_timer == 0) & csw_link_real
                rsw_usable_f = gating.usable_links(rsw_gate.stage,
                                                   rsw_gate.draining, P) \
                    & rsw_healthy
                csw_usable_f = gating.usable_links(csw_gate.stage,
                                                   csw_gate.draining, CUP) \
                    & csw_healthy
                acc["conn_loss_rack_ticks"] += jnp.sum(
                    rack_valid & jnp.any(rsw_healthy, axis=1)
                    & ~jnp.any(rsw_usable_f, axis=1))
                acc["conn_loss_csw_ticks"] += jnp.sum(
                    csw_valid & jnp.any(csw_healthy, axis=1)
                    & ~jnp.any(csw_usable_f, axis=1))

            # power accounting: a hard-faulted transceiver is dark — it
            # draws nothing while dead, whatever the controller thinks
            rsw_pow = jnp.sum(
                jnp.where(rack_valid[:, None] & (rsw_timer == 0),
                          rsw_gate.powered, False))
            csw_pow = jnp.sum(
                jnp.where(csw_valid[:, None] & (csw_timer == 0),
                          csw_gate.powered, False))
            acc["rsw_powered"] += rsw_pow
            acc["csw_powered"] += csw_pow
            # gated-link population of the REAL site:
            # ncl*rpc*cpc (RSW-CSW) + ncl*cpc*nfc (CSW-FC)
            cpcf = scen.cpc.astype(jnp.float32)
            nfcf = scen.nfc.astype(jnp.float32)
            n_gated = nclf * cpcf * (rpcf + nfcf)
            frac_on = (rsw_pow + csw_pow) / n_gated
            acc["half_off_ticks"] += (frac_on <= 0.5)
            # half-open-LEFT quartiles (0,25],(25,50],(50,75],(75,100]: an
            # exact boundary (e.g. the all-floor 25% state) belongs to the
            # LOWER bucket, matching the histogram labels
            bucket = on_frac_bucket(frac_on)
            # one-hot, no scatter
            acc["on_frac_hist"] += (jnp.arange(4) == bucket)

        return SimState(key, burst_on, flow_rem, flow_dest, flow_fast,
                        tick_now, ft_start, ft_rem, ft_size, ft_dst,
                        ft_cwnd,
                        rsw_q, csw_up_q, csw_down_q, fc_down_q,
                        rsw_gate, csw_gate,
                        gating.FaultState(rsw_timer, rsw_fwake),
                        gating.FaultState(csw_timer, csw_fwake),
                        node_on, acc)

    return step


class SweepValidationError(RuntimeError):
    """Raised by ``validate=True`` sweeps when the in-program guards
    (finite-value / conservation, see ``run_sweep``) tripped. Carries
    ``labels`` (the failing scenarios) and ``first_bad_chunk`` (the
    earliest chunk index at which any of them first failed)."""

    def __init__(self, labels, first_bad_chunk):
        self.labels = tuple(labels)
        self.first_bad_chunk = int(first_bad_chunk)
        super().__init__(
            f"sweep validation failed for scenario(s) {list(labels)} "
            f"(first failing chunk: {first_bad_chunk})")


# The executor marks its phases with ``jax.profiler.TraceAnnotation``
# host spans, which cost nothing until a profiler runs and then land in
# the profile beside the device's ops, on one clock: ``lcdc.sweep``
# (one ``run_sweep_planned`` call), ``lcdc.plan``, ``lcdc.batch``,
# ``lcdc.init`` (the operands' build), ``lcdc.dispatch`` and one
# ``lcdc.chunk`` per chunk, ``lcdc.snapshot``, ``lcdc.fetch`` (the host
# waiting on the device), ``lcdc.finalize`` and ``lcdc.retry``.
# chipbench/progtrace.py reads them.

#: test hook for the fault-tolerant planned executor: when set, called
#: as ``BUCKET_FAIL_HOOK(bucket_index, phase)`` with phase in
#: {"dispatch", "fetch", "retry"} before the corresponding stage of
#: each bucket; raising from it simulates a bucket failure
#: (tests/test_faults.py uses this to pin the isolation contract)
BUCKET_FAIL_HOOK = None

#: preemption-injection seam for the durable executor: when set, called
#: as ``CHUNK_HOOK(chunk_index)`` at the top of every chunk-loop
#: iteration (before that chunk is dispatched); raising from it
#: simulates a crash/preemption at an exact chunk boundary
#: (tests/test_durability.py kills runs here and resumes them)
CHUNK_HOOK = None

#: monkeypatchable sleep used by the retry-backoff loop, so tests can
#: pin the exact backoff sequence without waiting wall-clock time
RETRY_SLEEP = time.sleep


@dataclass(frozen=True)
class BucketRetryPolicy:
    """Retry/deadline policy for ``run_sweep_planned`` bucket failures.

    The default reproduces the PR 6 contract exactly: ONE serial retry
    on the conservative ``fold="host"`` path, immediately, with no
    deadline. ``backoff_s(r)`` is the sleep before retry attempt ``r``
    (1-based): ``min(backoff_base_s * backoff_mult**(r-1),
    backoff_max_s)``, or 0 when ``backoff_base_s`` is 0 (no sleep).
    ``deadline_s`` bounds each bucket's cumulative wall-clock time
    across its attempts: once exceeded, remaining retries are abandoned
    and the bucket degrades to a structured error entry. The deadline
    never discards finished work — a bucket that completed (however
    slowly) keeps its results; only further RETRIES are cut off.
    """
    max_retries: int = 1
    backoff_base_s: float = 0.0
    backoff_mult: float = 2.0
    backoff_max_s: float = 60.0
    deadline_s: float | None = None

    def __post_init__(self):
        def bad(msg):
            raise ValueError(f"BucketRetryPolicy: {msg}")
        if self.max_retries < 0:
            bad(f"max_retries must be >= 0, got {self.max_retries}")
        if self.backoff_base_s < 0.0:
            bad(f"backoff_base_s must be >= 0, got {self.backoff_base_s}")
        if self.backoff_mult < 1.0:
            bad(f"backoff_mult must be >= 1, got {self.backoff_mult}")
        if self.backoff_max_s < 0.0:
            bad(f"backoff_max_s must be >= 0, got {self.backoff_max_s}")
        if self.deadline_s is not None and self.deadline_s < 0.0:
            bad(f"deadline_s must be >= 0, got {self.deadline_s}")

    def backoff_s(self, attempt: int) -> float:
        """Sleep (seconds) before 1-based retry ``attempt``."""
        if self.backoff_base_s <= 0.0:
            return 0.0
        return min(self.backoff_base_s * self.backoff_mult ** (attempt - 1),
                   self.backoff_max_s)


def _fold_dtype():
    """The device fold-buffer dtype: float64 where the backend enables
    x64, otherwise float32 (compensated with a Kahan pair)."""
    return jax.dtypes.canonicalize_dtype(np.float64)


def _should_shard(n_scenarios: int, shard: bool | None) -> bool:
    """THE sharding-eligibility predicate, shared by ``_start_sweep``
    (actual execution) and ``execution_mode`` (cache keys / records) so
    the two can never drift: shard when more than one local device is
    visible and the batch has more than one scenario (a single
    scenario has nothing to distribute)."""
    n_dev = jax.local_device_count()
    want = shard if shard is not None else n_dev > 1
    return bool(want and n_dev > 1 and n_scenarios > 1)


def execution_mode(*, fold: str = "device", shard: bool | None = None,
                   n_scenarios: int | None = None):
    """The execution-layer knobs that can shift float results — joined
    into result-cache keys (benchmarks/simcache.py) so runs under a
    different fold path, fold precision or device layout never serve
    each other stale results. Pass ``n_scenarios`` (the batch size)
    when known: it applies the same ``_should_shard`` predicate
    ``_start_sweep`` uses, so the reported layout matches the actual
    execution."""
    sharded = _should_shard(2 if n_scenarios is None else n_scenarios,
                            shard)
    return {"fold": fold,
            "fold_dtype": jnp.dtype(_fold_dtype()).name,
            "devices": jax.local_device_count() if sharded else 1}


def _sweep_chunk_impl(site: FBSite, scen: Scenario, state: SimState,
                      length: int, live, fold, guard=None, chunk_idx=None,
                      tol=None, validate: bool = False):
    global TRACE_COUNT
    TRACE_COUNT += 1          # python side effect: counts traces only
    if TRACE_HOOK is not None:
        TRACE_HOOK(site)      # trace-time attribution (sanitizer seam)
    step = make_sim_step(site)
    vstep = jax.vmap(step)

    def tick(st, is_live):
        # a dead (masked remainder) tick passes the carry through
        # unchanged, so the tail chunk reuses this same trace; is_live
        # is a scalar (not vmapped), so the cond genuinely branches —
        # dead ticks skip the step instead of computing-and-discarding
        return jax.lax.cond(is_live, lambda s: vstep(scen, s),
                            lambda s: s, st), None

    out, _ = jax.lax.scan(tick, state, live, length=length)
    new_fold = None
    if fold is not None:
        # device-resident fold: absorb this chunk's accumulators into
        # the (sum, comp) Kahan buffer and re-zero them, all inside this
        # same program — the chunk loop never synchronizes with the host
        fsum, fcomp = fold
        nsum, ncomp = {}, {}
        with jax.named_scope("fold"):
            for k in out.acc:
                v = out.acc[k].astype(fsum[k].dtype)
                y = v - fcomp[k]
                t = fsum[k] + y
                nsum[k] = t
                ncomp[k] = (t - fsum[k]) - y
            out = out._replace(acc=jax.tree.map(jnp.zeros_like, out.acc))
        new_fold = (nsum, ncomp)
    if not validate:
        return out, new_fold, guard
    # ---- opt-in in-program guards (validate=True) -----------------------
    # per-scenario finite-value check over the in-flight queues and the
    # running totals, plus the conservation identity
    #   injected == delivered + drops + fault_drops + in-flight
    # on the device-fold path (the totals live on device there). The
    # guard carries, per scenario, the first chunk index at which any
    # check failed (-1 = clean); chunk_idx/tol are traced scalars so
    # the chunk loop still reuses one executable.
    with jax.named_scope("guard"):
        B = guard.shape[0]

        def finite(arrs):
            ok = jnp.ones((B,), bool)
            for a in arrs:
                ok &= jnp.all(jnp.isfinite(a.reshape(B, -1)), axis=1)
            return ok

        queues = (out.rsw_q, out.csw_up_q, out.csw_down_q, out.fc_down_q)
        ok = finite(queues)
        if new_fold is not None:
            tot = {k: new_fold[0][k] - new_fold[1][k] for k in new_fold[0]}
            ok &= finite(tuple(tot.values()))
            in_flight = sum(jnp.sum(q.reshape(B, -1), axis=1) for q in queues)
            inj = tot["injected"]
            resid = inj - (tot["csw_down_served"] + tot["drops"]
                           + tot["fault_drops"] + in_flight.astype(inj.dtype))
            ok &= jnp.abs(resid) <= tol * jnp.maximum(inj, 1.0)
            # flow-conservation identity (exactly 0 residual at
            # flow_mode=0, where every term is 0): started == completed +
            # evicted + in-table; in-table counts the live usable slots of
            # the end-of-chunk flow table
            in_table = jnp.sum(
                (out.ft_rem > 0.0)
                & (jnp.arange(C.FLOW_TABLE_SLOTS)[None, None, :]
                   < scen.flow_cap[:, None, None]), axis=(1, 2))
            started = tot["flows_started"]
            fresid = started - (tot["flows_completed"] + tot["flows_evicted"]
                                + in_table.astype(started.dtype))
            ok &= jnp.abs(fresid) <= tol * jnp.maximum(started, 1.0)
        else:
            # host-fold path: the running totals are host-side; guard the
            # chunk's own accumulators for finiteness only
            ok &= finite(tuple(out.acc.values()))
        guard = jnp.where((guard < 0) & ~ok, chunk_idx, guard)
    return out, new_fold, guard


@functools.lru_cache(maxsize=None)
def _sweep_runner(mesh=None):
    """The jitted chunk program. With a ``mesh`` (the scenario-axis
    layout of ``_scen_sharding``) it is a ``shard_map`` of the same
    program over the mesh's ``scen`` axis: every device steps its own
    rows, and the replicated operands (live mask, chunk index, tol)
    reach all of them. Scenarios are independent lanes, so the sharded
    program needs no collective."""
    # accelerators donate the carry so each chunk updates it in place;
    # the CPU keeps it (the artifact audit's CPU budgets are measured
    # so, and its forced-donation probe checks the aliasing there)
    kw = {} if jax.default_backend() == "cpu" \
        else {"donate_argnames": ("state", "fold")}
    impl = _sweep_chunk_impl
    if mesh is not None:
        from jax.sharding import PartitionSpec
        lane, rep = PartitionSpec("scen"), PartitionSpec()

        @functools.wraps(_sweep_chunk_impl)
        def impl(site, scen, state, length, live, fold, guard=None,
                 chunk_idx=None, tol=None, validate=False):
            def body(scen, state, live, fold, guard, chunk_idx, tol):
                return _sweep_chunk_impl(site, scen, state, length, live,
                                         fold, guard, chunk_idx, tol,
                                         validate)
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(lane, lane, rep, lane, lane, rep, rep),
                out_specs=lane, check_vma=False)(
                    scen, state, live, fold, guard, chunk_idx, tol)
    return jax.jit(impl, static_argnames=("site", "length", "validate"),
                   **kw)


def _runner_for(scen):
    """The chunk program for operands laid out as ``scen`` is: the
    shard_mapped one when the scenario axis is spread over a mesh of
    several devices, else the plain one."""
    from jax.sharding import NamedSharding
    sh = getattr(jax.tree.leaves(scen)[0], "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
        return _sweep_runner(sh.mesh)
    return _sweep_runner()


@functools.lru_cache(maxsize=None)
def _scen_sharding():
    """One ``NamedSharding`` over the scenario batch axis for all local
    devices (cached: pjit executable reuse keys on sharding equality)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()), ("scen",))
    return NamedSharding(mesh, PartitionSpec("scen"))


@dataclass
class _PendingSweep:
    """A dispatched-but-not-fetched sweep: every chunk program is
    enqueued on device; the only host synchronization left is the fold
    fetch in ``_finish_sweep`` (one transfer)."""
    batch: ScenarioBatch
    n_ticks: int
    fold: tuple | None       # device (sum, comp) trees (fold="device")
    acc64: dict | None       # host float64 accumulators (fold="host")
    state: SimState          # final device state (maybe padded/sharded)
    n_real: int              # batch rows before devices-multiple padding
    guard: object = None     # (B,) int32 first-bad-chunk (validate=True)


def _prepare_sweep_args(batch: ScenarioBatch, *, fold: str = "device",
                        shard: bool | None = None, validate: bool = False,
                        validate_tol: float | None = None):
    """Build the chunk-program operands for a batch: hull-shaped
    per-scenario state, the device (sum, comp) Kahan fold buffer and
    the optional validate guard — padded to a devices multiple and
    placed on the scenario-axis sharding when the sharded path is
    eligible.

    Shared seam: ``_start_sweep`` dispatches exactly these operands
    through ``_sweep_runner()``, and the artifact auditor
    (repro.analysis.artifact) AOT-lowers the runner on exactly these
    operands — so the audited HLO is the HLO the sweep engine runs, not
    a re-derived lookalike. Returns ``(scen, state, dev_fold, guard,
    tol)``.
    """
    hull = batch.hull
    n_real = len(batch)
    scen = batch.scen
    # one fused key build for the whole batch (vectorized; the old code
    # was an O(batch) host loop of per-seed jax.random.PRNGKey device
    # calls), matching PRNGKey's own canonicalization in BOTH x64
    # modes: with x64 the seed is an int64 and the key keeps the high
    # word; without it any Python int truncates to its low 32 bits
    # (-1 -> 4294967295, 2**32+5 -> 5; a bare uint32 cast would raise)
    if jax.dtypes.canonicalize_dtype(np.int64) == jnp.int64:
        seeds = jnp.asarray(batch.seeds, jnp.int64)
    else:
        seeds = jnp.asarray([s & 0xFFFFFFFF for s in batch.seeds],
                            jnp.uint32)

    sharding = None
    if _should_shard(n_real, shard):
        n_dev = jax.local_device_count()
        sharding = _scen_sharding()
        # pad the batch to a devices-multiple with copies of scenario 0:
        # scenarios are independent vmap lanes, so pad rows are bit-inert
        # for every real row and simply dropped before finalization
        pad = (-n_real) % n_dev
        if pad:
            def _pad0(x):
                return jnp.concatenate(
                    [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])
            scen = jax.tree.map(_pad0, scen)
            seeds = _pad0(seeds)

    keys = jax.vmap(jax.random.PRNGKey)(seeds)
    state = jax.vmap(lambda sc, k: _init_state(hull, sc, k))(scen, keys)

    dev_fold = None
    if fold == "device":
        zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, _fold_dtype()),
                             state.acc)
        dev_fold = (zeros, jax.tree.map(jnp.zeros_like, zeros))
    guard = tol = None
    if validate:
        guard = jnp.full((int(seeds.shape[0]),), -1, jnp.int32)
        tol = jnp.asarray(C.VALIDATE_CONS_REL_TOL if validate_tol is None
                          else validate_tol, jnp.float32)
    if sharding is not None:
        scen = jax.device_put(scen, sharding)
        state = jax.device_put(state, sharding)
        if dev_fold is not None:
            dev_fold = jax.device_put(dev_fold, sharding)
        if guard is not None:
            guard = jax.device_put(guard, sharding)
    return scen, state, dev_fold, guard, tol


def _dispatch_chunks(batch: ScenarioBatch, scen: Scenario, state: SimState,
                     dev_fold, guard, tol, *, n_ticks: int, chunk: int,
                     fold: str, validate: bool, n_real: int,
                     start_chunk: int = 0,
                     checkpoint: "CheckpointSpec | None" = None,
                     plan_meta: dict | None = None) -> _PendingSweep:
    """THE chunk loop, shared by ``_start_sweep`` (fresh runs, from
    chunk 0) and ``resume_sweep`` (restored runs, from the checkpoint's
    chunk index) so a resumed run replays byte-for-byte the same
    dispatch sequence a fresh run would have executed from that
    boundary. ``chunk`` is the EFFECTIVE chunk length
    (``max(1, min(chunk_ticks, n_ticks))``) — a checkpoint records it
    and resume reuses it, so the live-tick masks line up exactly.

    Checkpointing (``checkpoint`` set; device fold only) snapshots the
    full carry at every ``every_chunks`` boundary. The carry is FETCHED
    at its boundary ``ci``, before chunk ``ci`` is dispatched: that
    dispatch donates the carry's buffers on an accelerator, so only the
    host copy outlives it. The file WRITE is deferred by one chunk, so
    the device runs chunk ``ci`` while the host serializes and writes.
    The final boundary is never snapshotted (the run is finished, not
    resumable, there).
    """
    global HOST_TRANSFER_COUNT
    runner = _runner_for(scen)
    hull = batch.hull
    acc64 = None
    done = start_chunk * chunk
    ci = start_chunk
    pending_write = None
    # the bucket is known here only when a checkpoint records the plan
    bucket = {} if plan_meta is None else {"bucket": plan_meta["bucket"]}
    with TraceAnnotation("lcdc.dispatch", **bucket):
        while done < n_ticks:
            if CHUNK_HOOK is not None:
                CHUNK_HOOK(ci)
            with TraceAnnotation("lcdc.chunk", chunk=ci):
                live = jnp.arange(chunk) < (n_ticks - done)
                state, dev_fold, guard = runner(
                    hull, scen, state, chunk, live, dev_fold, guard,
                    jnp.asarray(ci, jnp.int32), tol, validate)
            ci += 1
            if fold == "host":
                # legacy path: fold this chunk's accumulators into
                # float64 on the host and zero them on device — one
                # blocking transfer per chunk
                chunk_acc = jax.device_get(state.acc)
                HOST_TRANSFER_COUNT += 1
                if acc64 is None:
                    acc64 = {k: np.zeros(np.shape(v), np.float64)
                             for k, v in chunk_acc.items()}
                for k, v in chunk_acc.items():
                    acc64[k] += np.asarray(v, np.float64)
                state = state._replace(
                    acc=jax.tree.map(jnp.zeros_like, state.acc))
            done += chunk
            if pending_write is not None:
                pending_write()
                pending_write = None
            if (checkpoint is not None and done < n_ticks
                    and ci % checkpoint.every_chunks == 0):
                pending_write = _snapshot_sweep(
                    checkpoint, batch, ci, state, dev_fold, guard,
                    n_ticks=n_ticks, chunk=chunk, validate=validate,
                    tol=tol, n_real=n_real, plan_meta=plan_meta)
    return _PendingSweep(batch=batch, n_ticks=n_ticks, fold=dev_fold,
                         acc64=acc64, state=state, n_real=n_real,
                         guard=guard)


def _start_sweep(batch: ScenarioBatch, n_ticks: int, *,
                 chunk_ticks: int = CHUNK_TICKS, fold: str = "device",
                 shard: bool | None = None, validate: bool = False,
                 validate_tol: float | None = None,
                 checkpoint: "CheckpointSpec | None" = None,
                 plan_meta: dict | None = None) -> _PendingSweep:
    """Dispatch a sweep's chunk programs without fetching results.

    With ``fold="device"`` (default) this returns as soon as the last
    chunk is ENQUEUED — jax dispatch is asynchronous, so the caller can
    trace/compile the next bucket while this one executes. The legacy
    ``fold="host"`` path synchronizes at every chunk boundary (the
    pre-PR-5 behaviour, kept for parity pinning).

    ``checkpoint`` (a :class:`CheckpointSpec`) snapshots the full
    per-scenario carry at the spec's chunk cadence — device fold only:
    the snapshot IS the device fold buffer plus the SimState carry, and
    the host path already synchronizes per chunk, so checkpointing it
    would pin a second fetch discipline for no benefit.
    """
    if fold not in ("device", "host"):
        raise ValueError(f"fold must be 'device' or 'host', got {fold!r}")
    if n_ticks < 1:
        raise ValueError(f"n_ticks must be >= 1, got {n_ticks}")
    if checkpoint is not None and fold != "device":
        raise ValueError(
            "checkpointing requires the device-resident fold "
            f"(fold='device'); got fold={fold!r}")
    n_real = len(batch)
    with TraceAnnotation("lcdc.init", rows=n_real):
        scen, state, dev_fold, guard, tol = _prepare_sweep_args(
            batch, fold=fold, shard=shard, validate=validate,
            validate_tol=validate_tol)
    return _dispatch_chunks(
        batch, scen, state, dev_fold, guard, tol, n_ticks=n_ticks,
        chunk=max(1, min(chunk_ticks, n_ticks)), fold=fold,
        validate=validate, n_real=n_real, checkpoint=checkpoint,
        plan_meta=plan_meta)


def _finish_sweep(p: _PendingSweep, return_state: bool = False):
    """Fetch a dispatched sweep's fold buffer (the run's single host
    transfer on the device-fold path) and finalize per-scenario
    metrics. A ``validate=True`` sweep whose in-program guards tripped
    raises ``SweepValidationError`` here (the guard rides the same
    transfer as the fold, so the one-transfer contract holds)."""
    global HOST_TRANSFER_COUNT
    guard_h = None
    if p.fold is not None:
        with TraceAnnotation("lcdc.fetch"):
            if p.guard is not None:
                (fsum, fcomp), guard_h = jax.device_get((p.fold, p.guard))
            else:
                fsum, fcomp = jax.device_get(p.fold)
        HOST_TRANSFER_COUNT += 1
        # Kahan: sum carries the running total, comp the rounding error
        # still to subtract; apply the residual in float64 on the host
        acc64 = {k: np.asarray(fsum[k], np.float64)
                 - np.asarray(fcomp[k], np.float64) for k in fsum}
    else:
        acc64 = p.acc64
        if p.guard is not None:
            with TraceAnnotation("lcdc.fetch"):
                guard_h = jax.device_get(p.guard)
            HOST_TRANSFER_COUNT += 1
    batch = p.batch
    if guard_h is not None:
        bad = [i for i in range(p.n_real) if int(guard_h[i]) >= 0]
        if bad:
            raise SweepValidationError(
                [batch.labels[i] for i in bad],
                min(int(guard_h[i]) for i in bad))
    with TraceAnnotation("lcdc.finalize", rows=len(batch)):
        res = [
            _finalize({k: v[i] for k, v in acc64.items()}, batch.sites[i],
                      p.n_ticks, batch.gating[i], batch.names[i],
                      batch.labels[i])
            for i in range(len(batch))
        ]
    if return_state:
        state = jax.device_get(p.state)
        # drop devices-multiple pad rows (copies of scenario 0)
        state = jax.tree.map(lambda x: x[:p.n_real], state)
        return res, state
    return res


def _snapshot_sweep(spec: CheckpointSpec, batch: ScenarioBatch,
                    ci: int, state: SimState, dev_fold, guard, *,
                    n_ticks: int, chunk: int, validate: bool, tol,
                    n_real: int, plan_meta: dict | None = None):
    """Fetch one checkpoint of a running sweep's full carry now, and
    return the zero-argument function that writes it (and returns its
    path), so the caller can overlap the write with the next chunk.

    THE registered checkpoint fetch (an RL003 blessed transfer): ONE
    explicit ``jax.device_get`` of the whole carry — every SimState
    leaf, the device Kahan fold ``(sum, comp)`` buffers, the validate
    guard, and the scenario batch — per cadence boundary, counted by
    ``HOST_TRANSFER_COUNT`` (so a checkpointed run's pin is exactly
    ``1 + n_checkpoints``). Devices-multiple pad rows (copies of
    scenario 0, bit-inert) are stripped before writing; resume re-pads
    for whatever device layout it finds, which is exact because a pad
    row is a FULL copy of row 0 (same scenario, same seed, same carry)
    and scenarios are independent vmap lanes.
    """
    global HOST_TRANSFER_COUNT
    with TraceAnnotation("lcdc.snapshot", chunk=ci):
        scen_h, state_h, fold_h, guard_h = jax.device_get(
            (batch.scen, state, dev_fold, guard))
    HOST_TRANSFER_COUNT += 1
    state_h = jax.tree.map(lambda x: np.asarray(x)[:n_real], state_h)
    arrays = {}
    for name, leaf in zip(Scenario._fields, scen_h):
        arrays[f"scen/{name}"] = np.asarray(leaf)
    for path, leaf in jax.tree_util.tree_flatten_with_path(state_h)[0]:
        arrays["state" + jax.tree_util.keystr(path)] = np.asarray(leaf)
    fsum, fcomp = fold_h
    for k, v in fsum.items():
        arrays[f"fold_sum/{k}"] = np.asarray(v)[:n_real]
    for k, v in fcomp.items():
        arrays[f"fold_comp/{k}"] = np.asarray(v)[:n_real]
    if guard_h is not None:
        arrays["guard"] = np.asarray(guard_h)[:n_real]
    meta = {
        "sim_schema": SIM_SCHEMA_VERSION,
        "fault_knobs": list(FAULT_KNOBS),
        "flow_knobs": list(FLOW_KNOBS),
        "scenario_fields": list(Scenario._fields),
        # the fold dtype pins the JAX_ENABLE_X64 mode: float64 iff x64
        "fold_dtype": jnp.dtype(_fold_dtype()).name,
        "n_ticks": int(n_ticks), "chunk_ticks": int(chunk),
        "chunk_index": int(ci), "n_real": int(n_real),
        "validate": bool(validate),
        "validate_tol": float(tol) if tol is not None else None,
        "hull": dataclasses.asdict(batch.hull),
        "sites": [dataclasses.asdict(s) for s in batch.sites],
        "names": list(batch.names), "labels": list(batch.labels),
        "gating": [bool(g) for g in batch.gating],
        "seeds": [int(s) for s in batch.seeds],
        "plan": plan_meta, "tag": spec.tag,
    }

    def write():
        with TraceAnnotation("lcdc.snapshot", chunk=ci):
            path = _ckpt.write_checkpoint(spec.path_for(ci), meta, arrays)
            _ckpt.prune(spec)
        return path
    return write


def resume_sweep(path, *, return_state: bool = False,
                 shard: bool | None = None,
                 checkpoint: "CheckpointSpec | None" = None):
    """Restart an interrupted sweep from a checkpoint file and run it
    to completion — BIT-identically to the uninterrupted run.

    The checkpoint carries the full per-scenario carry at a chunk
    boundary plus the run geometry, so the remaining chunks replay
    exactly the dispatch sequence the original run would have executed
    (same effective chunk length, same live-tick masks, same per-tick
    ``fold_in`` PRNG streams — nothing about the randomness depends on
    wall-clock history). Works across device layouts: the saved rows
    are re-padded/re-sharded for THIS process's devices (pad rows are
    bit-inert copies of row 0), so a run checkpointed on one device may
    resume on four, and vice versa. The x64 mode, however, must match:
    every restored dtype (fold buffers above all) pins it, and a
    mismatch fails fast.

    Raises :class:`CheckpointError` (reason naming the first mismatch:
    "format"/"checksum" from the file layer, "sim_schema",
    "fingerprint", "scenario_fields", "x64_mode", "state_schema" from
    the engine-compatibility checks) rather than resuming from a
    checkpoint this engine cannot reproduce. Pass ``checkpoint`` (a
    :class:`CheckpointSpec`) to KEEP checkpointing the resumed run at
    the same absolute chunk cadence.
    """
    meta, arrays = _ckpt.read_checkpoint(path)

    def reject(reason, detail):
        raise CheckpointError(reason, f"{path}: {detail}")

    if meta.get("sim_schema") != SIM_SCHEMA_VERSION:
        reject("sim_schema",
               f"written at SIM_SCHEMA_VERSION={meta.get('sim_schema')!r}"
               f", this engine is {SIM_SCHEMA_VERSION}")
    if meta.get("fault_knobs") != list(FAULT_KNOBS) \
            or meta.get("flow_knobs") != list(FLOW_KNOBS):
        reject("fingerprint",
               f"fault/flow knob inventory {meta.get('fault_knobs')!r}/"
               f"{meta.get('flow_knobs')!r} != this engine's "
               f"{list(FAULT_KNOBS)!r}/{list(FLOW_KNOBS)!r}")
    if meta.get("scenario_fields") != list(Scenario._fields):
        reject("scenario_fields",
               f"scenario leaves {meta.get('scenario_fields')!r} != "
               f"this engine's {list(Scenario._fields)!r}")
    fold_dtype = jnp.dtype(_fold_dtype()).name
    if meta.get("fold_dtype") != fold_dtype:
        reject("x64_mode",
               f"written with fold dtype {meta.get('fold_dtype')!r} "
               f"(JAX_ENABLE_X64={meta.get('fold_dtype') == 'float64'}),"
               f" this process folds in {fold_dtype!r}")
    missing_scen = [f for f in Scenario._fields
                    if f"scen/{f}" not in arrays]
    if missing_scen:
        reject("scenario_fields",
               f"scenario leaf arrays missing: {missing_scen}")

    hull = FBSite(**meta["hull"])
    scen = Scenario(**{f: jnp.asarray(arrays[f"scen/{f}"])
                       for f in Scenario._fields})
    batch = ScenarioBatch(
        scen=scen, hull=hull,
        sites=tuple(FBSite(**d) for d in meta["sites"]),
        names=tuple(meta["names"]), labels=tuple(meta["labels"]),
        gating=tuple(bool(g) for g in meta["gating"]),
        seeds=tuple(int(s) for s in meta["seeds"]))
    n_real = int(meta["n_real"])

    # rebuild the state pytree: shape/dtype template via eval_shape (no
    # compute), then place the saved leaves into it — any drift in the
    # carry inventory (new/renamed/re-shaped SimState leaves, an x64
    # dtype flip the fold check missed) is a structured rejection here
    if jax.dtypes.canonicalize_dtype(np.int64) == jnp.int64:
        seeds = jnp.asarray(batch.seeds, jnp.int64)
    else:
        seeds = jnp.asarray([s & 0xFFFFFFFF for s in batch.seeds],
                            jnp.uint32)
    tmpl = jax.eval_shape(
        jax.vmap(lambda sc, k: _init_state(hull, sc, k)),
        scen, jax.eval_shape(jax.vmap(jax.random.PRNGKey), seeds))
    tmpl_leaves, treedef = jax.tree_util.tree_flatten_with_path(tmpl)
    state_leaves = []
    for p_, leaf in tmpl_leaves:
        name = "state" + jax.tree_util.keystr(p_)
        if name not in arrays:
            reject("state_schema", f"carry array {name!r} missing")
        a = arrays[name]
        if tuple(a.shape) != tuple(leaf.shape) \
                or np.dtype(a.dtype) != np.dtype(leaf.dtype):
            reject("state_schema",
                   f"carry array {name!r} is {a.dtype}{a.shape}, this "
                   f"engine expects {leaf.dtype}{tuple(leaf.shape)}")
        state_leaves.append(jnp.asarray(a))
    state = jax.tree_util.tree_unflatten(treedef, state_leaves)

    fdt = _fold_dtype()
    fsum, fcomp = {}, {}
    for k in tmpl.acc:
        for d, store in (("fold_sum", fsum), ("fold_comp", fcomp)):
            name = f"{d}/{k}"
            if name not in arrays:
                reject("state_schema", f"fold buffer {name!r} missing")
            store[k] = jnp.asarray(arrays[name], fdt)
    dev_fold = (fsum, fcomp)

    validate = bool(meta["validate"])
    guard = tol = None
    if validate:
        if "guard" not in arrays:
            reject("state_schema", "validate guard array missing")
        guard = jnp.asarray(arrays["guard"], jnp.int32)
        tol = jnp.asarray(meta["validate_tol"], jnp.float32)

    # re-pad + re-shard for THIS process's device layout (mirrors
    # _prepare_sweep_args; pad rows are full copies of row 0, bit-inert)
    if _should_shard(n_real, shard):
        n_dev = jax.local_device_count()
        sharding = _scen_sharding()
        pad = (-n_real) % n_dev
        if pad:
            def _pad0(x):
                return jnp.concatenate(
                    [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])
            scen = jax.tree.map(_pad0, scen)
            state = jax.tree.map(_pad0, state)
            dev_fold = jax.tree.map(_pad0, dev_fold)
            if guard is not None:
                guard = _pad0(guard)
        scen = jax.device_put(scen, sharding)
        state = jax.device_put(state, sharding)
        dev_fold = jax.device_put(dev_fold, sharding)
        if guard is not None:
            guard = jax.device_put(guard, sharding)

    pend = _dispatch_chunks(
        batch, scen, state, dev_fold, guard, tol,
        n_ticks=int(meta["n_ticks"]), chunk=int(meta["chunk_ticks"]),
        fold="device", validate=validate, n_real=n_real,
        start_chunk=int(meta["chunk_index"]), checkpoint=checkpoint,
        plan_meta=meta.get("plan"))
    return _finish_sweep(pend, return_state=return_state)


def run_sweep(batch: ScenarioBatch, n_ticks: int, *,
              chunk_ticks: int = CHUNK_TICKS, return_state: bool = False,
              fold: str = "device", shard: bool | None = None,
              validate: bool = False,
              validate_tol: float | None = None,
              checkpoint: "CheckpointSpec | None" = None):
    """Run every scenario of ``batch`` for n_ticks us in one vmapped,
    chunk-scanned program; returns one metrics dict per scenario (same
    schema as ``run_sim``, plus the scenario ``label``). With
    ``return_state=True`` also returns the final device state (leaves
    batched over scenarios) — e.g. for conservation audits of in-flight
    packets.

    Compiles once per (hull, batch size, chunk length) and reuses the
    executable across calls; a remainder tail runs the same fixed-length
    chunk under a live-tick mask, so it never adds a trace (see module
    docstring).

    ``fold="device"`` (default) keeps the accumulator fold on device
    and performs exactly one host transfer per run; ``fold="host"`` is
    the legacy per-chunk host fold (parity reference). ``shard=None``
    auto-shards the scenario axis across all local devices when more
    than one is visible; ``shard=False`` forces single-device layout.

    ``validate=True`` compiles in-program guards into every chunk: a
    per-scenario finite-value check over the in-flight queues and
    running totals, and (device-fold path) the conservation identity
    injected == delivered + drops + fault_drops + in-flight within
    ``validate_tol`` (relative; default ``C.VALIDATE_CONS_REL_TOL``).
    A tripped guard raises ``SweepValidationError`` at fetch time,
    naming the failing scenario labels and the FIRST failing chunk
    index — localization without any extra host synchronization (the
    guard is a (B,) int32 riding the fold transfer). Validation changes
    the compiled program (one extra trace per hull/shape) but never the
    simulated dynamics: metric values are identical with it on or off.

    ``checkpoint`` (a :class:`CheckpointSpec`; device fold only)
    snapshots the full carry at the spec's chunk cadence so an
    interrupted run restarts from ``resume_sweep(path)`` bit-identically
    (see the durability contract in ROADMAP.md). Checkpointing only
    OBSERVES the run — the dispatched programs and their results are
    bit-identical with it on or off; each snapshot adds one blessed
    host transfer (``HOST_TRANSFER_COUNT`` becomes
    ``1 + n_checkpoints``).
    """
    return _finish_sweep(
        _start_sweep(batch, n_ticks, chunk_ticks=chunk_ticks, fold=fold,
                     shard=shard, validate=validate,
                     validate_tol=validate_tol, checkpoint=checkpoint),
        return_state=return_state)


def run_sweep_planned(runs: Sequence[tuple[SimParams, int]], n_ticks: int,
                      *, max_compiles: int = 4,
                      chunk_ticks: int = CHUNK_TICKS,
                      return_plan: bool = False, fold: str = "device",
                      shard: bool | None = None, pipeline: bool = True,
                      validate: bool = False,
                      validate_tol: float | None = None,
                      retry: "BucketRetryPolicy | None" = None,
                      checkpoint: "CheckpointSpec | None" = None):
    """Run a heterogeneous-site sweep through the hull-bucketing planner
    (core/planner.py): the (SimParams, seed) pairs are partitioned into
    <= ``max_compiles`` hull buckets by estimated padded cost, each
    bucket runs as its own ``make_multi_site_batch`` + sweep dispatch
    (one trace per (hull, batch-shape, chunk), exactly as before), and
    the per-scenario metric dicts come back in CALLER order, each
    annotated with its ``plan_bucket`` index and ``plan_hull`` tag.

    With ``pipeline=True`` (default) the buckets are executed as an
    async pipeline: every bucket's chunk programs are dispatched first,
    in the planner's ``dispatch_order`` (largest padded cost first, so
    tracing/compiling bucket k+1 overlaps device execution of bucket
    k), then results are fetched — one blocking transfer per bucket,
    after all device work is enqueued. Note the pipeline keeps every
    bucket's state + fold buffers resident at once; ``pipeline=False``
    runs buckets strictly serially (dispatch+fetch per bucket, caller
    order, one bucket resident at a time — the low-memory mode for
    accelerators) and is bit-identical: same programs, same inputs.

    With ``return_plan=True`` also returns the plan's padding-waste
    report (``SweepPlan.report()``: per-bucket waste fractions, the
    total padded cost, and the savings vs the single-hull K=1 path).

    ``max_compiles=1`` is the degenerate single-hull case — identical
    to ``run_sweep(make_multi_site_batch(runs), ...)`` (pinned by
    tests/test_planner.py).

    Bucket failures are ISOLATED: an exception while dispatching or
    fetching one bucket (a poisoned scenario tripping ``validate``
    guards, a compile failure, an OOM) never takes down the other
    buckets. The failed bucket is retried per the ``retry`` policy
    (:class:`BucketRetryPolicy`; default = the original contract, ONE
    immediate retry, no deadline), each retry strictly serial on the
    legacy ``fold="host"`` path (the most conservative execution mode:
    per-chunk synchronization, no device-resident fold buffer), with
    the policy's exponential backoff between attempts and its
    ``deadline_s`` bounding each bucket's cumulative wall-clock time —
    once a bucket has spent its deadline, remaining retries are
    abandoned (finished work is never discarded). On exhaustion that
    bucket's runs come back as structured error entries — ``{"label",
    "plan_bucket", "plan_hull", "error": {"type", "message", "stage",
    "retried"}}`` with ``stage`` the phase of the ORIGINAL failure
    ("dispatch" or "fetch") and ``message`` the final attempt's — in
    caller order alongside the successful buckets' metric dicts, so one
    bad scenario degrades exactly its own bucket and nothing else. All
    remaining pending buckets are drained even when a fetch raises, so
    no device buffers are left dangling.

    ``checkpoint`` checkpoints every bucket under a per-bucket tag
    (``<tag>-<plan.bucket_tag(k)>``, collision-free across plans), and
    guarantees graceful partial-result degradation: an exhausted bucket
    additionally carries ``error["checkpoint"]`` — the path of its
    newest cadence snapshot, or a freshly written chunk-0 snapshot of
    its initial carry when it never reached a boundary — so a failed
    planned sweep always leaves every other bucket's results PLUS a
    ``resume_sweep``-able artifact for the failed one (None only if
    even the salvage write failed).
    """
    # local import: the planner is deliberately jax-free and usable
    # standalone; only the execution path needs it
    from repro.core import planner

    if checkpoint is not None and fold != "device":
        raise ValueError(
            "checkpointing requires the device-resident fold "
            f"(fold='device'); got fold={fold!r}")
    runs = list(runs)
    with TraceAnnotation("lcdc.sweep", rows=len(runs), ticks=n_ticks):
        with TraceAnnotation("lcdc.plan"):
            plan = planner.plan_sites([p.site for p, _ in runs],
                                      max_compiles)
        order = plan.dispatch_order if pipeline \
            else tuple(range(len(plan.buckets)))
        policy = retry if retry is not None else BucketRetryPolicy()
        pending: dict[int, _PendingSweep] = {}
        fetched: dict[int, list] = {}
        errors: dict[int, dict] = {}
        elapsed: dict[int, float] = {}

        def hook(k, phase):
            if BUCKET_FAIL_HOOK is not None:
                BUCKET_FAIL_HOOK(k, phase)

        def timed(k, fn):
            # per-bucket wall-clock ledger: cumulative across the bucket's
            # dispatch, fetch and retry attempts; the policy's deadline_s
            # is checked against it before each retry
            t0 = time.monotonic()
            try:
                return fn()
            finally:
                elapsed[k] = elapsed.get(k, 0.0) + (time.monotonic() - t0)

        def bucket_spec(k):
            if checkpoint is None:
                return None
            return dataclasses.replace(
                checkpoint, tag=f"{checkpoint.tag}-{plan.bucket_tag(k)}")

        def bucket_plan_meta(k):
            return {"fingerprint": plan.fingerprint, "bucket": k,
                    "hull": full_site_tag(plan.buckets[k].hull)}

        def salvage_checkpoint(k, spec_k):
            # a resumable artifact for the exhausted bucket: its newest
            # cadence snapshot if it reached a boundary, else a fresh
            # chunk-0 snapshot of its INITIAL carry (resuming that replays
            # the whole bucket). Best-effort: None if even this fails.
            existing = _ckpt.latest_checkpoint(spec_k.directory, spec_k.tag)
            if existing is not None:
                return str(existing)
            try:
                batch = make_multi_site_batch(
                    [runs[i] for i in plan.buckets[k].indices])
                scen, state, dev_fold, guard, tol = _prepare_sweep_args(
                    batch, fold="device", shard=shard, validate=validate,
                    validate_tol=validate_tol)
                return str(_snapshot_sweep(
                    spec_k, batch, 0, state, dev_fold, guard,
                    n_ticks=n_ticks,
                    chunk=max(1, min(chunk_ticks, n_ticks)),
                    validate=validate, tol=tol, n_real=len(batch),
                    plan_meta=bucket_plan_meta(k))())
            except Exception:                  # noqa: BLE001 — best effort
                return None

        def retry_bucket(k, stage, exc):
            # bounded retries on the most conservative path; on exhaustion
            # record a structured error for the bucket (stage = the
            # ORIGINAL failure's phase, message = the final failure's)
            last = exc
            retried = False
            for attempt in range(1, policy.max_retries + 1):
                if (policy.deadline_s is not None
                        and elapsed.get(k, 0.0) >= policy.deadline_s):
                    break
                delay = policy.backoff_s(attempt)
                if delay > 0.0:
                    RETRY_SLEEP(delay)
                retried = True

                def one_retry():
                    with TraceAnnotation("lcdc.retry", bucket=k,
                                         attempt=attempt):
                        hook(k, "retry")
                        with TraceAnnotation("lcdc.batch", bucket=k):
                            batch = make_multi_site_batch(
                                [runs[i] for i in plan.buckets[k].indices])
                        return _finish_sweep(_start_sweep(
                            batch, n_ticks, chunk_ticks=chunk_ticks,
                            fold="host", shard=shard, validate=validate,
                            validate_tol=validate_tol))

                try:
                    fetched[k] = timed(k, one_retry)
                    return
                except Exception as exc2:      # noqa: BLE001 — isolation
                    last = exc2
            errors[k] = {"type": type(last).__name__, "message": str(last),
                         "stage": stage, "retried": retried}
            spec_k = bucket_spec(k)
            if spec_k is not None:
                errors[k]["checkpoint"] = salvage_checkpoint(k, spec_k)

        try:
            for k in order:
                bucket = plan.buckets[k]

                def dispatch(k=k, bucket=bucket):
                    hook(k, "dispatch")
                    with TraceAnnotation("lcdc.batch", bucket=k):
                        batch = make_multi_site_batch(
                            [runs[i] for i in bucket.indices])
                    return _start_sweep(
                        batch, n_ticks, chunk_ticks=chunk_ticks, fold=fold,
                        shard=shard, validate=validate,
                        validate_tol=validate_tol,
                        checkpoint=bucket_spec(k),
                        plan_meta=bucket_plan_meta(k)
                        if checkpoint is not None else None)

                try:
                    ps = timed(k, dispatch)
                except Exception as exc:       # noqa: BLE001 — isolation
                    retry_bucket(k, "dispatch", exc)
                    continue
                if pipeline:
                    pending[k] = ps
                else:
                    # strictly serial: block on this bucket before the
                    # next, and drop ps so its device state/fold buffers
                    # free now — this IS the advertised one-bucket-resident
                    # memory mode
                    try:
                        def fetch(ps=ps, k=k):
                            hook(k, "fetch")
                            return _finish_sweep(ps)
                        fetched[k] = timed(k, fetch)
                    except Exception as exc:   # noqa: BLE001 — isolation
                        retry_bucket(k, "fetch", exc)
            for k in (k for k in order if k in pending):
                try:
                    def fetch(k=k):
                        hook(k, "fetch")
                        return _finish_sweep(pending.pop(k))
                    fetched[k] = timed(k, fetch)
                except Exception as exc:       # noqa: BLE001 — isolation
                    retry_bucket(k, "fetch", exc)
        finally:
            # a raising fetch (pre-isolation this propagated) must never
            # leave later buckets' device state/fold buffers referenced
            pending.clear()
        results: list = [None] * len(runs)
        for k, bucket in enumerate(plan.buckets):
            # the FULL tag — the same format the plan report's bucket
            # "hull" field uses, so the two can be joined on it
            hull_tag = full_site_tag(bucket.hull)
            if k in fetched:
                for i, r in zip(bucket.indices, fetched[k]):
                    r["plan_bucket"] = k
                    r["plan_hull"] = hull_tag
                    results[i] = r
            else:
                for i in bucket.indices:
                    p, seed = runs[i]
                    results[i] = {
                        "label": _run_label(p, seed, tag_site=True),
                        "plan_bucket": k, "plan_hull": hull_tag,
                        "error": dict(errors[k]),
                    }
        if return_plan:
            return results, plan.report()
        return results


def _hist_quantile(hist: np.ndarray, q: float,
                   edges: np.ndarray = DELAY_BIN_EDGES_US) -> float:
    """Quantile of a log-binned histogram (default frame:
    DELAY_BIN_EDGES_US; the flow engine passes its FCT / slowdown
    frames), log-linearly interpolated within the crossing bin."""
    total = float(np.sum(hist))
    if total <= 0.0:
        return 0.0
    cdf = np.cumsum(hist) / total
    i = min(int(np.searchsorted(cdf, q)), len(hist) - 1)
    lo_e, hi_e = edges[i], edges[i + 1]
    prev = float(cdf[i - 1]) if i > 0 else 0.0
    frac = (q - prev) / max(float(cdf[i]) - prev, 1e-12)
    frac = min(max(frac, 0.0), 1.0)
    if lo_e <= 0.0:                       # bin 0 is linear [0, MIN)
        return float(hi_e * frac)
    return float(lo_e * (hi_e / lo_e) ** frac)


def _finalize(a: dict, site: FBSite, n_ticks: int, gating_enabled: bool,
              trace: str, label: str | None = None) -> dict:
    """Aggregate accumulators -> the paper's metrics (one scenario).

    ``site`` is the scenario's REAL site (not the batch hull): all link
    populations and power normalizations are the scenario's own.
    """
    s = site
    T = float(n_ticks)

    # ---- latency (Little's law per tier + fixed costs) -----------------
    def wait(backlog, served):
        return float(backlog / max(served, 1e-9))

    inj = max(float(a["injected"]), 1e-9)
    frac_inter = float(a["csw_up_served"]) / inj if inj else 0.0
    mean_wait = (
        wait(a["rsw_backlog"], a["rsw_served"])
        + wait(a["csw_down_backlog"], a["csw_down_served"])
        + frac_inter * (wait(a["csw_up_backlog"], a["csw_up_served"])
                        + wait(a["fc_backlog"], a["fc_served"])))
    ring_frac = float(a["ring_pkts"] + a["fc_ring_pkts"]) / inj
    hops = 4.0 + 2.0 * frac_inter + ring_frac
    mean_latency_us = STACK_US + hops * WIRE_HOP_US + mean_wait

    # ---- delay distribution + attribution (see module docstring) -------
    hist = np.asarray(a["delay_hist"], np.float64)
    wt = max(float(a["delay_wt"]), 1e-9)
    occ = {}
    for tier, n_ports in (("rsw", site.n_racks * site.rsw_uplinks),
                          ("csw", site.n_csw * site.csw_uplinks)):
        n = T * n_ports
        m1 = float(a[f"{tier}_occ_m1"]) / n
        occ[f"{tier}_occ_mean_pkts"] = m1
        occ[f"{tier}_occ_var_pkts"] = max(
            float(a[f"{tier}_occ_m2"]) / n - m1 * m1, 0.0)

    # ---- energy ---------------------------------------------------------
    pw = s.transceiver_power_w()
    rsw_on = float(a["rsw_powered"]) / (T * s.n_rsw_csw_links)
    csw_on = float(a["csw_powered"]) / (T * s.n_csw_fc_links)
    node_on = float(a["node_on"]) / (T * s.n_servers)
    if not gating_enabled:
        node_on = rsw_on = csw_on = 1.0

    # Fig 9 metric: the stage-gated switch-tier transceivers (RSW-CSW and
    # CSW-FC). Stage 1 never gates, so 75% is the ceiling.
    switch_w = pw["rsw_csw"] * rsw_on + pw["csw_fc"] * csw_on
    switch_total = pw["rsw_csw"] + pw["csw_fc"]
    switch_savings = 1.0 - switch_w / switch_total

    # All transceivers (feeds the Fig 11 whole-DC estimate): server links
    # gated by the node-level OS mechanism + switch tiers + always-on rings.
    power_w = pw["server"] * node_on + switch_w + pw["ring"]
    total_w = s.total_transceiver_power_w()

    return {
        "trace": trace,
        "label": label or trace,
        "gating": gating_enabled,
        "ticks": n_ticks,
        "mean_latency_us": mean_latency_us,
        "mean_wait_us": float(mean_wait),
        "wait_rsw_us": wait(a["rsw_backlog"], a["rsw_served"]),
        "wait_csw_up_us": wait(a["csw_up_backlog"], a["csw_up_served"]),
        "wait_csw_down_us": wait(a["csw_down_backlog"],
                                 a["csw_down_served"]),
        "wait_fc_us": wait(a["fc_backlog"], a["fc_served"]),
        "injected_pkts": float(a["injected"]),
        "delivered_pkts": float(a["csw_down_served"]),
        "drop_frac": float(a["drops"]) / inj,
        # availability under faults: delivered fraction, the fault-drop
        # conservation bin, wake-retry/fallback counts, and the
        # connectivity-loss audit (all exactly 0 with zero fault knobs)
        "delivered_frac": float(a["csw_down_served"]) / inj,
        "fault_drop_frac": float(a["fault_drops"]) / inj,
        "fault_dropped_pkts": float(a["fault_drops"]),
        "wake_retries": float(a["wake_retries"]),
        "forced_wakes": float(a["forced_wakes"]),
        "conn_loss_rack_ticks": float(a["conn_loss_rack_ticks"]),
        "conn_loss_csw_ticks": float(a["conn_loss_csw_ticks"]),
        "conn_loss_ticks": float(a["conn_loss_rack_ticks"]
                                 + a["conn_loss_csw_ticks"]),
        # fraction of gated-link-ticks spent hard-faulted (availability)
        "link_fault_frac": float(a["fault_link_ticks"])
        / (T * (s.n_rsw_csw_links + s.n_csw_fc_links)),
        "ring_frac": ring_frac,
        "rsw_link_on_frac": rsw_on,
        "csw_link_on_frac": csw_on,
        "node_link_on_frac": node_on,
        "switch_energy_savings_frac": float(switch_savings),
        "transceiver_power_w": float(power_w),
        "all_transceiver_savings_frac": float(1.0 - power_w / total_w),
        "half_off_frac": float(a["half_off_ticks"]) / T,
        "on_frac_hist": (a["on_frac_hist"] / T).tolist(),
        "offered_load_pkts_per_tick": inj / T,
        # in-scan delay distribution (normalized; bins in
        # DELAY_BIN_EDGES_US) + percentiles + the attribution split
        "delay_hist": (hist / wt).tolist(),
        "delay_p50_us": _hist_quantile(hist, 0.50),
        "delay_p95_us": _hist_quantile(hist, 0.95),
        "delay_p99_us": _hist_quantile(hist, 0.99),
        "delay_mean_sampled_us": float(a["delay_sum"]) / wt,
        "delay_queue_us": float(a["delay_queue_sum"]) / wt,
        "delay_wake_stall_us": float(a["delay_stall_sum"]) / wt,
        "delay_fault_stall_us": float(a["delay_fault_sum"]) / wt,
        "delay_ring_us": ring_frac * WIRE_HOP_US,
        "delay_frac_inter": float(a["delay_wt_inter"]) / wt,
        "wake_stall_frac": float(a["wake_stall_pkts"]) / wt,
        "fault_stall_frac": float(a["fault_stall_pkts"]) / wt,
        **occ,
        **_finalize_flows(a),
    }


def _finalize_flows(a: dict) -> dict:
    """Flow-engine metrics (all exactly 0 / empty-normalized at
    flow_mode=0, where every flow accumulator is exactly zero):
    per-size-class FCT p50/p99 + slowdown percentiles vs the
    ideal-bandwidth baseline, and the flow-conservation census."""
    fct_hist = np.asarray(a["fct_hist"], np.float64)       # (3, bins)
    slow_hist = np.asarray(a["fct_slow_hist"], np.float64)
    started = float(a["flows_started"])
    completed = float(a["flows_completed"])
    n_done = max(completed, 1e-9)
    out = {
        "flows_started": started,
        "flows_completed": completed,
        "flows_evicted": float(a["flows_evicted"]),
        "flow_evicted_frac": float(a["flows_evicted"])
        / max(started, 1e-9),
        "fct_mean_us": float(a["fct_sum"]) / n_done,
        "fct_slowdown_mean": float(a["fct_slow_sum"]) / n_done,
        # aggregate (all classes) percentiles
        "fct_p50_us": _hist_quantile(fct_hist.sum(0), 0.50,
                                     FCT_BIN_EDGES_US),
        "fct_p99_us": _hist_quantile(fct_hist.sum(0), 0.99,
                                     FCT_BIN_EDGES_US),
        "fct_slowdown_p50": _hist_quantile(slow_hist.sum(0), 0.50,
                                           FCT_SLOWDOWN_BIN_EDGES),
        "fct_slowdown_p99": _hist_quantile(slow_hist.sum(0), 0.99,
                                           FCT_SLOWDOWN_BIN_EDGES),
        # normalized per-class slowdown distributions (rows in
        # FLOW_CLASS_NAMES order, bins in FCT_SLOWDOWN_BIN_EDGES)
        "fct_slow_hist": (slow_hist / n_done).tolist(),
    }
    for c, cname in enumerate(workloads.FLOW_CLASS_NAMES):
        out[f"flows_completed_{cname}"] = float(fct_hist[c].sum())
        out[f"fct_p50_us_{cname}"] = _hist_quantile(
            fct_hist[c], 0.50, FCT_BIN_EDGES_US)
        out[f"fct_p99_us_{cname}"] = _hist_quantile(
            fct_hist[c], 0.99, FCT_BIN_EDGES_US)
        out[f"fct_slowdown_p50_{cname}"] = _hist_quantile(
            slow_hist[c], 0.50, FCT_SLOWDOWN_BIN_EDGES)
        out[f"fct_slowdown_p99_{cname}"] = _hist_quantile(
            slow_hist[c], 0.99, FCT_SLOWDOWN_BIN_EDGES)
    return out


def _sim_program(hull: FBSite, scen: Scenario, n_ticks: int):
    """Build the single-scenario jitted program ``run_sim`` executes.

    A module-level lowering seam: the artifact auditor
    (repro.analysis.artifact) AOT-lowers exactly this program — not a
    re-derived lookalike — so the audited HLO is the HLO run_sim runs.
    ``scen`` leaves are concrete 0-d arrays that close over the step as
    per-scenario constants (the pre-sweep specialization behaviour).
    """
    step = make_sim_step(hull)

    @jax.jit
    def go(state):
        out, _ = jax.lax.scan(lambda st, _: (step(scen, st), None),
                              state, None, length=n_ticks)
        return out

    return go


def run_sim(params: SimParams, n_ticks: int, seed: int = 0) -> dict:
    """Run ONE scenario for n_ticks us; returns aggregate metrics.

    Kept for unit runs and ablations, and deliberately preserves the
    pre-sweep engine's behaviour: the scenario knobs are baked into the
    trace as constants, so every distinct scenario lowers to its own
    jaxpr and pays a fresh specialize-and-compile (no cross-scenario
    cache reuse, no batching, no chunking). Serial loops over scenarios
    therefore scale wall-clock with compile count — use ``run_sweep``
    for sweeps, which traces once for the whole batch.
    """
    batch = make_batch([(params, seed)])
    hull = batch.hull          # == the site's own exact dims
    scen = jax.tree.map(lambda x: x[0], batch.scen)
    state = _init_state(hull, scen, jax.random.PRNGKey(seed))
    go = _sim_program(hull, scen, n_ticks)

    # repro-lint: disable=RL003(single-scenario debug path: one fetch per run_sim call, outside the sweep engine's HOST_TRANSFER_COUNT budget)
    acc = jax.device_get(go(state).acc)
    return _finalize({k: np.asarray(v, np.float64) for k, v in acc.items()},
                     batch.sites[0], n_ticks, batch.gating[0],
                     batch.names[0], batch.labels[0])


def compare_traces(n_ticks: int = 200_000, seed: int = 0,
                   traces=None) -> dict:
    """LC/DC vs always-on across every modeled trace (Figs 8-10), as a
    single batched sweep (one compile, 2x|traces| scenarios)."""
    names = list(traces or TRAFFIC_SPECS)
    runs = []
    for name in names:
        spec = TRAFFIC_SPECS[name]
        runs.append((SimParams(spec=spec, gating_enabled=True), seed))
        runs.append((SimParams(spec=spec, gating_enabled=False), seed))
    res = run_sweep(make_batch(runs), n_ticks)
    out = {}
    for i, name in enumerate(names):
        lc, base = res[2 * i], res[2 * i + 1]
        out[name] = {
            "lcdc": lc, "baseline": base,
            "switch_energy_savings": lc["switch_energy_savings_frac"],
            "all_transceiver_savings": lc["all_transceiver_savings_frac"],
            "latency_penalty":
                lc["mean_latency_us"] / base["mean_latency_us"] - 1.0,
        }
    return out
