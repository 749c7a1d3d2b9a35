"""Multi-host RDCA fabric: Clos topologies, switches, hosts, driver, sweeps.

- topology:  leaf–spine Clos graphs + presets (jet_testbed, incast_fabric)
             and 3-level pod-of-pods fabrics (`make_pod_clos`), with
             per-link up/down state and scheduled failure events
             (`Topology.fail_link` / `flap_link`) — see "Choosing a
             topology" below
- routing:   first-class per-tick path selection (`RoutingConfig`):
             static ECMP / flowlet-weighted ECMP / adaptive
             least-congested / packet spray, with link-failure rerouting
             — see "The routing layer" below
- switch:    output-queued switch with per-traffic-class queues (one
             FIFO + buffer partition + ECN knee + PFC xoff/xon pair per
             TC; pause targets are `(ingress link, tc)` pairs, 802.1Qbb
             style; `SwitchConfig.per_tc=False` restores the legacy
             whole-link pause for comparison baselines)
- hosts:     step-able ReceiverHost (wrapping the shared
             `repro.core.datapath.HostDatapath` — the same QoS admission/
             escape/recycle machine behind run_sim and JetService) and
             DCQCN SenderHost
- messages:  op-granular verbs layer over the fluid byte streams
             (`MessageConfig`: WRITE/SEND, msg size, outstanding-op
             window, go-back-N replay) with deterministic per-message
             completion latency — exact sorted percentiles in the
             scalar driver, a fixed-bucket log histogram with a proven
             relative error bound in the vector engines — see "The
             message layer" below
- faults:    fault-injection + loss-recovery layer (`FaultConfig`):
             per-link stochastic loss/corruption, link flaps
             (`Topology.flap_link`), NIC/host crash--restart, sender
             RTO timers with exponential backoff and IRN-style
             selective retransmit (`MessageConfig.recovery`), plus a
             PFC-deadlock watchdog — see "The fault layer" below
- cc:        pluggable congestion-control zoo (`CcConfig`): DCQCN
             (default, bit-equal to the pre-zoo driver), Timely
             delay-gradient and HPCC utilization controllers,
             selectable per flow and per sweep point — see "Choosing a
             congestion controller" below
- fabric:    scalar multi-host driver -> per-host SimResults + fabric
             metrics (victim goodput, pause fan-out + per-TC pause
             breakdown, incast FCT); `Flow.qos` selects both the
             receiver admission class and the switch queue along the
             route, escape-ladder ECN comes back as CNPs, and NP->RP
             CNP propagation delay is per flow (`Flow.cnp_delay_us`
             falling back to `FabricConfig.cnp_delay_us`); burst-train
             sources via `Flow.on_off_us`
- scenarios: incast-N / all-to-all HPC / storage OLTP-OLAP-backup /
             mixed Jet+DDIO fleet / QoS-mixed storage (LOW bulk incast
             + HIGH on-off OLTP + NORMAL OLAP, per-TC vs per-link
             pause) / pod-scale cross-pod incast, shuffle and PFC-storm
             bundles + fabric_grid / mixed_fleet_grid / qos_mixed_grid
             / pod_incast_grid / pod_storm_grid for building scenario
             grids; the named-grid registry (`GRIDS` / `build_grid`)
             and `chunk_plan` behind the sweep farm
- sweep:     vectorized receiver-datapath grid (jax.vmap + lax.scan over
             stacked single-host fluid state; numpy reference backend)
- vector:    vectorized *fabric* grid — the whole multi-host tick body
             (flows x ports x receivers, with the HostDatapath QoS
             classes as a stacked [G, Q, R] block and a per-flow
             CNP-delay ring) as one vmap+scan program; switch state is
             classed too ([G, Q, P] occupancy/assert/pause via the
             flow->TC one-hot, priority-unrolled drain grants); 3-level
             pod fabrics run a segmented-incidence ("sparse") variant
             of the same program whose cost is linear in flows + ports
- fused:     fused hot-tick stages for the vector engines (strict-
             priority drain grants + QoS receiver admission as single
             water-fill primitives with a Pallas kernel tier), the
             jaxpr op-census profiling hooks behind the bench, and the
             adaptive time-stepping machinery (quiet-stride predicate,
             closed-form macro-tick advance) — see "Engine
             performance" below
- farm:      sweep farm (`run_farm`, `python -m repro.fabric.farm`):
             any scenario grid executed as fixed-shape chunks across
             local jax devices and/or a multiprocess worker pool, with
             versioned run artifacts and resume — see "Running sweeps
             at farm scale" below
- spans:     the farm's host spans (profiler annotation + seconds in
             the manifest record) and transfer counters
- artifacts: versioned run-artifact layer behind the farm
             (`experiments/runs/<run_id>/`: manifest + per-chunk
             result shards + merged table; atomic writes, resume
             contract)
- _scan:     shared lax.scan compile-cost machinery (explicit unroll,
             donated carries, persistent XLA compilation cache)

Which engine advances which datapath backend: the scalar driver steps
real ``HostDatapath`` objects (float64 Python, via ``ReceiverHost``);
``run_sweep`` and ``run_fabric_sweep`` advance the equivalent stacked-
array recurrence (batched-numpy float64 reference / jax float32
vmap+scan), verified against the scalar machine in the test suite.

Choosing an engine
------------------
``run_fabric`` (scalar driver)
    One scenario at a time, Python objects, float64.  The semantic
    reference: returns full per-host :class:`~repro.core.simulator
    .SimResult` (including message latency percentiles) and per-link
    pause breakdowns.  Also the only engine for things that resist
    stacking, e.g. ``cpu_membw_schedule`` callables.  Seconds per point.

``run_sweep`` (datapath sweep)
    Grids over *receiver* ``SimConfig`` knobs with the single-host
    sender model (no switches, no cross-flow coupling).  Cheapest per
    point; use it to map the receiver datapath (DDIO knee, pool sizing,
    DCQCN constants) before involving a fabric.

``run_fabric_sweep`` (fabric sweep)
    Grids over whole scenarios — topology rates, switch config, per-flow
    offered/burst/start, per-receiver knobs — with every flow, port and
    receiver advanced together ([G, F] / [G, P, F] / [G, R] arrays).
    Matches the scalar driver to float32 round-off (float64 exact via
    ``backend="numpy"``) and turns minutes-per-grid into seconds.  Grid
    points must share topology *structure* (same flows/routes/ticks).

Choosing a topology
-------------------
Two construction families, one :class:`~repro.fabric.topology.Topology`
contract (named nodes, per-link rate and up/down schedule, route /
candidate_paths / fail_link / flap_link):

``clos(...)`` and the presets (``jet_testbed``, ``incast_fabric``)
    2-level leaf–spine: hosts ``h{leaf}_{i}``, every leaf wired to
    every spine.  Routes are 3 hops (same-leaf) or 5 hops (cross-leaf,
    one spine choice).  The right size for last-mile receiver studies
    — every dense-engine feature (dynamic routing, CC zoo, message
    layer, fault injection, adaptive dt) is available.

``make_pod_clos(pods, leaves_per_pod, hosts_per_leaf, ...)``
    3-level pod-of-pods Clos: hosts ``p{pod}h{leaf}_{i}``, leaves
    ``p{pod}l{leaf}``, per-pod spines ``p{pod}s{k}``, and a global
    super-spine tier ``ss{k}`` with plane-aligned wiring (pod spine
    ``k`` connects to super-spine ``k``).  Cross-pod routes are 7 hops
    and climb two oversubscription points; tier speeds default to
    100/200/400 Gbps.  ``pods=1`` degenerates to the 2-level fabric.
    Partial wiring is legal: spines may serve a leaf subset, and
    ``Topology.candidate_spines`` / ``route`` skip spines that cannot
    reach both endpoints (raising ``unroutable`` only when *no*
    candidate survives).

Engine support: the scalar driver takes either family.  For vector
sweeps, ``run_fabric_sweep(..., incidence="auto")`` (default) picks the
dense one-hot program for 2-level grids and the segmented-incidence
("sparse") program whenever a super-spine tier is present.  The sparse
program freezes routes as incidence structure, so it supports static
ECMP plus failure/flap windows *and* the full CC zoo (per-flow
DCQCN / Timely / HPCC — per-flow state plus segment-summed per-port
telemetry, bit-equal to the dense formulation on 2-tier grids, held by
``tests/test_sparse_cc.py``); dynamic routing modes, the message
layer, FaultConfig injection and adaptive dt stay dense-only (it
rejects them with clear errors).  Within that envelope it is bit-equal
to the dense engine on 2-level grids and matches the scalar driver
like any other engine (held by ``tests/test_topology_pods.py``).  Its per-tick cost is linear in
flows + ports instead of the dense flows x ports — the bench ``scale``
section gates the measured growth exponent (~1.2 at 64 -> 256 hosts)
below the dense engine's 2.0.

Pod-scale scenario bundles: ``pod_incast`` (cross-pod fan-in through
both oversubscription tiers, optional in-pod victim), ``pod_shuffle``
(all-to-all across pods, ``uplink_util`` observability), and
``pod_pfc_storm`` (small-buffer pause cascade climbing tiers), each
with a ``*_grid`` companion that runs the mode x PFC (or buffer) grid
as ONE sparse vector program.

Engine performance
------------------
The vector tick body is built from *fused stages*: the innermost
strict-priority port drain and the QoS receiver admission are single
water-fill primitives (:func:`repro.fabric.fused.priority_grants` /
:func:`~repro.fabric.fused.priority_admit`) rather than per-class
op chains.  Each primitive has three interchangeable tiers selected by
``run_fabric_sweep(..., impl=...)``:

``"ref"``
    The stacked jnp/numpy formulation.  The default everywhere off-TPU,
    and always the tier behind ``backend="numpy"`` (float64 reference).
``"pallas"``
    A Pallas TPU kernel (grid/BlockSpec idiom shared with
    ``repro.kernels``): queue/port panels are padded to (8, 128) tiles
    and the water-fill runs on-chip.  ``impl="auto"`` (the default)
    activates it exactly when ``jax.default_backend() == "tpu"``.
``"interpret"``
    The same Pallas kernel run under ``pl.pallas_call(interpret=True)``
    — bit-equal to what the TPU executes, runnable on CPU CI, but
    *slow* (it emulates the kernel lane-by-lane); use it to validate
    kernel changes (``tests/test_fused.py`` pins interpret == ref
    bit-for-bit), never for throughput.

Adaptive time-stepping (``run_fabric_sweep(..., adaptive_dt=True)``,
tuned via :class:`repro.fabric.fused.AdaptiveConfig`) takes closed-form
macro-ticks over quiet stretches — every queue steady, no pause/timer/
watermark within a guard band — and fine dt near events.  Delivered
bytes stay within ``AdaptiveConfig.rel_bytes_bound`` (default 1 %,
relative) of the fine-tick run and completion timestamps shift at most
``(max_stride + 1) * dt`` per crossed macro window (property-tested in
``tests/test_fused.py``); ``adaptive_dt=False`` (the default) traces
none of this machinery and stays bit-equal to the fixed-dt engines.

Reading the bench profiling fields (``experiments/bench/
BENCH_fabric.json``, emitted per vector section by
``benchmarks/bench_fabric.py``): ``per_tick_ms_warm`` is warm wall
clock per simulated tick; ``compile_s`` the cold-minus-warm split;
``op_count_step`` the jaxpr op census of the scan body (the per-tick
dispatch load — if a perf regression shows here it is op growth, if
wall clock moves while the census is flat it is runtime);
``op_count_total`` / ``op_kinds`` the whole-program census.  The
``adaptive`` section gates what adaptivity promises — ``coarsen_ratio``
(fine ticks per adaptive iteration) and ``dev_delivered_vs_fixed``
(against ``rel_bytes_bound``) — while recording its wall clock
honestly (on CPU the ``lax.while_loop`` per-iteration overhead can eat
the iteration savings; the win is the iteration count, which is what
transfers to accelerators).

Running sweeps at farm scale
----------------------------
One ``run_fabric_sweep`` call is one process, one device, one XLA
program over the whole grid — the right shape up to a few hundred
points, and exactly wrong beyond that.  ``repro.fabric.farm``
(``run_farm(...)`` / ``python -m repro.fabric.farm --grid pod_storm
--workers N``) runs any grid — a registry name from
``scenarios.GRIDS``, a picklable ``GridSpec``, or a raw scenario list —
as **fixed-shape chunks**:

- **Chunking + padding semantics.**  ``scenarios.chunk_plan`` cuts the
  grid into full chunks of ``chunk_size`` plus one remainder padded up
  to the next power of two (at most two program shapes per run, so at
  most two compiles after the caches are cold).  Padding replicates a
  real scenario; vmap lanes are independent and every result is
  per-point, so padded lanes are sliced off without perturbing real
  points.  Because capability flags (CC/messages/faults/…) and ring
  lengths are any-over-points, a chunk of a heterogeneous grid would
  naturally trace a *different* program — the farm prevents that by
  passing the full grid's **structure envelope**
  (``FabricSweepParams.envelope()``) into every chunk's packing, which
  floors flags and ring sizes to the monolithic values.  Net effect,
  gated in the bench ``farm`` section and ``tests/test_farm.py``: at
  fixed dt, chunked results are **bit-identical** to the monolithic
  program (``adaptive_dt`` is the one exception — its macro-stride is
  a grid-wide lockstep reduction, so chunk membership legitimately
  changes stride schedules; the farm therefore always runs fixed dt).
- **Dispatch.**  ``workers <= 1`` stays in-process: a one-deep
  prefetch thread packs chunk k+1 while chunk k computes, and chunks
  round-robin across ``jax.devices()``.  ``workers > 1`` fans chunks to
  a ``spawn`` pool (CPU hosts only: a chip belongs to one process);
  workers rebuild the grid from the registry name (scenario closures
  don't pickle), share the on-disk XLA compilation cache, and write
  their own shards.
- **Artifact layout + resume contract.**  Each run writes
  ``experiments/runs/<run_id>/``: ``manifest.json`` (grid spec, chunk
  plan, structure envelope + key, config hash, git SHA, engine,
  per-chunk wall/compile timings, status; ``envelope_s``,
  ``plan_s`` and ``merge_s`` for the run, and per chunk the host spans ``pack_s``,
  ``pack_wait_s``, ``params_s``, ``h2d_s``, ``dispatch_s``,
  ``device_s``, ``d2h_s``, ``unpack_s`` and the transfer counters
  ``h2d_arrays``/``h2d_bytes``, ``d2h_arrays``/``d2h_bytes`` (a chunk
  puts its parameters as one float32 and one int32 buffer, builds its
  zero carry on the device, and pulls back only the carry keys the
  metrics read, one float32 buffer plus an int32 one with messages) — the
  same spans land on the ``jax.profiler`` host plane as ``farm.*`` /
  ``chunk.*`` annotations, see :mod:`repro.fabric.farm`),
  ``chunk_NNNN.npz`` shards
  (real points only, written atomically), and the merged ``result.npz``
  table in input order.  ``run_farm(..., run_id=..., resume=True)``
  re-reads the manifest, verifies the grid fingerprint, and executes
  only chunks whose shards are missing or unreadable — kill a run at
  50% and the restart completes the other half (CI smoke-tests
  exactly this).  ``benchmarks/bench_trajectory.py`` reads the
  ``BENCH_*.json`` history the same artifacts-first way for the
  per-metric trajectory dashboard.

The routing layer
-----------------
Routing used to be construction-time metadata (`Topology.route` froze a
`flow -> path` dict).  It is now a per-tick layer shared by every
engine: `FabricConfig.routing` selects a :class:`~repro.fabric.routing
.RoutingConfig` mode and the spine choice of each cross-leaf flow is
resolved every tick from per-uplink queue depth and link up/down state.

``static_ecmp`` (default)
    `flow_id % n_spines`, frozen — bit-equal to the pre-routing-layer
    driver (golden-tested in tests/test_routing.py) and the baseline
    the dynamic modes are judged against.
``weighted_ecmp``
    Deterministic flowlet re-hash whenever a flow's injection has been
    idle longer than `flowlet_gap_us` (Kandula-style flowlet boundary;
    immediately on a dead path), weighted by per-uplink free buffer
    space.  A flow that never pauses keeps its path — steady grids are
    bit-equal to the pre-gap-semantics engine.
``adaptive``
    Per-tick least-congested uplink with a `hysteresis_frac` flap
    guard.
``spray``
    Per-tick proportional byte split across all up spines; the reorder
    cost is a `spray_settle_us` delay before sprayed arrivals reach
    receiver admission.

`Topology.fail_link(src, dst, at_us, restore_us)` schedules link
failures: in-flight bytes on the dead link are dropped and re-credited
(fluid go-back-N) and dynamic modes reroute around it, which is the
`scenarios.link_failure_incast` / `routing_grid` experiment (adaptive
and spray complete the incast after a failure that stalls static ECMP).
Observability: `FabricResult.uplink_util` / `flow_reroutes` /
`uplink_imbalance()`, and `uplink_util[_max/_mean]` + `reroute_count`
in sweep outputs.

The vector engines treat routing mode, failure schedules, WRR
scheduling and per-TC host PFC as *per-point parameters*: the old
"grid points must share routes" restriction is lifted (points must
only share node/link structure and the flow set), so one
`run_fabric_sweep` program can compare `static_ecmp` against
`adaptive` under a mid-burst uplink failure (`scenarios.routing_grid`).
Grids whose points are all static ECMP without failures keep the
original single-path program, bit-for-bit.  One caveat: in a
dynamic-routing grid, pause targeting is candidate-ingress-granular
for every point (a rerouted flow's queued bytes have mixed
provenance), matching the scalar driver's behaviour for dynamic
scenarios — keep PFC'd static baselines in their own static grid when
bit-parity with the frozen-route program matters.

Per-TC queue support across engines
-----------------------------------
Every engine implements the classed switch identically (the test suite
in ``tests/test_pfc_priority.py`` holds them together): per-TC FIFOs
with their own buffer partition, ECN knee and PFC xoff/xon watermarks,
strict-priority drain, and ``(ingress link, tc)`` pause targeting.
``Flow.qos`` selects the class end to end — switch queue on every hop
*and* receiver RNIC admission class.  The scalar driver additionally
reports the per-``(link, tc)`` pause breakdown
(``FabricResult.pause_tc_us``); the vector engines aggregate it to a
per-class total (``pause_tc_total_us``, [G, Q]).  ``per_tc`` and the
``tc_*`` watermark overrides are plain per-point parameters, so one
sweep grid can compare 802.1Qbb pause against the legacy whole-link
pause (``SwitchConfig.per_tc=False``, which is bit-equal to the
pre-refactor switch for single-class traffic in every engine).

The message layer
-----------------
The fluid core moves continuous byte streams; applications issue
discrete verbs ops.  `FabricConfig.msg` (or per-flow `Flow.msg`)
attaches a :class:`~repro.fabric.messages.MessageConfig` to a flow and
the engines carve its byte stream into fixed-size messages:

- **verb**: ``"write"`` (RDMA WRITE — no receiver CPU touch, small
  per-op gap) or ``"send"`` (SEND/RECV — adds a receive-side completion
  cost `send_extra_us` to every message latency and a larger issue
  gap).  The per-op issue gap caps the flow's offered rate at
  ``msg_bytes * 8e-3 / op_gap_us`` Gbps.
- **window**: max outstanding (unacked) ops; injection stalls when
  ``window * msg_bytes`` are in flight beyond the delivered watermark.
  ``window=None`` means unbounded (scalar driver only — the vector
  engines need a static completion ring and reject it with a clear
  error).  With DCQCN and an unbounded window the message layer is
  pure observability: goodput reproduces the plain fluid run exactly.
- **go-back-N**: drops re-credit the flow's injected watermark, so a
  message's clock keeps running across replays — its completion time
  includes every retransmission, matching NACK-based verbs recovery.

A message *starts* when its first byte is injected and *completes*
when its last byte is delivered (or escapes to the slow path — the
latency then includes the escape penalty).  Per-message completion
times feed latency percentiles in every engine:

- scalar driver: exact — all completion times are kept and sorted
  (`FabricResult.msg_p50_us` / `msg_p99_us` / `msg_p999_us`,
  NaN-safe accessors returning 0.0 when no messages completed, with
  `FabricResult.has_messages` to tell "no ops" apart from "fast ops").
- vector engines: a fixed 128-bucket log-spaced histogram
  (1 µs … 100 ms) accumulated inside the scan; the bucket-midpoint
  estimate is within ``sqrt(ratio) - 1`` ≈ 4.6 % relative error of the
  exact value (pinned in tests/test_messages.py), and message *counts*
  are exact — the numpy engine matches the scalar driver's completion
  times to 1e-9.

`scenarios.message_incast` builds an N-to-1 verbs incast and
`scenarios.message_sweep_grid` sweeps msg-size x window x verb x CC as
ONE vectorized program, reporting Mops, goodput GiB/s and p99 per
point — the msg-rate-vs-msg-size curve of the paper's Fig. 2 family.

The fault layer
---------------
The fluid core is lossless by construction — drops exist only as the
instant drop-re-credit idiom.  `FabricConfig.faults` attaches a
:class:`~repro.fabric.faults.FaultConfig` and makes failure a
first-class, *deterministic* experiment axis:

- **stochastic link loss** (`loss_rate`, per-link `link_loss`
  overrides, an independent `corrupt_rate` stream on the receiver
  access links): a link drops everything it drained on a tick iff
  ``hash(tick, link_salt) < floor(rate * 65536)``.  The hash is pure
  modular int arithmetic seeded from the link *name*, so the scalar
  driver, the batched-numpy engine and the jax engine see
  bit-identical fault realizations — fault runs stay
  equivalence-testable, and loss-rate sweeps are coherent (raising the
  rate only *adds* drops to the same realization; nested thresholds).
- **link flaps**: `Topology.flap_link(src, dst, start_us, period_us,
  down_us)` generalizes `fail_link` to a periodic up/down schedule;
  in-flight bytes drop on each down edge and dynamic routing modes
  steer around the hole every cycle.
- **NIC/host crash--restart**: `FaultConfig.crash(host, at_us,
  restart_us)` zeroes the receiver's admission state at `at_us`, drops
  everything queued on its access link, and discards arrivals until
  `restart_us`; `FabricResult.crash_recovery_us` stamps the first
  re-accepted byte after restart.
- **loss recovery**: flows with a message config get a sender-side
  retransmission ledger replacing the instant re-credit.
  `MessageConfig.recovery` picks ``"go_back_n"`` (RTO with exponential
  backoff — `rto_us` x `rto_backoff`**k capped at `rto_cap`, reset on
  delivery progress; bytes arriving while the window is gapped are
  discarded as duplicates and replayed too) or IRN-style
  ``"selective"`` (arrivals keep landing; only the lost span replays
  after a short `nack_us` NACK delay).  `examples/fault_recovery.py`
  puts numbers on the gap: under stochastic loss go-back-N's p999 and
  retransmitted bytes blow up while selective stays near the lossless
  baseline (asserted in tests/test_faults.py).
- **graceful-degradation metrics**: `FabricResult.dropped_pkts`,
  `retransmit_bytes`, `crash_recovery_us`, `deadlock_ticks` (a per-tick
  PFC pause-cycle watchdog in every engine — the vector engines run the
  same cycle predicate via boolean-matrix squaring over the pause-pair
  graph, equivalence-tested against the scalar walker), and the
  routing-aware
  PFC-storm view `pause_tc_fanout` / `n_pausable_links` /
  `pause_storm()` (paused fraction of the pausable link set, NaN-safe).

All fault knobs ride the sweep axes like every other parameter:
`scenarios.lossy_incast` / `lossy_incast_grid` race loss-rate x
recovery-mode grids as ONE vectorized program.  ``faults=None`` (the
default) is bit-equal to the pre-fault engines.

Choosing a congestion controller
--------------------------------
`FabricConfig.cc` (or per-flow `Flow.cc`) selects the rate controller
behind every sender; vector sweeps take it per point, so one grid can
race the zoo:

``dcqcn`` (default)
    ECN-mark driven rate cuts + additive/hyper increase — the classic
    RoCE controller, bit-equal to the pre-zoo engines (a ``CcConfig``
    with ``algo="dcqcn"`` reuses the existing `DcqcnRate` machinery,
    including per-flow `Flow.dcqcn` overrides).
``timely``
    RTT-gradient control: the fluid RTT signal is base RTT plus the
    queue-drain delay along the flow's current path; rates are cut
    proportionally to the smoothed RTT gradient and increased
    additively below `t_low_us` / when the gradient is negative.
    Reacts to *queue growth* before queues are deep, which is why it
    wins the incast p99 race below.
``hpcc``
    INT-style utilization control: every hop reports
    ``(tx + queue/base_rtt) / capacity``; the rate is multiplied by
    ``eta / max_utilization`` each update (clipped to [0.5, 2]) plus
    an additive term — drives utilization to `hpcc_eta` (95 %) with
    near-empty queues.

Under the 8-to-1 message incast both alternatives beat DCQCN's p99
message latency by ~4x (asserted in tests/test_messages.py): DCQCN
only reacts once the ECN knee is crossed, so its window oscillates
around a standing queue, while Timely/HPCC hold the queue near zero.
Signals are computed from the same per-tick state in every engine
(scalar and vector runs agree on counts exactly and on percentiles
within the histogram bound).
"""
from .cc import CC_ALGOS, CcConfig, HpccRate, TimelyRate, make_controller
from .fabric import (FabricConfig, FabricResult, Flow, burst_done_bytes,
                     run_fabric)
from .farm import GridSpec, run_farm
from .faults import FaultConfig, FlowRecovery, has_pause_cycle
from .hosts import HostFeedback, ReceiverHost, SenderHost
from .messages import (LogHistogram, MessageConfig, MessageTracker,
                       exact_percentile, percentile_from_counts)
from .routing import ROUTING_MODES, RoutingConfig
from .scenarios import (GRIDS, Scenario, all_to_all, build_grid,
                        chunk_plan, fabric_grid, incast, incast_grid,
                        link_failure_incast, lossy_incast,
                        lossy_incast_grid, message_incast,
                        message_sweep_grid, mixed_fleet,
                        mixed_fleet_grid, olap_shuffle, pod_incast,
                        pod_incast_grid, pod_pfc_storm, pod_shuffle,
                        pod_storm_grid, qos_mixed_grid,
                        qos_mixed_storage, routing_grid, single_pair,
                        storage_mix)
from .switch import OutputPort, Switch, SwitchConfig
from .sweep import SweepParams, grid_configs, run_sweep
from .topology import (Link, Topology, clos, incast_fabric, jet_testbed,
                       make_pod_clos)
from .vector import FabricSweepParams, run_fabric_sweep

__all__ = [
    "CC_ALGOS", "CcConfig", "FabricConfig", "FabricResult",
    "FabricSweepParams", "FaultConfig", "Flow", "FlowRecovery",
    "GRIDS", "GridSpec",
    "HostFeedback", "HpccRate", "Link",
    "LogHistogram", "MessageConfig", "MessageTracker", "OutputPort",
    "ROUTING_MODES", "ReceiverHost", "RoutingConfig", "Scenario",
    "SenderHost", "Switch", "SwitchConfig", "SweepParams", "TimelyRate",
    "Topology", "all_to_all", "build_grid", "burst_done_bytes",
    "chunk_plan", "clos",
    "exact_percentile", "fabric_grid", "grid_configs",
    "has_pause_cycle", "incast", "incast_grid",
    "incast_fabric", "jet_testbed", "link_failure_incast",
    "lossy_incast", "lossy_incast_grid",
    "make_controller", "make_pod_clos", "message_incast",
    "message_sweep_grid", "mixed_fleet", "mixed_fleet_grid",
    "olap_shuffle", "percentile_from_counts", "pod_incast",
    "pod_incast_grid", "pod_pfc_storm", "pod_shuffle", "pod_storm_grid",
    "qos_mixed_grid", "qos_mixed_storage",
    "routing_grid", "run_fabric", "run_fabric_sweep", "run_farm",
    "run_sweep", "single_pair", "storage_mix",
]
