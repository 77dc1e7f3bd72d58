"""Heterogeneous federated simulation subsystem.

The paper's value proposition is what FedLite saves on the wire; this
package *measures* it — in BOTH directions — instead of only asserting it
analytically. Compression is direction-agnostic: each side of the cut runs
a codec from the `core/compressors.py` registry (``none`` | ``pq`` |
``topk`` | ``scalarq`` | ``chain:...``), configured per direction on
`FederatedTrainer` (``uplink_compressor`` / ``downlink_compressor`` spec
strings) or on `ArchConfig` for the big archs. The uplink default is the
paper's grouped PQ; the downlink default is dense — the measured traffic
that motivated the stack, since the cut-layer *gradient* dominates
bytes-on-the-wire once the uplink is PQ-compressed.

Eight layers, composed by `FederatedTrainer`:

  runtime.py    — the algorithm drivers (FedAvg / SplitFed / FedLite round
                  logic, cohort sampling — uniform or p_i-weighted — and
                  weighted aggregation). `FederatedTrainer.run` executes
                  training rounds through the scheduler below; it installs
                  the downlink codec into the model's VJP and measures
                  both directions' payloads through the wire codec.
  wire.py       — the versioned tagged wire codec: every payload is a 24 B
                  header + a kind-specific body (``pq`` codebooks+packed
                  codes, ``dense`` tensors, ``sparse`` top-k indices with
                  optionally *nested* values, ``scalar`` b-bit packed
                  codes). Bit-exact round-trips; unknown versions/kinds are
                  rejected loudly; measured bytes validate the compressors'
                  ``analytic_bits``.
  network.py    — `ClientProfile` (asymmetric bandwidth, latency, compute
                  multiplier, dropout), the struct-of-arrays `ClientFleet`
                  population (one float64 column per field — the
                  representation the vectorized scheduler core runs on),
                  and fleet samplers (all returning `ClientFleet`):
                  `uniform_fleet` (the IDEAL pre-subsystem clients),
                  `lognormal_fleet` (heavy-tailed broadband),
                  `mobile_fleet` (flaky mobile mixture).
  scheduler.py  — a virtual-clock round core dispatching rounds under a
                  participation policy: `FullSync`, `DropSlowestK`,
                  `Deadline`, or FedBuff-style `AsyncBuffer` whose
                  staleness weights are applied per contribution
                  (``core/fedlite.make_weighted_step``). Two backends —
                  the vectorized array core and the per-arrival heapq
                  reference — produce bitwise-identical traces (see
                  "Scaling fleets" below).
  topology.py   — `TwoTierTopology`: a hierarchical aggregation tier
                  (clients -> edge aggregators -> server) with clients
                  k-means-clustered by simulated location; edges
                  pre-combine their cluster's uplinks so the
                  parameter-server link carries one payload per edge.
  trace.py      — per-round `RoundRecord`s (simulated wall-clock, measured
                  uplink AND downlink bytes, stragglers dropped, staleness,
                  per-participant shard placement) collected into a `Trace`
                  with per-direction time/bytes-to-target reductions,
                  windowed controller signals (straggler ``tail_ratio``,
                  ``drop_rate``, ``bytes_per_round``, ``loss_slope``) and
                  run-level codec metadata in ``Trace.meta``.
  executor.py   — the cohort execution engine (see "Scaling cohorts across
                  devices" below): ``stacked`` | ``mesh`` backends mapping
                  each server update's per-client math onto devices.
  autoscale.py  — `TraceAutoscaler`: a deterministic controller that turns
                  the trace's windowed signals into (cohort, policy,
                  downlink codec) moves, plus ``autoscale_run`` driving a
                  training run in plan-sized segments.
  faults.py     — the chaos layer: a seeded, declarative `FaultPlan`
                  (client crashes, wire corruption, poisoned gradients,
                  arrival reordering, edge outages, server kills) whose
                  draws come from a stateless hash stream — never the
                  training or scheduler RNGs (see "Fault tolerance").
  recovery.py   — crash-consistent runtime snapshots + `run_with_recovery`,
                  the segmented driver that survives `ServerKilled` by
                  restoring the latest snapshot from disk.

Scaling cohorts across devices
------------------------------
The scheduler decides WHO participates; the `CohortExecutor` decides WHERE
their math runs. ``FederatedTrainer(executor="stacked")`` (default) is the
historical single-device path — synchronous cohorts fuse into one stacked
batch, async flushes run the per-contribution weighted step — and stays
bitwise-identical to the pre-engine trainer. ``executor="mesh"`` (or
``"mesh(shards=N)"``) shards the cohort over the ``clients`` axis of a 1-D
device mesh (``launch/mesh.make_clients_mesh``; on CPU CI a real 2-4-shard
mesh via ``XLA_FLAGS=--xla_force_host_platform_device_count=4``):
client-major batches, PRNG keys, error-feedback memories and `CutState`s
are placed with ``NamedSharding(mesh, P("clients"))``, each shard computes
its local clients' gradients, and the weighted combine crosses shards once
as an explicit psum (``core/fedlite.make_mesh_step``). All four policies
execute unchanged on either backend; traces record every participant's
shard. Round wall-clock then scales with the shard count
(``benchmarks/bench_network.py --executor mesh`` measures it), which is
what lets cohort size become an autoscaler knob rather than a hardware
ceiling.

Scaling fleets (the vectorized scheduler core)
----------------------------------------------
The executor scales WHERE cohort math runs; the vectorized scheduler core
scales HOW MANY clients the simulation can hold. Populations are
struct-of-arrays (`ClientFleet`: one float64 column per profile field), so
a million-client fleet is five arrays, not 10^6 boxed Python objects, and
a round is a handful of whole-cohort array ops: one gather-and-add chain
for every participant's ``downlink + compute + uplink`` round trip, one
vectorized Bernoulli draw for dropouts, one stable argsort of arrival
times, and a policy *prefix cut* on the sorted vector
(``Policy.split_vector``). Python touches a round only at its boundary.
``Scheduler(backend=...)`` selects the core: ``"vector"``, ``"heapq"``
(the original per-arrival event loop, kept as the reference
implementation), or ``"auto"`` (vector whenever the policy supports it —
all four built-ins do; custom split-only policies fall back to heapq).
Both backends evaluate the same IEEE-double expressions in the same
association order and share one RNG draw sequence, so their traces are
*bitwise identical* — asserted across fleet x policy x cohort in
tests/test_fleet_scale.py, which makes the heapq backend a standing
parity oracle for the array core. At 10^6 clients / 10^4-client cohorts
the vector core runs a round in tens of milliseconds
(``benchmarks/bench_network.py --fleet-scale`` measures it, and CI
asserts the budget).

Hierarchical aggregation rides the same scale: ``TwoTierTopology``
(``topology.py``) k-means-clusters clients by simulated location into
edge aggregators; each edge pre-combines its cluster's surviving uplinks
(aggregation is linear, so sync-policy pre-combination is semantically
free) and ships ONE edge payload over the edge->server hop, decongesting
the parameter-server link. Round end under a topology is when the last
participating edge's payload lands. Async buffers relay store-and-forward
(per-contribution staleness must survive, so no pre-combination — every
contribution pays the edge hop instead). The trace's byte ledger splits
tiers — ``edge_uplink/<kind>`` vs ``server_uplink/<kind>`` — and
`Trace.tier_totals` / `Trace.tier_bytes_per_round` expose where bytes
flow; `TraceAutoscaler` observes both tier signals.

Cross-round state (all default-off): `FederatedTrainer` can additionally
carry cut-layer state across scheduler rounds — PQ codebook warm-start
(``warm_start=True``: Lloyd resumes from last round's codebook at
``PQConfig.warm_iters`` iterations; cohort-global under the stacked
policies, per-client under `AsyncBuffer`), per-client error-feedback
memory (``error_feedback=True``), stochastic downlink rounding
(``stochastic_downlink=True``) and ``pq-delta`` codebook wire encoding
(``codebook_delta_bits``: the uplink ships b-bit quantized codebook deltas
against the acked reference; ``wire.encode_pq_delta``).

Fault tolerance
---------------
`faults.py` turns the simulation into a chaos harness: a frozen
`FaultPlan` declares per-round fault rates and the `FaultInjector` draws
every fault from a stateless splitmix64 hash keyed on (plan seed, fault
kind, round/stream-seq, client) — never from the training or scheduler
RNGs, so a zero-fault plan is bitwise-identical to no plan at all and
backend trace parity holds under any plan. What the runtime survives:

  * **Client crashes mid-round** — the scheduler retries with
    exponential backoff in virtual time (both backends, identical
    IEEE association); each retry re-pays the downlink, ledgered under
    ``retry_downlink/<kind>``; past ``max_retries`` the client is
    permanently dropped from the round.
  * **Wire corruption** — every v4 frame carries a CRC32 trailer, and
    ANY malformed payload raises from the typed `WireError` hierarchy
    (``WireTruncationError`` / ``WireCorruptionError`` /
    ``WireVersionError`` / ``WireResyncError``; fuzzed in
    tests/test_wire.py). The server decodes a per-round canary through
    the real codec and quarantines corrupt contributions; the
    ``corrupt_undetected`` counter must stay 0 (canary assertion).
  * **Poisoned gradients** — non-finite contributions are quarantined by
    a finiteness screen before aggregation; eq.-5 λ-correction and
    staleness weights renormalize over the survivors. A round whose
    survivor fraction falls below ``quorum_fraction`` is VOIDED (no
    server update).
  * **pq-delta lineage breaks** — delta codebook payloads carry an epoch
    word; an epoch or reference-geometry mismatch raises
    `WireResyncError` and `wire.DeltaCodebookLink` falls back to a full
    codebook resync handshake.
  * **Edge-aggregator outages** — `TwoTierTopology.rehome` re-homes a
    down edge's clients to the next-nearest live edge for the outage
    window (``rehomed``/``edges_down`` counters).
  * **Server kills between rounds** — `ServerKilled` unwinds the run;
    `recovery.run_with_recovery` restores the latest crash-consistent
    snapshot FROM DISK (atomic tmp+rename writes, sha256 manifest
    written last, verified on restore — `checkpointing/checkpoint.py`)
    and resumes from the scheduler cursor bitwise-identically
    (tests/test_faults.py pins final params AND trace).

Every fault and recovery lands in the observability stack: per-round
``RoundRecord.faults`` counters (``Trace.fault_totals()`` for the run),
``fault.*`` events on the obs log, and the run inspector's ``--faults``
table. ``benchmarks/bench_network.py --chaos`` sweeps fault rate x
policy and asserts graceful degradation: target loss still reached at
the baseline fault rate, retry byte inflation bounded, canary clean.

The ideal fleet + `FullSync` + dense downlink reproduces the original
synchronous simulation bitwise (tests/test_scheduler.py,
tests/test_compressors.py); heterogeneous fleets and per-direction codecs
turn the same trainer into the paper-§5 trade-off harness driven by
``benchmarks/bench_network.py`` (``--downlink`` sweeps the gradient codec).

Observability
-------------
The whole subsystem is permanently instrumented through `repro.obs`,
organized as three layers — each built on the one below, all free when
no recorder is configured:

  * **Layer 1 — spans + sync-free metrics (how long, how often).**
    ``obs.configure(run=...)`` installs a recorder; from then on
    `Scheduler.run` records every round twice — once on the *host
    wall-clock* lane (what the process spent, jit dispatch only, never a
    device sync) and once on the *scheduler virtual-clock* lane (what
    the simulated fleet spent) — alongside ``trainer.round``, executor
    place/execute/dispatch phases, wire encode/decode and checkpoint I/O
    spans; autoscaler plan moves and straggler cuts are instant events on
    the same log. Under a ``jax.profiler`` session the host spans are also
    profiler annotations, and the step's ``jax.named_scope``s
    (``fl_client``, ``fl_uplink_codec``, ``fl_downlink_codec``,
    ``fl_server``, ``fl_optimizer``) name the layer of each device
    operation. Jitted steps return metrics as device arrays through aux
    pytrees into an `obs.MetricsBuffer`, converted with ONE
    ``jax.device_get`` at the end of the run — tests/test_obs.py counts
    transfers to hold instrumented runs to "no more than
    uninstrumented". Export with ``Recorder.write_jsonl`` (append-only
    JSONL, the durable artifact; ``obs.read_jsonl_tolerant`` re-reads
    logs whose writer was killed mid-line) and ``Recorder.write_perfetto``
    (Chrome trace_event JSON; the two lanes render as two processes at
    https://ui.perfetto.dev).
  * **Layer 2 — the byte ledger (how many bytes, which wire).** Each
    `RoundRecord` carries a ``ledger`` mapping
    ``"<direction>/<wire-kind>"`` to measured bytes
    (``Trace.ledger_totals()`` for whole-run totals), including
    fault-attributed entries like ``retry_downlink/dense``, so "how many
    bytes were pq vs dense" and "what did crashes cost" are first-class
    queries.
  * **Layer 3 — contribution flights + SLO health (what happened to
    each update, and was the run OK).** Every sampled cohort
    contribution gets a stable flight id (``r{round}-c{client}-s{seq}``)
    and a `repro.obs.FlightFrame` row tracing its causal lifecycle —
    sampled → placed (executor shard, edge) → uplink (crash retries,
    re-homes) → terminal state (aggregated / policy-cut / dropped /
    quarantined / voided) — recorded identically by the heapq and
    vectorized scheduler backends (asserted in tests), persisted through
    kill-and-resume snapshots, and kept O(cohort) at 1M clients via
    per-round rollup histograms plus reservoir-sampled exemplar
    lifecycles; in Perfetto, flow arrows link each exemplar's spans
    across the two lanes. On top of the same reductions,
    `repro.obs.HealthMonitor` grades declarative windowed SLO rules
    (``tail_ratio<=3``, ``quarantine_rate<=0.25``, ...) — pass one as
    ``FederatedTrainer(slo_monitor=...)`` and failures land as
    ``slo_violation`` events in the run's own log; `TraceAutoscaler`
    consumes the same signals.

``python -m repro.obs <run.jsonl>`` prints round tables, duration
percentiles, the ledger and bytes/time-to-target; ``--faults`` the
fault ledger; ``--flight <id-or-client>`` reconstructs a recorded
flight's lifecycle; ``--health`` / ``--slo "sig<=thr[@win]"`` the SLO
grade. ``benchmarks/bench_network.py --emit-trace`` (defaulting into
gitignored ``benchmarks/out/``) and the femnist example's
``--emit-trace`` produce such logs end-to-end; ``benchmarks/common``
appends every bench row to ``BENCH_history.jsonl`` and
``benchmarks/sentinel.py`` gates committed snapshots against a baseline
in CI.

Static analysis
---------------
This subsystem concentrates the repo's classic silent-failure modes: a
host sync inside a per-arrival scheduler callback serializes every round,
a jit closure rebuilt per round retraces the step each call, a typo'd
mesh axis explodes only at trace time on a real mesh, and a wire kind
without an explicit decoder arm mis-decodes the *next* kind added. The
`repro.lint` package (``python -m repro.lint src benchmarks examples``)
checks all of these statically — eight AST/jaxpr passes (fleet-scale,
host-sync, custom-vjp, mesh-axes, obs-events, pallas, wire-format,
wire-decode; catalogue in the ``repro.lint`` docstring, ``--list-rules``
for the full list). The obs-events pass cross-checks every literal
``obs.event`` name emitted from the federated hot paths against the
`repro.obs.schema` registry, so a typo'd event name (invisible to every
dashboard filtering on the real one) is a lint error. CI's
``static-analysis`` job fails on any finding, and
``python -m benchmarks.run --preflight`` runs the identical gate before a
benchmark spend. Intentional syncs (e.g. the once-per-``log_every``
trainer log line) carry an inline ``# fedlint: disable=<rule>`` so the
decision is visible in review. The host-sync pass additionally bans
hand-rolled ``time.perf_counter()``/``print()`` instrumentation in the
``repro/federated`` and ``repro/core`` hot paths
(``raw-timing-in-hot-path``): measurements belong in `repro.obs`
spans/events so they land in the run's exportable two-lane log, and the
fleet-scale pass (``python-loop-over-fleet``) bans per-client Python
loops in ``repro/federated`` hot paths — fleet-sized iteration belongs
on `ClientFleet` columns; the heapq reference backend's per-arrival code
carries reviewed suppressions. ``wire.py``'s encoder bodies are pinned by
AST hash in ``repro/lint/wire_manifest.json``: editing an encode body
without bumping its version literal (and re-running ``python -m
repro.lint --update-wire-manifest``) is a lint error, so old decoders can
never silently accept payloads they cannot parse.
"""

from repro.federated.autoscale import (
    AutoscalePlan,
    TraceAutoscaler,
    autoscale_run,
    make_policy,
)
from repro.federated.executor import (
    CohortExecutor,
    MeshExecutor,
    StackedExecutor,
    available_executors,
    make_executor,
    register_executor,
)
from repro.federated.faults import (
    DEFAULT_CHAOS,
    FaultInjector,
    FaultPlan,
    ServerKilled,
    make_injector,
)
from repro.federated.network import (
    IDEAL,
    ClientFleet,
    ClientProfile,
    lognormal_fleet,
    mobile_fleet,
    uniform_fleet,
    validate_fleet,
)
from repro.federated.recovery import (
    restore_runtime,
    run_with_recovery,
    snapshot_runtime,
)
from repro.federated.runtime import (
    FederatedTrainer,
    fedavg_round,
    run_fedavg,
    sample_clients,
    weighted_average,
)
from repro.federated.scheduler import (
    AsyncBuffer,
    Deadline,
    DropSlowestK,
    FullSync,
    Scheduler,
)
from repro.federated.topology import TwoTierTopology
from repro.federated.trace import RoundRecord, Trace
from repro.federated import wire

__all__ = [
    "AsyncBuffer", "AutoscalePlan", "ClientFleet", "ClientProfile",
    "CohortExecutor", "DEFAULT_CHAOS", "Deadline", "DropSlowestK",
    "FaultInjector", "FaultPlan", "FederatedTrainer", "FullSync", "IDEAL",
    "MeshExecutor", "RoundRecord", "Scheduler", "ServerKilled",
    "StackedExecutor", "Trace", "TraceAutoscaler", "TwoTierTopology",
    "autoscale_run", "available_executors", "fedavg_round",
    "lognormal_fleet", "make_executor", "make_injector", "make_policy",
    "mobile_fleet", "register_executor", "restore_runtime", "run_fedavg",
    "run_with_recovery", "sample_clients", "snapshot_runtime",
    "uniform_fleet", "validate_fleet", "weighted_average", "wire",
]
