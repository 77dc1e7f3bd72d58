"""Measured time-to-target-loss and bytes-per-round under heterogeneity.

The end-to-end version of the paper's §5 trade-off: the same FEMNIST
training run is dispatched through the virtual-clock scheduler under
compression level x bandwidth distribution x straggler policy, and each
cell reports *measured* wire bytes (``federated/wire.py``) plus simulated
wall-clock — where ``bench_comm.py`` only counts bits analytically.

Scenario axes (fast mode keeps a 2x3 slice; --full runs the grid):

  * compression — SplitFed (raw fp32 activations) vs FedLite
    (q=1152, L=2: the paper's 490x point).
  * fleet       — ideal (identical infinitely-fast clients), lognormal
    broadband (heavy straggler tail), wired/mobile mixture with dropout.
  * policy      — full sync, drop-slowest-k, per-round deadline,
    FedBuff-style async buffer.
  * downlink    — (``--downlink`` / ``downlink=True``) the server->client
    gradient codec: dense vs ``chain:topk(k=0.1)+scalarq(bits=8)``. The
    compressed cell must show >= 8x measured downlink-bytes reduction
    (asserted — acceptance criterion) and still reach the round-0-derived
    target loss.
  * warm-start  — always-on extra cell: cross-round codebook warm-start
    (half the Lloyd iterations per steady-state round) + pq-delta codebook
    wire encoding on the default fleet; must still reach the target loss
    (asserted — acceptance criterion).
  * executor    — (``--executor mesh``) run the scenario cells through the
    cohort-parallel mesh executor (``federated/executor.py``) instead of
    the stacked single-device path, plus a shard-scaling cell: the
    cohort-execute phase (one synchronous server update over a fixed
    8-client cohort) timed at 1/2/4 shards, one child process per shard
    count with ONE DEDICATED CPU CORE PER SHARD (``taskset``) — the CPU
    emulation of one accelerator per shard. On hosts with >= 4 cores the
    4-shard speedup over 1 shard must be >= 1.5x (asserted — acceptance
    criterion); see ``run_executor_scaling`` for the calibrated
    smaller-host bars.
  * fleet scale — (``--fleet-scale``) scheduler-core scaling cells with a
    stub execute: simulated rounds per second and peak RSS at 10^5 and
    10^6 lognormal clients (10^3 / 10^4-client cohorts) under both
    scheduler backends, the 10^6 cells through a `TwoTierTopology` with
    per-tier measured bytes in the row. The 1M-client / 10k-cohort vector
    cell must finish a round inside the wall-clock budget and both
    backends' traces must match bitwise (asserted — acceptance criteria).
  * autoscale   — (``--autoscale``) one training run on the lognormal
    straggler fleet driven by the trace-driven `TraceAutoscaler`
    (``federated/autoscale.py``) in plan-sized segments, next to the
    static (cohort, policy) cells it chooses between. The autoscaled run
    must reach the target loss with NO MORE uplink bytes than the best
    static cell (asserted — acceptance criterion).

Emitted per row: simulated seconds, simulated time and uplink bytes to
reach the target loss (0.9x the round-0 loss), measured uplink AND
downlink MB/round, stragglers dropped, mean staleness. Every run also
snapshots the rows as ``BENCH_network.json`` at the repo root
(``benchmarks/common.write_bench_json``).

``--emit-trace [PATH]`` additionally records the whole run through the
``repro.obs`` telemetry recorder — scheduler rounds on the virtual-clock
lane, executor/wire/host spans on the wall-clock lane, per-round byte
ledgers, and the contribution flight recorder's rollups + exemplar
lifecycles — writing an append-only JSONL event log (default
``benchmarks/out/BENCH_network_trace.jsonl``; the out/ dir is
gitignored scratch) plus a Perfetto-loadable trace_event twin
(``--perfetto PATH`` to relocate it). Summarize the JSONL with
``python -m repro.obs <path>`` (``--health`` grades it against the SLO
rules; ``--flight <client-or-id>`` reconstructs one lifecycle).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import emit, out_path, write_bench_json
from repro import obs
from repro.obs import flight as flightlib
from repro.obs import slo
from repro.core.quantizer import PQConfig
from repro.data.synthetic import make_federated_image_data
from repro.federated import (DEFAULT_CHAOS, AsyncBuffer, AutoscalePlan,
                             Deadline, DropSlowestK, FaultPlan,
                             FederatedTrainer, FullSync, Scheduler,
                             TraceAutoscaler, TwoTierTopology,
                             autoscale_run, lognormal_fleet, make_policy,
                             mobile_fleet, uniform_fleet)
from repro.models.paper_models import FemnistCNN
from repro.optim import sgd

NUM_CLIENTS = 16
COHORT = 4
CLIENT_BATCH = 8

DOWNLINK_CHAIN = "chain:topk(k=0.1)+scalarq(bits=8)"

# marker line the shard-scaling leg children print their result through
_SCALING_MARKER = "BENCH_SCALING_LEG:"


def _fleets():
    return {
        "ideal": uniform_fleet(NUM_CLIENTS),
        "lognormal": lognormal_fleet(
            NUM_CLIENTS, median_uplink_bps=2e6, median_downlink_bps=10e6,
            bandwidth_sigma=1.0, compute_sigma=0.4, seed=0),
        "mobile": mobile_fleet(NUM_CLIENTS, flaky_fraction=0.4, seed=0),
    }


def _policies():
    return {
        "full_sync": FullSync(),
        "drop_slowest_1": DropSlowestK(1),
        "deadline_6s": Deadline(6.0),
        "async_buffer_2": AsyncBuffer(2),
    }


def _compressions():
    return {
        "splitfed": None,
        "fedlite_q1152_L2": PQConfig(num_subvectors=1152, num_clusters=2,
                                     kmeans_iters=2),
    }


# fast mode: the three straggler/bandwidth scenarios the acceptance
# criteria name, each at both compression levels
FAST_SCENARIOS = [
    ("ideal", "full_sync"),
    ("lognormal", "drop_slowest_1"),
    ("mobile", "deadline_6s"),
]


def _run_cell(data, fleet, policy, pq, downlink, rounds, fast,
              warm_start=False, delta_bits=None, executor="stacked",
              cohort=COHORT, fault_plan=None):
    # the mesh executor runs per-client math: give the model the matching
    # per-client quantization granularity so both executors cluster alike
    client_batch = CLIENT_BATCH if executor != "stacked" else 0
    model = FemnistCNN(pq=pq, lam=1e-4, client_batch=client_batch)
    trainer = FederatedTrainer(
        model, sgd(10 ** -1.5), data, cohort=cohort,
        client_batch=CLIENT_BATCH, quantize=pq is not None,
        fleet=fleet, policy=policy, downlink_compressor=downlink,
        warm_start=warm_start, codebook_delta_bits=delta_bits,
        executor=executor, fault_plan=fault_plan)
    t0 = time.perf_counter()
    state, hist = trainer.run(rounds, jax.random.PRNGKey(0))
    wall_us = (time.perf_counter() - t0) * 1e6 / max(rounds, 1)
    trace = trainer.last_trace
    losses = [h["loss"] for h in hist if "loss" in h]
    # fast mode only runs 8 rounds; use a reachable smoke target
    factor = 0.93 if fast else 0.9
    target = factor * losses[0] if losses else float("nan")
    t_target = trace.time_to_target(target)
    b_target = trace.bytes_to_target(target)
    s = trace.summary()
    row = {
        "us_per_call": wall_us,
        "sim_seconds": round(s["simulated_seconds"], 2),
        "sim_seconds_to_target": None if t_target is None
        else round(t_target, 2),
        "uplink_mb_to_target": None if b_target is None
        else round(b_target / 1e6, 4),
        "uplink_mb_per_round": round(s["uplink_bytes_per_round"] / 1e6, 4),
        "downlink_mb_per_round": round(
            s["downlink_bytes_per_round"] / 1e6, 4),
        "stragglers_dropped": s["stragglers_dropped"],
        "mean_staleness": round(s["mean_staleness"], 2),
        "final_loss": round(losses[-1], 4) if losses else None,
        "reached_target": t_target is not None,
    }
    return row, trainer, state


def run(fast: bool = True, downlink: bool = False,
        executor: str = "stacked", autoscale: bool = False,
        fleet_scale: bool = False, chaos: bool = False):
    data = make_federated_image_data(num_clients=NUM_CLIENTS, seed=0)
    fleets, policies, pqs = _fleets(), _policies(), _compressions()
    scenarios = FAST_SCENARIOS if fast else \
        [(f, p) for f in fleets for p in policies]
    rounds = 8 if fast else 40

    rows = []
    # historical (stacked) rows keep their names so cross-PR trajectory
    # comparisons keyed on row name stay valid; mesh cells get a suffix
    suffix = "" if executor == "stacked" else f"_{executor}"
    for fleet_name, policy_name in scenarios:
        for pq_name, pq in pqs.items():
            row, _, _ = _run_cell(data, fleets[fleet_name],
                                  policies[policy_name], pq, None,
                                  rounds, fast, executor=executor)
            rows.append(dict(
                {"name": f"{fleet_name}_{policy_name}_{pq_name}"
                         f"{suffix}"}, **row))

    if executor == "stacked":
        # the warm-start cell has no executor dimension; don't re-train it
        # in the mesh smoke when the stacked smoke already covered it
        rows.extend(run_warm_start_cell(data, fleets, policies, rounds,
                                        fast))
    if downlink:
        rows.extend(run_downlink_sweep(data, fleets, policies, rounds, fast))
    if chaos:
        rows.extend(run_chaos_cell(data, fleets, policies, rounds, fast))
    if executor == "mesh":
        rows.extend(run_executor_scaling())
    if autoscale:
        rows.extend(run_autoscale_cell(data, fleets, rounds, fast,
                                       executor=executor))
    if fleet_scale:
        rows.extend(run_fleet_scale(fast))
    # serialize before emit() strips the row keys
    write_bench_json(
        "network", rows,
        note="virtual-clock scheduler cells: measured wire bytes + "
             "simulated wall-clock per (fleet, policy, compression)")
    return rows


def run_warm_start_cell(data, fleets, policies, rounds, fast):
    """Cross-round codebook warm-start on the default (ideal, full-sync)
    fleet: steady-state rounds run PQConfig.warm_iters Lloyd iterations
    from last round's codebook and ship pq-delta codebooks. The run must
    still reach the round-0-derived target loss (acceptance criterion)."""
    pq = _compressions()["fedlite_q1152_L2"]
    row, trainer, _ = _run_cell(
        data, fleets["ideal"], policies["full_sync"], pq, None, rounds,
        fast, warm_start=True, delta_bits=8)
    assert row["reached_target"], \
        "warm-start run failed to reach the target loss"
    meta = trainer.last_trace.meta
    return [dict({"name": "warmstart_delta8_ideal_full_sync_fedlite"}, **row),
            {"name": "warmstart_claim", "us_per_call": 0.0,
             "reached_target": row["reached_target"],
             "codebook_bytes_reduction": round(
                 meta.get("codebook_bytes_reduction", 0.0), 2),
             "warm_iters": pq.effective_warm_iters,
             "cold_iters": pq.kmeans_iters}]


def run_chaos_cell(data, fleets, policies, rounds, fast):
    """The --chaos dimension: seeded fault injection (federated/faults.py)
    over fault-rate x straggler-policy cells on the lognormal fleet.

    Asserts graceful degradation (acceptance criteria):
      * the baseline-rate full-sync cell still reaches the target loss —
        quarantine + retry keep training on track;
      * downlink byte inflation from crash retries stays bounded
        (<= 1.5x the fault-free cell);
      * the chaos canary holds: contributions were quarantined, and NO
        corrupted payload ever slipped past the wire CRC undetected.
    """
    pq = _compressions()["fedlite_q1152_L2"]
    # chaos cells need headroom past the fault-free round count: voided
    # and quarantined rounds make no progress by design
    rounds = rounds * 2
    clean, _, _ = _run_cell(data, fleets["lognormal"],
                            policies["full_sync"], pq, None, rounds, fast)
    clean_dl = clean["downlink_mb_per_round"]
    plans = {
        "baseline": DEFAULT_CHAOS,
        "storm": FaultPlan(seed=0, crash_rate=0.2, corrupt_rate=0.25,
                           poison_rate=0.1, reorder_rate=0.4,
                           reorder_max_s=2.0, quorum_fraction=0.5),
    }
    rows = []
    totals = {}
    for plan_name, plan in plans.items():
        for policy_name in ("full_sync", "drop_slowest_1"):
            row, trainer, _ = _run_cell(
                data, fleets["lognormal"], policies[policy_name], pq, None,
                rounds, fast, fault_plan=plan)
            ft = trainer.last_trace.fault_totals()
            totals[(plan_name, policy_name)] = (row, ft)
            # the run-health signals the SLO monitors grade, as columns:
            # how much extra downlink the crash retries cost, and what
            # fraction of admitted contributions the server quarantined
            health = slo.trace_signals(trainer.last_trace)
            rows.append(dict(
                {"name": f"chaos_{plan_name}_{policy_name}_fedlite"}, **row,
                crashes=ft.get("crashes", 0),
                retries=ft.get("retries", 0),
                crash_dropped=ft.get("crash_dropped", 0),
                quarantined=ft.get("quarantined", 0),
                rounds_voided=ft.get("round_voided", 0),
                corrupt_undetected=ft.get("corrupt_undetected", 0),
                retry_byte_overhead=round(health["retry_byte_overhead"], 4),
                quarantine_rate=round(health["quarantine_rate"], 4),
                downlink_inflation=round(
                    row["downlink_mb_per_round"] / max(clean_dl, 1e-12), 3)))
    base_row, base_ft = totals[("baseline", "full_sync")]
    assert base_row["reached_target"], \
        "baseline-rate chaos run failed to reach the target loss"
    inflation = base_row["downlink_mb_per_round"] / max(clean_dl, 1e-12)
    assert inflation <= 1.5, \
        f"retry downlink inflation {inflation:.2f}x exceeds the 1.5x bound"
    all_ft = [ft for _, ft in totals.values()]
    assert sum(ft.get("quarantined", 0) for ft in all_ft) > 0, \
        "chaos sweep never exercised the quarantine path"
    assert all(ft.get("corrupt_undetected", 0) == 0 for ft in all_ft), \
        "a corrupted payload slipped past the wire CRC undetected"
    rows.append({"name": "chaos_claim", "us_per_call": 0.0,
                 "reached_target": base_row["reached_target"],
                 "baseline_downlink_inflation": round(inflation, 3),
                 "quarantined_total": sum(ft.get("quarantined", 0)
                                          for ft in all_ft),
                 "corrupt_undetected_total": 0})
    return rows


def run_downlink_sweep(data, fleets, policies, rounds, fast):
    """The --downlink dimension: dense vs chained gradient codec on the
    default (ideal, full-sync) fleet, FedLite uplink. The compressed cell
    must cut measured downlink bytes >= 8x (acceptance criterion)."""
    pq = _compressions()["fedlite_q1152_L2"]
    rows = []
    per_round = {}
    for dl_name, dl in [("dense", None), ("topk0.1_sq8", DOWNLINK_CHAIN)]:
        row, trainer, state = _run_cell(
            data, fleets["ideal"], policies["full_sync"], pq, dl,
            rounds, fast)
        per_round[dl_name] = row["downlink_mb_per_round"]
        rows.append(dict(
            {"name": f"downlink_{dl_name}_ideal_full_sync_fedlite"}, **row))
    reduction = per_round["dense"] / max(per_round["topk0.1_sq8"], 1e-12)
    assert reduction >= 8.0, \
        f"measured downlink reduction {reduction:.2f}x below the 8x bar"
    assert rows[-1]["reached_target"], \
        "compressed-downlink run failed to reach the target loss"
    rows.append({
        "name": "downlink_claim",
        "us_per_call": 0.0,
        "measured_downlink_reduction": round(reduction, 1),
        "compressed_reached_target": rows[-1]["reached_target"],
    })
    return rows


# ---------------------------------------------------------------------------
# executor dimension: cohort-execute wall-clock scaling with shard count
# ---------------------------------------------------------------------------

def _scaling_leg(shards: int):
    """One leg of the shard-scaling cell (runs inside its own child
    process, jax initialized with exactly ``shards`` forced host devices):
    time the cohort-execute phase — one synchronous server update over a
    fixed 8-client cohort through the mesh executor — and print the
    min-of-3 wall-clock through the marker line."""
    cohort, batch = 8, 32
    data = make_federated_image_data(num_clients=cohort, seed=0)
    pq = PQConfig(num_subvectors=288, num_clusters=8, kmeans_iters=6)
    model = FemnistCNN(pq=pq, lam=1e-4, client_batch=batch)
    trainer = FederatedTrainer(
        model, sgd(10 ** -1.5), data, cohort=cohort, client_batch=batch,
        executor=f"mesh(shards={shards})")
    state = trainer.init_state(jax.random.PRNGKey(0))
    parts = [trainer.client_batch_for(c, jax.random.PRNGKey(1))
             for c in range(cohort)]
    ex = trainer.executor
    jax.block_until_ready(ex.execute(state, parts)[0].params)  # compile
    reps = []
    for _ in range(3):
        t0 = time.perf_counter()
        out, _ = ex.execute(state, parts)
        jax.block_until_ready(out.params)
        reps.append(time.perf_counter() - t0)
    print(_SCALING_MARKER + json.dumps({"shards": shards,
                                        "seconds": min(reps)}))


def run_executor_scaling():
    """Cohort-execute wall-clock scaling with shard count.

    Methodology: one child process per shard count with ONE CPU CORE PER
    SHARD (``taskset -c 0..k-1`` where available) and exactly ``k`` forced
    host devices — the CPU emulation of one accelerator per shard, so the
    1-shard baseline cannot borrow the other shards' cores through
    intra-op threading. The asserted bar anchors at the largest shard
    count the host can physically parallelize:

      * >= 4 cores (the CI runner): 4-shard speedup >= 1.5x — the
        acceptance bar.
      * 2-3 cores: 2-shard speedup >= 1.15x. jax's CPU client overlaps
        multi-device execution only partially (measured ~1.3-1.5x of the
        2x ideal on 2 dedicated cores), so the 2-core bar is calibrated to
        that runtime ceiling, not to the mesh design.
      * 1 core: rows only, nothing to assert.
    """
    # the cores THIS process may run on (affinity/cgroup mask), not the
    # host's total — a container limited to 2 of 16 cores must anchor at 2
    try:
        core_ids = sorted(os.sched_getaffinity(0))
    except AttributeError:   # non-Linux: no affinity API, no taskset either
        core_ids = list(range(os.cpu_count() or 1))
    cores = len(core_ids)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    has_taskset = subprocess.run(["which", "taskset"],
                                 capture_output=True).returncode == 0
    times = {}
    # two interleaved passes, min per shard count: shared-host noise drifts
    # over minutes, and min-statistics across interleaved samples converge
    # on the quiet-machine value instead of whichever leg got unlucky
    for _ in range(2):
        for shards in (1, 2, 4):
            # host-device legs by design: pinned to the CPU so a child can
            # never contend for a chip this parent process already holds
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={shards}"
            cmd = [sys.executable, "-m", "benchmarks.bench_network",
                   "--_scaling-leg", str(shards)]
            if has_taskset:
                cmd = ["taskset", "-c", ",".join(
                    str(c) for c in core_ids[:min(shards, cores)])] + cmd
            proc = subprocess.run(cmd, env=env, check=True,
                                  capture_output=True, text=True, cwd=repo)
            for line in proc.stdout.splitlines():
                if line.startswith(_SCALING_MARKER):
                    t = json.loads(line[len(_SCALING_MARKER):])["seconds"]
                    times[shards] = min(times.get(shards, t), t)
    rows = [{"name": f"execute_scaling_shards{s}",
             "us_per_call": round(t * 1e6, 1),
             "ms_per_round": round(t * 1e3, 1),
             "cores_used": min(s, cores),
             "speedup_vs_1shard": round(times[1] / t, 2)}
            for s, t in sorted(times.items())]
    anchor = min(4, cores) if cores >= 2 else 1
    if anchor >= 2:
        anchor = 4 if anchor >= 4 else 2
        bar = 1.5 if anchor == 4 else 1.15
        speedup = times[1] / times[anchor]
        assert speedup >= bar, \
            f"mesh cohort-execute speedup {speedup:.2f}x at {anchor} " \
            f"shards ({anchor} dedicated cores) below the {bar}x bar"
        rows.append({"name": "execute_scaling_claim", "us_per_call": 0.0,
                     "anchor_shards": anchor, "host_cores": cores,
                     "speedup": round(speedup, 2), "bar": bar})
    return rows


# ---------------------------------------------------------------------------
# fleet-scale dimension: the vectorized scheduler core at 10^5-10^6 clients
# ---------------------------------------------------------------------------

# wall-clock budget for one simulated round of the 1M-client / 10k-cohort
# vector cell (measured ~0.02 s on the CI-class host; the bar is generous
# because it must hold on loaded shared runners)
FLEET_SCALE_BUDGET_S = 5.0


def _peak_rss_mb() -> float:
    """Peak resident set of this process in MB (0.0 where unavailable)."""
    try:
        import resource
    except ImportError:        # non-POSIX
        return 0.0
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _fleet_scale_cell(fleet, cohort, backend, rounds, topology=None,
                      seed=7):
    """Time ``rounds`` scheduler rounds with a stub execute.

    The cohort sampler is seeded per round (identical across backends) so
    the heapq/vector pair in a cell runs the exact same cohorts and their
    traces can be compared record-for-record.
    """
    n = len(fleet)

    def sample_cohort(rd):
        return np.random.default_rng((seed, rd)).choice(n, cohort,
                                                        replace=False)

    sched = Scheduler(fleet=fleet, policy=DropSlowestK(max(cohort // 10, 1)),
                      client_step_seconds=1.0, seed=seed, backend=backend,
                      topology=topology)
    t0 = time.perf_counter()
    trace = sched.run(rounds, sample_cohort=sample_cohort,
                      uplink_bytes=81920, downlink_bytes=262144,
                      execute=lambda rd, parts, weights: {},
                      wire_kinds=("pq", "dense"))
    wall = (time.perf_counter() - t0) / rounds
    return wall, trace


def run_fleet_scale(fast: bool = True):
    """The ``--fleet-scale`` dimension: scheduler-core scaling cells.

    Pure scheduler throughput (stub execute — the executor's compute is
    the other benchmarks' business): lognormal fleets at 10^5 and 10^6
    clients, 1%-of-fleet cohorts, both backends where affordable. The
    10^6 cells run through a 32-edge `TwoTierTopology`, so their rows
    carry the per-tier measured bytes. Asserted acceptance criteria: the
    1M/10k vector cell finishes a round inside ``FLEET_SCALE_BUDGET_S``
    with both tier ledger entries present and nonzero, and the heapq and
    vector traces of every cell match record-for-record (bitwise parity
    at fleet scale, not just on the small test fleets).
    """
    rounds = 3 if fast else 8
    rows = []
    traces = {}
    cells = [
        (100_000, 1_000, None),
        (1_000_000, 10_000, TwoTierTopology(num_edges=32, seed=0)),
    ]
    for clients, cohort, topo in cells:
        setup0 = time.perf_counter()
        fleet = lognormal_fleet(clients, dropout_prob=0.01, seed=1)
        if topo is not None:
            topo.ensure(clients)       # k-means once, shared by backends
        setup_s = time.perf_counter() - setup0
        for backend in ("heapq", "vector"):
            wall, trace = _fleet_scale_cell(fleet, cohort, backend, rounds,
                                            topology=topo)
            traces[(clients, backend)] = trace
            tiers = trace.tier_totals()
            row = {
                "name": f"fleet_{clients}c_{cohort}cohort_{backend}",
                "us_per_call": round(wall * 1e6, 1),
                "s_per_round": round(wall, 4),
                "clients": clients,
                "cohort": cohort,
                "rounds": rounds,
                "sim_seconds_per_round": round(
                    trace.simulated_seconds / rounds, 2),
                "peak_rss_mb": round(_peak_rss_mb(), 1),
                "setup_s": round(setup_s, 2),
            }
            if topo is not None:
                row["edge_uplink_bytes"] = tiers.get("edge_uplink", 0)
                row["server_uplink_bytes"] = tiers.get("server_uplink", 0)
            rows.append(row)
        # bitwise parity at fleet scale: same cohorts, same records,
        # and the flight recorder saw the identical contribution set
        assert traces[(clients, "heapq")].records \
            == traces[(clients, "vector")].records, \
            f"backend traces diverge at {clients} clients"
        assert traces[(clients, "heapq")].flights \
            == traces[(clients, "vector")].flights, \
            f"backend flight frames diverge at {clients} clients"

    # flights-overhead A/B on the headline cell: re-run the 1M vector
    # cell (fleet/cohort/topo still bound from the last loop iteration)
    # off/on back-to-back. Both legs are warm — the cells loop above
    # already paid the lazy topology clustering and allocator warmup, so
    # neither leg carries setup cost the other doesn't — and the min of
    # two interleaved passes per leg damps shared-host jitter. Recording
    # must cost <= 15% wall-clock at O(cohort) per round.
    wall_off = wall_on = float("inf")
    for _ in range(2):
        prev = flightlib.set_flights(False)
        try:
            w, _ = _fleet_scale_cell(fleet, cohort, "vector", rounds,
                                     topology=topo)
        finally:
            flightlib.set_flights(prev)
        wall_off = min(wall_off, w)
        w, _ = _fleet_scale_cell(fleet, cohort, "vector", rounds,
                                 topology=topo)
        wall_on = min(wall_on, w)

    # the headline acceptance criteria: 1M clients, 10k cohort, vector
    big = next(r for r in rows
               if r["name"] == "fleet_1000000c_10000cohort_vector")
    assert big["s_per_round"] <= FLEET_SCALE_BUDGET_S, \
        f"1M-client vector round took {big['s_per_round']:.2f}s, over " \
        f"the {FLEET_SCALE_BUDGET_S:g}s budget"
    assert big["edge_uplink_bytes"] > 0 and big["server_uplink_bytes"] > 0, \
        f"two-tier ledger entries missing from the 1M cell: {big}"
    assert big["server_uplink_bytes"] < big["edge_uplink_bytes"], \
        "edge pre-combination should shrink the server tier below the " \
        "edge tier"
    # 5 ms absolute slack so a fast host does not turn scheduler jitter
    # into a failed relative bound
    overhead = wall_on / max(wall_off, 1e-9)
    assert wall_on <= max(1.15 * wall_off, wall_off + 0.005), \
        f"flight recording costs {overhead:.2f}x wall-clock on the " \
        f"1M-client vector cell (budget 1.15x)"
    rows.append({
        "name": "fleet_flights_overhead", "us_per_call": 0.0,
        "s_per_round_flights_on": round(wall_on, 4),
        "s_per_round_flights_off": round(wall_off, 4),
        "overhead_x": round(overhead, 3),
    })
    rows.append({
        "name": "fleet_scale_claim", "us_per_call": 0.0,
        "s_per_round_1m_vector": big["s_per_round"],
        "budget_s": FLEET_SCALE_BUDGET_S,
        "speedup_vs_heapq": round(
            next(r for r in rows
                 if r["name"] == "fleet_1000000c_10000cohort_heapq")
            ["s_per_round"] / max(big["s_per_round"], 1e-9), 1),
        "server_vs_edge_bytes": round(
            big["server_uplink_bytes"] / big["edge_uplink_bytes"], 4),
    })
    return rows


# ---------------------------------------------------------------------------
# autoscale dimension: trace-driven (cohort, policy, codec) control
# ---------------------------------------------------------------------------

def run_autoscale_cell(data, fleets, rounds, fast, executor="stacked"):
    """One training run on the lognormal straggler fleet driven by the
    `TraceAutoscaler`, next to the static (cohort, policy) cells it picks
    between. Asserts (acceptance criterion) that the autoscaled run reaches
    the round-0-derived target loss with no more uplink bytes than the best
    static cell."""
    fleet = fleets["lognormal"]
    pq = _compressions()["fedlite_q1152_L2"]
    interval = 4 if fast else 8
    factor = 0.93 if fast else 0.9
    rows = []

    static_bytes = {}
    for pname in ("full_sync", "drop_slowest_1", "deadline_6s"):
        row, _, _ = _run_cell(data, fleet, _policies()[pname], pq, None,
                              rounds, fast, executor=executor)
        static_bytes[pname] = row["uplink_mb_to_target"]
        rows.append(dict({"name": f"autoscale_static_{pname}"}, **row))

    def make_trainer(plan, seg):
        client_batch = CLIENT_BATCH if executor != "stacked" else 0
        model = FemnistCNN(pq=pq, lam=1e-4, client_batch=client_batch)
        return FederatedTrainer(
            model, sgd(10 ** -1.5), data, cohort=plan.cohort,
            client_batch=CLIENT_BATCH, quantize=True, fleet=fleet,
            policy=make_policy(plan.policy),
            downlink_compressor=plan.downlink, seed=seg, executor=executor)

    # max_cohort clamps at the population: sample_clients would silently
    # cap larger cohorts, and the plan rows must report what actually ran
    controller = TraceAutoscaler(window=interval, tail_hi=1.5,
                                 max_cohort=NUM_CLIENTS)
    out = autoscale_run(make_trainer, AutoscalePlan(cohort=COHORT), rounds,
                        jax.random.PRNGKey(0), controller=controller,
                        interval=interval)
    losses = [h["loss"] for h in out["history"] if "loss" in h]
    target = factor * losses[0]
    total = 0
    auto_bytes = None
    for h in out["history"]:
        total += h.get("uplink_bytes", 0)
        if "loss" in h and h["loss"] <= target:
            auto_bytes = total
            break
    assert auto_bytes is not None, \
        "autoscaled run failed to reach the target loss"
    reached = [b for b in static_bytes.values() if b is not None]
    assert reached, \
        f"no static cell reached the target loss: {static_bytes}"
    best_static = min(reached)
    auto_mb = auto_bytes / 1e6
    assert auto_mb <= best_static + 1e-9, \
        f"autoscaled run used {auto_mb:.4f} MB to target vs best static " \
        f"{best_static:.4f} MB"
    for i, plan in enumerate(out["plans"]):
        rows.append({"name": f"autoscale_plan_{i}", "us_per_call": 0.0,
                     "cohort": plan.cohort, "policy": plan.policy,
                     "downlink": plan.downlink or "dense",
                     "reason": plan.reason.replace(",", ";")})
    rows.append({
        "name": "autoscale_claim", "us_per_call": 0.0,
        "uplink_mb_to_target": round(auto_mb, 4),
        "best_static_mb_to_target": round(best_static, 4),
        "plans_applied": len(out["plans"]),
        "final_loss": round(losses[-1], 4),
        "sim_seconds": round(out["simulated_seconds"], 2),
    })
    return rows


def main(fast: bool = True, downlink: bool = False,
         executor: str = "stacked", autoscale: bool = False,
         fleet_scale: bool = False, chaos: bool = False,
         emit_trace: str = None, perfetto: str = None):
    if executor == "mesh" and len(jax.devices()) < 2 \
            and not os.environ.get("_BENCH_MESH_CHILD"):
        # re-exec with forced host devices so the mesh cells see a real
        # mesh (the trace/obs flags ride along through sys.argv); pinned to
        # the CPU — this parent already initialised JAX and may hold a chip
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4 " \
            + env.get("XLA_FLAGS", "")
        env["_BENCH_MESH_CHILD"] = "1"
        raise SystemExit(subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_network",
             *sys.argv[1:]], env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ).returncode)
    if emit_trace:
        obs.configure(run="bench_network", meta={
            "suite": "network_tradeoff", "fast": fast, "downlink": downlink,
            "executor": executor, "autoscale": autoscale,
            "fleet_scale": fleet_scale, "chaos": chaos,
            "jax_backend": jax.default_backend()})
    emit(run(fast, downlink=downlink, executor=executor,
             autoscale=autoscale, fleet_scale=fleet_scale, chaos=chaos),
         "network_tradeoff")
    recorder = obs.shutdown()
    if emit_trace and recorder is not None:
        n = recorder.write_jsonl(emit_trace)
        pf = perfetto or (emit_trace[:-len(".jsonl")] + ".perfetto.json"
                          if emit_trace.endswith(".jsonl")
                          else emit_trace + ".perfetto.json")
        recorder.write_perfetto(pf)
        # stdout is the CSV channel (and the scaling-leg marker); report
        # the trace artifacts on stderr
        print(f"wrote {n} events to {emit_trace}; perfetto trace at {pf}\n"
              f"inspect with: python -m repro.obs {emit_trace}",
              file=sys.stderr)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--downlink", action="store_true",
                    help="sweep the downlink gradient codec too")
    ap.add_argument("--executor", choices=["stacked", "mesh"],
                    default="stacked",
                    help="cohort execution engine for the scenario cells; "
                         "mesh adds the shard-scaling cell")
    ap.add_argument("--autoscale", action="store_true",
                    help="run the trace-driven autoscaler cell")
    ap.add_argument("--fleet-scale", action="store_true",
                    help="run the 10^5/10^6-client scheduler-core scaling "
                         "cells (wall-clock budget + backend parity "
                         "asserted)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-injection sweep (fault rate x "
                         "policy; graceful-degradation + canary "
                         "assertions)")
    ap.add_argument("--emit-trace", nargs="?",
                    const="__default__", default=None,
                    metavar="PATH",
                    help="record an obs telemetry trace of the run and "
                         "write it as JSONL (default "
                         "benchmarks/out/BENCH_network_trace.jsonl — "
                         "gitignored scratch); a Perfetto-loadable twin "
                         "is written next to it")
    ap.add_argument("--perfetto", default=None, metavar="PATH",
                    help="where to write the Perfetto trace_event JSON "
                         "(default: the --emit-trace path with .jsonl "
                         "swapped for .perfetto.json)")
    ap.add_argument("--_scaling-leg", type=int, default=0,
                    dest="scaling_leg", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.emit_trace == "__default__":
        args.emit_trace = str(out_path("BENCH_network_trace.jsonl"))
    if args.scaling_leg:
        _scaling_leg(args.scaling_leg)
    else:
        main(fast=not args.full, downlink=args.downlink,
             executor=args.executor, autoscale=args.autoscale,
             fleet_scale=args.fleet_scale, chaos=args.chaos,
             emit_trace=args.emit_trace, perfetto=args.perfetto)
