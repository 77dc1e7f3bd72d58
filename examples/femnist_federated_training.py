"""End-to-end driver: federated FedLite training on the paper's FEMNIST task.

Trains the paper's CNN (client: 2 conv layers; server: 2 dense layers, cut
at d=9216) for a few hundred rounds with cohort sampling, grouped-PQ uplink
compression and gradient correction, evaluating accuracy and cumulative
communication as it goes. Compares against the SplitFed baseline.

    PYTHONPATH=src python examples/femnist_federated_training.py \
        --rounds 300 --q 1152 --clusters 2 --lam 1e-4

Heterogeneous-fleet variant: dispatch the same training through the
virtual-clock scheduler over a realistic fleet and a straggler policy,
reporting measured wire bytes and simulated wall-clock:

    PYTHONPATH=src python examples/femnist_federated_training.py \
        --rounds 100 --fleet mobile --policy deadline

Downlink-compressed variant (the cut-layer gradient message through a
`core/compressors.py` codec instead of dense fp32):

    PYTHONPATH=src python examples/femnist_federated_training.py \
        --rounds 100 --fleet lognormal \
        --downlink "chain:topk(k=0.1)+scalarq(bits=8)"

Mesh-parallel cohorts (the `federated/executor.py` engine): shard each
round's client forward/backward over the ``clients`` device axis instead
of stacking on one device. On CPU, force a few host devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python examples/femnist_federated_training.py \
        --rounds 100 --fleet lognormal --executor mesh

Trace-driven autoscaling (the `federated/autoscale.py` controller): run in
segments, letting the observed straggler tail / drop rate / loss slope
move (cohort, policy, downlink codec) between segments:

    PYTHONPATH=src python examples/femnist_federated_training.py \
        --rounds 100 --fleet mobile --autoscale

Telemetry: ``--emit-trace [PATH]`` records the run through the
`repro.obs` recorder — scheduler rounds on host AND virtual-clock lanes,
executor and wire spans, the per-round byte ledger — then writes an
append-only JSONL event log (default ``femnist_trace.jsonl``) plus a
Perfetto-loadable twin (``--perfetto PATH`` to relocate; load at
https://ui.perfetto.dev). Summarize with ``python -m repro.obs <jsonl>``:

    PYTHONPATH=src python examples/femnist_federated_training.py \
        --rounds 100 --fleet lognormal --emit-trace
    PYTHONPATH=src python -m repro.obs femnist_trace.jsonl --target 2.0

Inspector cookbook — everything below works on any ``--emit-trace`` log
(the run-forensics layer is always recorded; add ``--chaos`` to make the
flight lifecycles interesting):

    # round table, duration percentiles, byte ledger, time-to-target
    python -m repro.obs femnist_trace.jsonl --target 2.0
    # the same document as JSON, for scripting/jq
    python -m repro.obs femnist_trace.jsonl --json | jq .ledger
    # per-round fault ledger: crashes, retries, quarantines, re-homes
    python -m repro.obs femnist_trace.jsonl --faults
    # grade the run against the default SLOs + one ad-hoc rule
    python -m repro.obs femnist_trace.jsonl --health
    python -m repro.obs femnist_trace.jsonl --slo "drop_rate<=0.3@50"
    # reconstruct one contribution's causal lifecycle end-to-end:
    # sampled -> placed -> uplink (retries/re-homes) -> screening -> state
    python -m repro.obs femnist_trace.jsonl --flight r3-c17-s5
    # ...or every recorded exemplar flight for a client id
    python -m repro.obs femnist_trace.jsonl --flight 17

In the Perfetto UI the exemplar flights render as flow arrows linking
each contribution's uplink span (virtual-clock lane) to the server
screening span, so one straggling or quarantined update is traceable by
eye across lanes.
"""

import argparse
import time

import jax

from repro import obs
from repro.checkpointing import save_checkpoint
from repro.core.quantizer import PQConfig
from repro.core.split import tree_bits
from repro.data.synthetic import make_federated_image_data
from repro.launch.cache import enable_compile_cache
from repro.federated import (DEFAULT_CHAOS, AsyncBuffer, Deadline,
                             DropSlowestK, FederatedTrainer, FullSync,
                             lognormal_fleet, mobile_fleet)
from repro.models.paper_models import FemnistCNN
from repro.optim import sgd

FLEETS = {
    "ideal": lambda n: None,  # trainer default: identical ideal clients
    "lognormal": lambda n: lognormal_fleet(n, median_uplink_bps=2e6, seed=0),
    "mobile": lambda n: mobile_fleet(n, flaky_fraction=0.3, seed=0),
}
POLICIES = {
    "full_sync": FullSync,
    "drop2": lambda: DropSlowestK(2),
    "deadline": lambda: Deadline(6.0),
    "async": lambda: AsyncBuffer(4),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--q", type=int, default=1152)
    ap.add_argument("--clusters", type=int, default=2)
    ap.add_argument("--lam", type=float, default=1e-4)
    ap.add_argument("--cohort", type=int, default=10)
    ap.add_argument("--client-batch", type=int, default=20)
    ap.add_argument("--baseline", action="store_true",
                    help="run SplitFed (no compression) instead")
    ap.add_argument("--fleet", choices=sorted(FLEETS), default="ideal",
                    help="client population for the virtual-clock scheduler")
    ap.add_argument("--policy", choices=sorted(POLICIES), default="full_sync",
                    help="round participation policy")
    ap.add_argument("--downlink", default=None, metavar="SPEC",
                    help="downlink gradient codec spec, e.g. "
                         "'chain:topk(k=0.1)+scalarq(bits=8)'")
    ap.add_argument("--warm-start", action="store_true",
                    help="carry PQ codebooks across rounds (half the Lloyd "
                         "iterations on steady-state rounds)")
    ap.add_argument("--delta-bits", type=int, default=0,
                    help="ship codebooks as pq-delta wire payloads at this "
                         "many bits per delta (0 = fresh fp16 codebooks)")
    ap.add_argument("--executor", choices=["stacked", "mesh"],
                    default="stacked",
                    help="cohort execution engine: stacked single-device "
                         "path or shard_map over the `clients` device axis "
                         "(mesh needs >1 device: set XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N on CPU)")
    ap.add_argument("--autoscale", action="store_true",
                    help="drive the run with the trace-driven autoscaler "
                         "(re-plans cohort/policy/downlink every 8 rounds)")
    ap.add_argument("--chaos", action="store_true",
                    help="arm DEFAULT_CHAOS fault injection (crashes, "
                         "payload corruption, poisoning) so the recorded "
                         "flight lifecycles exercise retries/quarantine; "
                         "the SLO monitor grades the finished run")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--emit-trace", nargs="?", const="femnist_trace.jsonl",
                    default=None, metavar="PATH",
                    help="record obs telemetry (spans on host + virtual "
                         "lanes, byte ledger) and write it as JSONL; "
                         "summarize with `python -m repro.obs PATH`")
    ap.add_argument("--perfetto", default=None, metavar="PATH",
                    help="Perfetto trace_event JSON output (default: the "
                         "--emit-trace path with .jsonl swapped for "
                         ".perfetto.json)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.emit_trace:
        obs.configure(run="femnist_example", meta={
            "rounds": args.rounds, "fleet": args.fleet,
            "policy": args.policy, "executor": args.executor,
            "autoscale": args.autoscale, "baseline": args.baseline})

    num_clients = 64
    if args.executor == "mesh" and len(jax.devices()) < 2:
        raise SystemExit(
            "--executor mesh needs a multi-device mesh; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4 before "
            "launching")
    data = make_federated_image_data(num_clients=num_clients, seed=0)
    pq = None if args.baseline else PQConfig(
        num_subvectors=args.q, num_clusters=args.clusters, kmeans_iters=5)
    model = FemnistCNN(pq=pq, lam=args.lam, client_batch=args.client_batch)

    def build_trainer(cohort, policy, downlink, seed=0):
        return FederatedTrainer(model, sgd(10 ** -1.5), data, cohort=cohort,
                                client_batch=args.client_batch,
                                quantize=not args.baseline,
                                fleet=FLEETS[args.fleet](num_clients),
                                policy=policy, downlink_compressor=downlink,
                                warm_start=args.warm_start,
                                codebook_delta_bits=args.delta_bits or None,
                                fault_plan=DEFAULT_CHAOS if args.chaos
                                else None,
                                slo_monitor=obs.HealthMonitor()
                                if args.emit_trace else None,
                                seed=seed, executor=args.executor)

    eval_batch = data.eval_batch(jax.random.PRNGKey(99), 512)
    heterogeneous = args.fleet != "ideal" or args.policy != "full_sync" \
        or args.downlink is not None or args.warm_start \
        or bool(args.delta_bits) or args.executor != "stacked"

    if args.autoscale:
        from repro.federated import (AutoscalePlan, TraceAutoscaler,
                                     autoscale_run, make_policy)
        # seed the plan with every CLI knob the controller may later move
        policy_specs = {"full_sync": "full_sync", "drop2": "drop_slowest:2",
                        "deadline": "deadline:6.0", "async": "async:4"}
        plan0 = AutoscalePlan(cohort=args.cohort,
                              policy=policy_specs[args.policy],
                              downlink=args.downlink)

        def make_trainer(plan, seg):
            return build_trainer(plan.cohort, make_policy(plan.policy),
                                 plan.downlink, seed=seg)

        t0 = time.time()
        out = autoscale_run(
            make_trainer, plan0, args.rounds, jax.random.PRNGKey(0),
            controller=TraceAutoscaler(window=8, max_cohort=num_clients),
            interval=8)
        state = out["state"]
        acc = float(model.accuracy(state.params, eval_batch))
        print(f"autoscaled run: {args.rounds} rounds, "
              f"{len(out['plans'])} plan(s), acc={acc:.3f} "
              f"({time.time() - t0:.0f}s real)")
        for i, plan in enumerate(out["plans"]):
            print(f"  plan {i}: cohort={plan.cohort} policy={plan.policy} "
                  f"downlink={plan.downlink or 'dense'}  [{plan.reason}]")
        print(f"  simulated wall-clock : {out['simulated_seconds']:10.1f} s")
        print(f"  measured uplink      : {out['uplink_bytes'] / 1e6:10.2f} MB")
        print(f"  measured downlink    : "
              f"{out['downlink_bytes'] / 1e6:10.2f} MB")
        losses = [h["loss"] for h in out["history"] if "loss" in h]
        if losses:
            print(f"  loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    elif heterogeneous:
        # scheduled run: measured wire bytes + simulated wall-clock per round
        trainer = build_trainer(args.cohort, POLICIES[args.policy](),
                                args.downlink)
        t0 = time.time()
        state, hist = trainer.run(args.rounds, jax.random.PRNGKey(0))
        trace = trainer.last_trace
        acc = float(model.accuracy(state.params, eval_batch))
        s = trace.summary()
        print(f"fleet={args.fleet} policy={args.policy}  "
              f"rounds={s['rounds']}  acc={acc:.3f}  "
              f"({time.time() - t0:.0f}s real)")
        print(f"  simulated wall-clock : {s['simulated_seconds']:10.1f} s")
        print(f"  measured uplink      : {s['uplink_bytes'] / 1e6:10.2f} MB "
              f"({s['uplink_bytes_per_round'] / 1e6:.4f} MB/round)")
        print(f"  measured downlink    : {s['downlink_bytes'] / 1e6:10.2f} MB")
        print(f"  stragglers dropped   : {s['stragglers_dropped']:10d}")
        if s["mean_staleness"]:
            print(f"  mean staleness       : {s['mean_staleness']:10.2f}")
        losses = [h["loss"] for h in hist if "loss" in h]
        if losses:
            print(f"  loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    else:
        # ideal synchronous loop with periodic eval (the paper's simulation);
        # analytic uplink accounting at the params' native phi (fp32: 32-bit)
        trainer = build_trainer(args.cohort, POLICIES[args.policy](),
                                args.downlink)
        state = trainer.init_state(jax.random.PRNGKey(0))
        client_bits = tree_bits(state.params["client"])
        act_bits = 32 * 9216 * args.client_batch
        per_round = client_bits + (pq.message_bits(args.client_batch, 9216,
                                                   phi_bits=32)
                                   if pq else act_bits)
        t0 = time.time()
        for r in range(args.rounds):
            state, metrics = trainer.round(state, jax.random.fold_in(
                jax.random.PRNGKey(1), r))
            if r % 25 == 0 or r == args.rounds - 1:
                acc = float(model.accuracy(state.params, eval_batch))
                mb = per_round * args.cohort * (r + 1) / 8e6
                print(f"round {r:4d}  loss={float(metrics['loss']):.4f}  "
                      f"acc={acc:.3f}  uplink={mb:8.1f} MB  "
                      f"({time.time() - t0:.0f}s)")
    if args.ckpt:
        save_checkpoint(args.ckpt, args.rounds, state.params)
        print(f"saved params to {args.ckpt}")
    if pq:
        print(f"activation compression (phi=32): "
              f"{pq.compression_ratio(args.client_batch, 9216, phi_bits=32):.0f}x")
    recorder = obs.shutdown()
    if args.emit_trace and recorder is not None:
        n = recorder.write_jsonl(args.emit_trace)
        pf = args.perfetto or (
            args.emit_trace[:-len(".jsonl")] + ".perfetto.json"
            if args.emit_trace.endswith(".jsonl")
            else args.emit_trace + ".perfetto.json")
        recorder.write_perfetto(pf)
        print(f"wrote {n} telemetry events to {args.emit_trace}; "
              f"perfetto trace at {pf}")
        print(f"inspect with: python -m repro.obs {args.emit_trace}")


if __name__ == "__main__":
    main()
