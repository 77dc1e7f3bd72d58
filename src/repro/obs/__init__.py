"""Run-wide telemetry: spans, sync-free in-jit metrics, exporters, inspector.

Three pillars, one event log:

  spans.py   — `span`/`virtual_span`/`event`/`instrument` record host
               wall-clock and the scheduler's simulated clock as parallel
               lanes into a module-level `Recorder` (`configure` installs
               one). While a ``jax.profiler`` session collects, `span` and
               `instrument` also emit a ``TraceAnnotation`` of the span's
               name, on the device trace's clock. Everything is a no-op
               otherwise, and inside jit tracing. The hot path is
               permanently instrumented: ``trainer.round``, executor
               execute/place/dispatch, scheduler rounds, wire
               encode/decode, checkpoint save/restore.
  metrics.py — `MetricsBuffer`: metrics are plain arrays computed inside
               jitted steps that ride the existing aux pytrees; the host
               records them without looking and flushes the whole run with
               exactly one ``jax.device_get`` — instrumentation adds zero
               host syncs.
  export.py  — append-only JSONL event logs and Chrome/Perfetto
               ``trace_event`` JSON (host and virtual lanes render as two
               processes with per-category tracks).
  inspect.py — ``python -m repro.obs <run.jsonl>``: round tables,
               duration percentiles, the per-direction/per-wire-kind byte
               ledger, bytes/time-to-target, ``--health`` SLO grading and
               ``--flight`` lifecycle drill-down.
  flight.py  — level 2: the contribution flight recorder. Every cohort
               contribution gets a stable ``flight_id`` and a recorded
               causal lifecycle (sampled → placed → uplink →
               retry/re-home/quarantine/drop → aggregate) as column-array
               `FlightFrame`s on ``Trace.flights``, emitted into the
               event log as per-update rollups + reservoir exemplars.
  slo.py     — declarative windowed SLO rules over trace reductions;
               violations become structured ``slo_violation`` events.
  schema.py  — the obs event-name registry fedlint's ``orphan-obs-event``
               pass checks `repro/federated/` emissions against.

Typical wiring (what ``bench_network.py --emit-trace`` and the femnist
example's ``--emit-trace`` flag do):

    from repro import obs
    obs.configure(run="bench", meta={"fleet": "lognormal"})
    ...  # run training; Scheduler/executor/wire spans record themselves
    rec = obs.shutdown()
    rec.write_jsonl("run.jsonl")
    rec.write_perfetto("run.perfetto.json")
"""

from repro.obs.export import (
    jsonable,
    read_jsonl,
    read_jsonl_tolerant,
    to_perfetto,
    write_jsonl,
    write_perfetto,
)
from repro.obs.flight import (
    FlightFrame,
    flights_enabled,
    log_frames,
    set_flights,
)
from repro.obs.metrics import MetricsBuffer
from repro.obs.slo import (
    DEFAULT_SLOS,
    HealthMonitor,
    SloRule,
    parse_rule,
)
from repro.obs.spans import (
    Recorder,
    configure,
    current,
    enabled,
    event,
    instrument,
    shutdown,
    span,
    virtual_span,
)


def log_trace(trace, run=None) -> None:
    """Append a finished `repro.federated.Trace` to the event log.

    Each `RoundRecord` becomes a ``type: "round"`` event on the virtual
    lane carrying participants, per-direction bytes, the wire-kind ledger
    and the round's (already host-side) metrics; the run's meta + summary
    close it out as a ``type: "run"`` event. Duck-typed on the record
    fields so this package never imports the federated layer."""
    rec = current()
    if rec is None:
        return
    for r in trace:
        rec.append({
            "type": "round", "lane": "virtual", "cat": "rounds",
            "name": f"round {r.round}",
            "t0": float(r.t_start), "t1": float(r.t_end),
            "args": {"round": r.round,
                     "participants": len(r.participants),
                     "dropped": len(r.dropped),
                     "uplink_bytes": r.uplink_bytes,
                     "downlink_bytes": r.downlink_bytes,
                     "staleness": list(r.staleness),
                     "ledger": dict(r.ledger),
                     "faults": dict(getattr(r, "faults", {}) or {}),
                     "metrics": dict(r.metrics)}})
    # the contribution flight layer: per-update rollup histograms plus
    # reservoir-sampled exemplar lifecycles (called after the runtime has
    # applied screening verdicts, so exemplars carry final states)
    frames = getattr(trace, "flights", None)
    if frames:
        log_frames(rec, frames)
    rec.append({"type": "run", "lane": "host", "cat": "obs",
                "name": run or rec.run, "t": rec.now(),
                "args": {"meta": jsonable(dict(trace.meta)),
                         "summary": jsonable(trace.summary())}})


__all__ = [
    "DEFAULT_SLOS", "FlightFrame", "HealthMonitor", "MetricsBuffer",
    "Recorder", "SloRule", "configure", "current", "enabled",
    "event", "flights_enabled", "instrument",
    "jsonable", "log_frames", "log_trace", "parse_rule", "read_jsonl",
    "read_jsonl_tolerant", "set_flights", "shutdown", "span",
    "to_perfetto", "virtual_span", "write_jsonl", "write_perfetto",
]
