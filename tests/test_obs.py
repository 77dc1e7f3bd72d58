"""Observability tests: span recording + trace-safety, exporter
round-trips, Trace windowed-reduction edge cases, the run inspector, and
the transfer-counting guarantee (instrumentation adds zero device→host
syncs to a training run)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.quantizer import PQConfig
from repro.data.synthetic import make_federated_image_data
from repro.federated import (DEFAULT_CHAOS, DropSlowestK, FederatedTrainer,
                             lognormal_fleet)
from repro.federated.trace import RoundRecord, Trace
from repro.models.paper_models import FemnistCNN
from repro.obs.inspect import format_report, main, percentile, summarize
from repro.optim import sgd


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    """Every test starts and ends without a module-level recorder."""
    obs.shutdown()
    yield
    obs.shutdown()


def _record(round, t0, t1, loss=None, up=100, down=200, dropped=(),
            ledger=None):
    return RoundRecord(
        round=round, t_start=t0, t_end=t1, participants=(0, 1),
        dropped=tuple(dropped), uplink_bytes=up, downlink_bytes=down,
        metrics={} if loss is None else {"loss": loss},
        ledger=ledger or {})


# ---------------------------------------------------------------------------
# Trace windowed reductions: empty / single-round / extreme-q edge cases
# ---------------------------------------------------------------------------

def test_empty_trace_reductions_are_defined():
    t = Trace()
    assert t.duration_percentile(50.0) == 0.0
    assert t.duration_percentile(0.0) == 0.0
    assert t.tail_ratio() == 1.0
    assert t.loss_slope() == 0.0
    assert t.drop_rate() == 0.0
    assert t.bytes_per_round() == 0.0
    assert t.ledger_totals() == {}
    s = t.summary()
    assert s["rounds"] == 0
    assert s["simulated_seconds"] == 0.0
    assert s["mean_staleness"] == 0.0


def test_single_round_trace_reductions():
    t = Trace(records=[_record(0, 0.0, 2.5, loss=1.0)])
    # every percentile of one sample is that sample, including q in {0, 1}
    for q in (0.0, 1.0, 50.0, 100.0):
        assert t.duration_percentile(q) == pytest.approx(2.5)
    assert t.tail_ratio() == pytest.approx(1.0)
    assert t.loss_slope() == 0.0          # needs >= 2 loss points
    assert t.summary()["rounds"] == 1


def test_duration_percentile_extreme_q():
    durations = [1.0, 2.0, 4.0, 8.0]
    t = Trace(records=[_record(i, 0.0, d) for i, d in enumerate(durations)])
    assert t.duration_percentile(0.0) == pytest.approx(1.0)    # the min
    assert t.duration_percentile(100.0) == pytest.approx(8.0)  # the max
    # q is clamped, not wrapped, outside [0, 100]
    assert t.duration_percentile(-5.0) == pytest.approx(1.0)
    assert t.duration_percentile(250.0) == pytest.approx(8.0)
    # q=1 (of 100) interpolates just above the minimum
    assert 1.0 <= t.duration_percentile(1.0) < 2.0


def test_loss_slope_and_targets():
    t = Trace(records=[_record(i, float(i), float(i + 1), loss=4.0 - i)
                       for i in range(4)])
    assert t.loss_slope() == pytest.approx(-1.0)
    assert t.time_to_target(2.0) == pytest.approx(3.0)
    assert t.bytes_to_target(2.0) == 300          # 3 rounds of uplink
    assert t.time_to_target(-10.0) is None


def test_ledger_totals_accumulate_across_rounds():
    t = Trace(records=[
        _record(0, 0.0, 1.0, ledger={"uplink/pq": 10, "downlink/dense": 50}),
        _record(1, 1.0, 2.0, ledger={"uplink/pq": 15}),
        _record(2, 2.0, 3.0),                     # legacy: empty ledger
    ])
    assert t.ledger_totals() == {"uplink/pq": 25, "downlink/dense": 50}


# ---------------------------------------------------------------------------
# spans: recording, trace-safety, the instrument wrapper
# ---------------------------------------------------------------------------

def test_span_is_noop_without_recorder():
    with obs.span("nothing", cat="test") as sp:
        sp.set(key="value")                       # must not raise
    assert obs.current() is None
    assert not obs.enabled()


def test_span_records_host_lane():
    rec = obs.configure(run="t", meta={"k": "v"})
    with obs.span("work", cat="test", n=3) as sp:
        sp.set(extra=1)
    obs.virtual_span("simwork", 1.0, 3.5, cat="test", round=0)
    obs.event("mark", cat="test", lane="virtual", t=2.0, why="x")
    spans = [e for e in rec.events if e["type"] == "span"]
    assert {(s["lane"], s["name"]) for s in spans} == \
        {("host", "work"), ("virtual", "simwork")}
    host = next(s for s in spans if s["lane"] == "host")
    assert host["t1"] >= host["t0"] >= 0.0
    assert host["args"] == {"n": 3, "extra": 1}
    virt = next(s for s in spans if s["lane"] == "virtual")
    assert (virt["t0"], virt["t1"]) == (1.0, 3.5)
    ev = next(e for e in rec.events if e["type"] == "event")
    assert (ev["name"], ev["t"], ev["lane"]) == ("mark", 2.0, "virtual")
    # the run_start meta event carries the configured meta
    assert rec.events[0]["args"] == {"k": "v", "run": "t"}


def test_span_suppressed_inside_jit_tracing():
    rec = obs.configure(run="t")

    @jax.jit
    def f(x):
        with obs.span("should-not-record", cat="test"):
            pass
        obs.event("should-not-record-either", cat="test")
        return x * 2

    f(jnp.ones(3)).block_until_ready()
    names = {e["name"] for e in rec.events}
    assert "should-not-record" not in names
    assert "should-not-record-either" not in names


def test_instrument_wrapper_records_per_call():
    @obs.instrument("my.fn", cat="test")
    def fn(a, b=1):
        return a + b

    assert fn(2, b=3) == 5                        # no recorder: plain call
    rec = obs.configure(run="t")
    assert fn(2) == 3
    spans = [e for e in rec.events if e["type"] == "span"]
    assert [s["name"] for s in spans] == ["my.fn"]
    assert fn.__name__ == "fn"                    # functools.wraps preserved


def _profiled_host_events(tmp_path, fn):
    """Run ``fn`` under a profiler session; the names of the host events
    in the ``.xplane.pb`` it wrote."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    return [e.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_span_is_a_profiler_annotation_without_recorder(tmp_path):
    def work():
        with obs.span("obs.test.span", cat="test"):
            pass

    names = _profiled_host_events(tmp_path, work)
    assert names.count("obs.test.span") == 1
    assert obs.current() is None


def test_span_args_stay_out_of_the_annotation_name(tmp_path):
    rec = obs.configure(run="t")

    @obs.instrument("obs.test.fn", cat="test")
    def fn():
        return 1

    def work():
        with obs.span("obs.test.args", cat="test", n=3, mode="sync") as sp:
            sp.set(extra=1)
        fn()

    names = _profiled_host_events(tmp_path, work)
    assert [n for n in names if n.startswith("obs.test.")] == \
        ["obs.test.args", "obs.test.fn"]
    spans = [e for e in rec.events if e["type"] == "span"]
    assert spans[0]["args"] == {"n": 3, "mode": "sync", "extra": 1}


def test_no_annotation_while_jit_tracing(tmp_path):
    @jax.jit
    def f(x):
        with obs.span("obs.test.traced", cat="test"):
            return x * 2

    names = _profiled_host_events(
        tmp_path, lambda: f(jnp.ones(3)).block_until_ready())
    assert "obs.test.traced" not in names
    assert "PjitFunction(f)" in names             # the session saw the call


# ---------------------------------------------------------------------------
# exporters: JSONL append-only round-trip + Perfetto structure
# ---------------------------------------------------------------------------

def test_jsonl_round_trip_and_incremental_append(tmp_path):
    rec = obs.configure(run="t")
    with obs.span("a", cat="test"):
        pass
    path = tmp_path / "run.jsonl"
    n1 = rec.write_jsonl(path)
    assert n1 == 2                                # run_start meta + 1 span
    assert rec.write_jsonl(path) == 0             # nothing new: no rewrite
    with obs.span("b", cat="test"):
        pass
    assert rec.write_jsonl(path) == 1             # only the new event
    events = obs.read_jsonl(path)
    assert [e.get("name") for e in events] == ["run_start", "a", "b"]
    assert events == json.loads(
        "[" + ",".join(p for p in path.read_text().splitlines()) + "]")


def test_jsonable_handles_arrays_and_fallbacks():
    assert obs.jsonable(jnp.arange(3)) == [0, 1, 2]
    assert obs.jsonable(np.float32(1.5)) == 1.5
    assert obs.jsonable({"k": (1, 2)}) == {"k": [1, 2]}
    assert obs.jsonable(object()).startswith("<object")


def test_perfetto_two_lanes_and_phases(tmp_path):
    rec = obs.configure(run="t")
    with obs.span("hostwork", cat="exec"):
        pass
    obs.virtual_span("round 0", 0.0, 1.0, cat="rounds")
    obs.event("cut", cat="sched", lane="virtual", t=0.5)
    path = tmp_path / "trace.perfetto.json"
    rec.write_perfetto(path)
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    lanes = {e["args"]["name"] for e in evs if e.get("name") == "process_name"}
    assert lanes == {"host wall-clock", "scheduler virtual-clock"}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"hostwork", "round 0"}
    assert xs["hostwork"]["pid"] != xs["round 0"]["pid"]   # distinct lanes
    assert xs["round 0"]["dur"] == pytest.approx(1e6)      # µs
    assert all(e["dur"] >= 0.0 for e in evs if e["ph"] == "X")
    inst = [e for e in evs if e["ph"] == "i"]
    assert {e["name"] for e in inst} >= {"cut"}
    assert all(e["s"] == "t" for e in inst)


# ---------------------------------------------------------------------------
# in-jit metrics + the single-flush buffer
# ---------------------------------------------------------------------------

def test_metric_helpers_inside_jit():
    """Metrics computed as plain arrays inside a jitted step are recorded
    without a sync and flushed in one transfer: scalars as floats, vectors
    as lists."""
    @jax.jit
    def step(x):
        return {"n": jnp.sum(jnp.ones_like(x)),
                "mean": x.mean(),
                "hist": jnp.zeros(4).at[jnp.clip(
                    (x * 4).astype(jnp.int32), 0, 3)].add(1.0)}

    buf = obs.MetricsBuffer()
    buf.record(step(jnp.array([0.1, 0.3, 0.6, 0.9])))
    buf.record(step(jnp.array([-1.0, 2.0])))
    assert len(buf) == 2
    out = buf.flush()
    assert len(buf) == 0
    assert out[0]["n"] == 4.0 and isinstance(out[0]["n"], float)
    assert out[0]["mean"] == pytest.approx(0.475)
    assert out[0]["hist"] == [1.0, 1.0, 1.0, 1.0]
    assert out[1]["hist"] == [1.0, 0.0, 0.0, 1.0]
    assert buf.flush() == []                       # idempotent when drained


def _small_trainer():
    data = make_federated_image_data(num_clients=8, seed=0)
    pq = PQConfig(num_subvectors=288, num_clusters=4, kmeans_iters=2)
    model = FemnistCNN(pq=pq, lam=1e-4)
    return FederatedTrainer(model, sgd(0.03), data, cohort=4, client_batch=8,
                            fleet=lognormal_fleet(8, seed=0),
                            policy=DropSlowestK(1))


def _count_transfers(monkeypatch, configured):
    calls = {"n": 0}
    real = jax.device_get

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(jax, "device_get", counting)
    try:
        if configured:
            obs.configure(run="count")
        tr = _small_trainer()
        tr.run(2, jax.random.PRNGKey(0))
    finally:
        monkeypatch.setattr(jax, "device_get", real)
        rec = obs.shutdown()
    if configured:
        assert any(e["type"] == "round" for e in rec.events)
    return calls["n"]


def test_instrumentation_adds_no_device_transfers(monkeypatch):
    """The sync-free contract: a fully instrumented run performs no more
    blocking device→host transfers than an uninstrumented one, and the
    whole run's metrics arrive through a single flush."""
    plain = _count_transfers(monkeypatch, configured=False)
    instrumented = _count_transfers(monkeypatch, configured=True)
    assert instrumented <= plain
    assert plain >= 1                              # the run's single flush


# ---------------------------------------------------------------------------
# log_trace + the run inspector
# ---------------------------------------------------------------------------

def _synthetic_run_events():
    rec = obs.configure(run="synthetic", meta={"suite": "unit"})
    trace = Trace(records=[
        _record(0, 0.0, 1.0, loss=4.0, up=1000, down=4000,
                ledger={"uplink/pq": 1000, "downlink/dense": 4000}),
        _record(1, 1.0, 3.0, loss=2.0, up=1000, down=4000, dropped=(7,),
                ledger={"uplink/pq": 1000, "downlink/dense": 4000}),
    ], meta={"uplink_compressor": "pq"})
    obs.log_trace(trace)
    obs.shutdown()
    return rec.events


def test_log_trace_emits_round_and_run_events():
    events = _synthetic_run_events()
    rounds = [e for e in events if e["type"] == "round"]
    assert [r["args"]["round"] for r in rounds] == [0, 1]
    assert all(r["lane"] == "virtual" for r in rounds)
    assert rounds[1]["args"]["dropped"] == 1
    runs = [e for e in events if e["type"] == "run"]
    assert len(runs) == 1
    assert runs[0]["args"]["meta"]["uplink_compressor"] == "pq"


def test_log_trace_is_noop_without_recorder():
    obs.log_trace(Trace(records=[_record(0, 0.0, 1.0)]))  # must not raise


def test_summarize_rounds_ledger_and_target():
    events = _synthetic_run_events()
    s = summarize(events, target=2.5)
    assert len(s["rounds"]) == 2
    assert s["ledger"] == {"uplink/pq": 2000, "downlink/dense": 8000}
    assert s["uplink_bytes"] == 2000
    assert s["simulated_seconds"] == pytest.approx(3.0)
    assert s["round_duration_p50_s"] == pytest.approx(1.5)
    assert s["target"]["reached_round"] == 1
    assert s["target"]["time_to_target_s"] == pytest.approx(3.0)
    assert s["target"]["bytes_to_target"] == 10000    # both directions
    missed = summarize(events, target=0.1)
    assert missed["target"]["reached_round"] is None
    report = format_report(s)
    assert "byte ledger" in report and "uplink/pq" in report
    assert "reached at round 1" in report


def test_summarize_empty_and_percentile_edges():
    s = summarize([])
    assert s["events"] == 0 and s["rounds"] == []
    assert s["tail_ratio"] == 1.0
    assert percentile([], 50) == 0.0
    assert percentile([3.0], 0) == 3.0
    assert percentile([3.0], 100) == 3.0
    assert percentile([1.0, 3.0], 200) == 3.0         # clamped
    format_report(s)                                  # renders without rounds


def test_inspector_cli(tmp_path, capsys):
    rec = obs.configure(run="cli")
    obs.log_trace(Trace(records=[_record(0, 0.0, 1.0, loss=1.0)]))
    obs.shutdown()
    path = tmp_path / "run.jsonl"
    rec.write_jsonl(path)
    assert main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "run: cli" in out and "round" in out
    assert main([str(path), "--json", "--target", "2.0"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["target"]["reached_round"] == 0
    assert main([str(tmp_path / "missing.jsonl")]) == 2


# ---------------------------------------------------------------------------
# contribution flight recorder: frames, exemplars, flow links, inspector
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chaos_run(tmp_path_factory):
    """One chaos training run recorded end-to-end, shared by the flight /
    SLO / inspector tests below (the run itself is the expensive part).
    DEFAULT_CHAOS on this seed yields >= 1 quarantine and >= 1 crash
    retry, so the exemplar stream exercises every lifecycle edge."""
    obs.shutdown()
    rec = obs.configure(run="chaos", meta={"suite": "unit"})
    data = make_federated_image_data(num_clients=8, seed=0)
    pq = PQConfig(num_subvectors=288, num_clusters=4, kmeans_iters=2)
    model = FemnistCNN(pq=pq, lam=1e-4)
    tr = FederatedTrainer(
        model, sgd(0.03), data, cohort=4, client_batch=8, quantize=True,
        seed=0, fleet=lognormal_fleet(8, seed=0), fault_plan=DEFAULT_CHAOS,
        slo_monitor=obs.HealthMonitor(rules=(
            obs.SloRule("impossible", "rounds", ">=", 1000),)))
    tr.run(6, jax.random.PRNGKey(0))
    obs.shutdown()
    path = tmp_path_factory.mktemp("chaos") / "run.jsonl"
    rec.write_jsonl(path)
    ppath = path.parent / "run.perfetto.json"
    rec.write_perfetto(ppath)
    return {"events": rec.events, "trace": tr.last_trace,
            "path": path, "ppath": ppath}


def _by_name(events, name):
    return [e for e in events if e.get("name") == name]


def test_flight_frame_json_round_trip(chaos_run):
    frames = chaos_run["trace"].flights
    assert len(frames) == 6
    for frame in frames:
        doc = frame.to_json()
        json.dumps(doc)                       # plain-JSON serializable
        clone = obs.FlightFrame.from_json(doc)
        assert clone == frame                 # NaN-aware column equality
        assert clone is not frame and len(clone) == len(frame)


def test_chaos_run_emits_rollups_and_exemplars(chaos_run):
    events = chaos_run["events"]
    rollups = _by_name(events, "flight.rollup")
    assert [r["args"]["round"] for r in rollups] == list(range(6))
    for r in rollups:
        # O(cohort) rollup: state histogram covers the whole cohort
        assert sum(r["args"]["states"].values()) == r["args"]["flights"] == 4
    # reservoir exemplars: every lifecycle stage event carries a flight_id
    for name in ("flight.sampled", "flight.placed", "flight.uplink",
                 "flight.outcome", "flight.server"):
        stage = _by_name(events, name)
        assert len(stage) == 24               # 4-exemplar cohorts x 6 rounds
        assert all(e["args"]["flight_id"].startswith("r") for e in stage)
    # the chaos plan actually bit on this seed, and the recorder saw it
    assert _by_name(events, "flight.quarantined")
    assert _by_name(events, "flight.retry")


def test_flight_exemplar_lifecycle_is_causally_ordered(chaos_run):
    events = chaos_run["events"]
    quarantined = _by_name(events, "flight.quarantined")[0]
    fid = quarantined["args"]["flight_id"]
    stages = [e["name"] for e in events
              if e.get("args", {}).get("flight_id") == fid]
    assert stages[0] == "flight.sampled"
    assert stages.index("flight.placed") < stages.index("flight.uplink")
    assert stages.index("flight.quarantined") < stages.index("flight.outcome")
    assert stages[-1] == "flight.server"      # server-side screening span


def test_perfetto_flow_events_link_flight_spans(chaos_run):
    doc = json.loads(chaos_run["ppath"].read_text())
    flows = [e for e in doc["traceEvents"] if e.get("cat") == "flights"
             and e["ph"] in ("s", "t", "f")]
    assert flows
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    for fid, chain in by_id.items():
        chain.sort(key=lambda e: e["ts"])
        phases = [e["ph"] for e in chain]
        # each flight is one s -> t* -> f arrow chain across the lanes
        assert phases[0] == "s" and phases[-1] == "f"
        assert set(phases[1:-1]) <= {"t"}
        assert chain[-1].get("bp") == "e"     # bind the arrow to span end


def test_inspector_reconstructs_a_flight(chaos_run, capsys):
    events = chaos_run["events"]
    fid = _by_name(events, "flight.quarantined")[0]["args"]["flight_id"]
    assert main([str(chaos_run["path"]), "--flight", fid]) == 0
    out = capsys.readouterr().out
    assert fid in out and "quarantined" in out
    # a miss lists known exemplars instead, and exits nonzero
    assert main([str(chaos_run["path"]), "--flight", "r9-c9-s9"]) == 1
    assert "r9-c9-s9" in capsys.readouterr().out


def test_inspector_health_and_slo_flags(chaos_run, capsys):
    path = str(chaos_run["path"])
    assert main([path, "--health"]) == 0
    out = capsys.readouterr().out
    assert "PASS" in out and "corruption-detected" in out
    # extra rule that must fail: still a report (exit 0), graded FAIL
    assert main([path, "--slo", "rounds>=100"]) == 0
    assert "FAIL" in capsys.readouterr().out
    assert main([path, "--slo", "not a rule"]) == 2


def test_slo_monitor_emits_violation_events(chaos_run):
    violations = _by_name(chaos_run["events"], "slo_violation")
    assert len(violations) == 1               # the impossible rounds>=1000
    args = violations[0]["args"]
    assert args["rule"] == "impossible" and args["signal"] == "rounds"
    assert args["value"] == 6.0 and args["op"] == ">="


# ---------------------------------------------------------------------------
# SLO rules + health monitor unit surface
# ---------------------------------------------------------------------------

def test_parse_rule_round_trips_the_cli_syntax():
    r = obs.parse_rule("drop_rate<=0.3")
    assert (r.signal, r.op, r.threshold, r.window) == \
        ("drop_rate", "<=", 0.3, None)
    r = obs.parse_rule("rounds >= 5 @ 20")
    assert (r.signal, r.op, r.threshold, r.window) == ("rounds", ">=", 5.0, 20)
    with pytest.raises(ValueError):
        obs.parse_rule("drop_rate == 0.3")


def test_health_monitor_grades_a_trace():
    trace = Trace(records=[_record(0, 0.0, 1.0), _record(1, 1.0, 2.0)])
    results = obs.HealthMonitor().evaluate(trace)
    assert [r.rule.name for r in results] == \
        [r.name for r in obs.DEFAULT_SLOS]
    assert all(r.ok for r in results)         # clean run passes defaults
    tight = obs.HealthMonitor(rules=(
        obs.SloRule("floor", "rounds", ">=", 3),))
    bad = tight.evaluate(trace)[0]
    assert not bad.ok and bad.value == 2.0
    assert bad.describe().startswith("FAIL")
    # an unknown signal is "not measurable": no violation, but rendered
    # as value=n/a so the gap is visible in the report
    missing = obs.HealthMonitor(rules=(
        obs.SloRule("ghost", "no_such_signal", "<=", 1.0),))
    res = missing.evaluate(trace)[0]
    assert res.value is None and res.ok
    assert "n/a" in res.describe()


def test_health_monitor_check_without_recorder_is_quiet():
    trace = Trace(records=[_record(0, 0.0, 1.0)])
    results = obs.HealthMonitor(rules=(
        obs.SloRule("floor", "rounds", ">=", 3),)).check(trace)
    assert results and not results[0].ok      # graded, nothing emitted


# ---------------------------------------------------------------------------
# tolerant JSONL reads (mid-write-killed logs)
# ---------------------------------------------------------------------------

def test_tolerant_reader_recovers_a_truncated_tail(tmp_path):
    rec = obs.configure(run="t")
    with obs.span("a", cat="test"):
        pass
    obs.shutdown()
    path = tmp_path / "run.jsonl"
    rec.write_jsonl(path)
    with open(path, "a") as fh:               # process killed mid-write
        fh.write('{"type": "event", "name": "half')
    with pytest.raises(json.JSONDecodeError):
        obs.read_jsonl(path)                  # strict reader refuses
    events, skipped = obs.read_jsonl_tolerant(path)
    assert skipped == 1
    assert [e.get("name") for e in events] == ["run_start", "a"]


def test_tolerant_reader_skips_non_object_lines(tmp_path):
    path = tmp_path / "weird.jsonl"
    path.write_text('{"type": "event", "name": "ok"}\n'
                    '[1, 2, 3]\n'
                    '\n'
                    'not json at all\n')
    events, skipped = obs.read_jsonl_tolerant(path)
    assert [e["name"] for e in events] == ["ok"]
    assert skipped == 2                       # array + garbage; blank is free


def test_inspector_warns_but_renders_truncated_logs(tmp_path, capsys):
    rec = obs.configure(run="cut")
    obs.log_trace(Trace(records=[_record(0, 0.0, 1.0, loss=1.0)]))
    obs.shutdown()
    path = tmp_path / "run.jsonl"
    rec.write_jsonl(path)
    with open(path, "a") as fh:
        fh.write('{"truncat')
    assert main([str(path)]) == 0
    captured = capsys.readouterr()
    assert "run: cut" in captured.out
    assert "skipped 1 unparseable line" in captured.err
