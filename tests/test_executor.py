"""Cohort execution engine tests (PR 5 tentpole).

Covers: the executor registry; the stacked backend's bitwise preservation
of the pre-engine trainer (together with test_scheduler.py's
run-vs-manual-loop pin, which IS the pre-PR contract); stacked-vs-mesh
parity for every scheduler policy on a forced multi-device CPU mesh
(loss/params allclose, identical participant sets and traced bytes, shard
placement recorded); per-client EF/cut-state round-trips across executors
(the satellite per-client warm-start keying); the stateful downlink hook;
and the trace-driven autoscaler's deterministic rules on canned traces.

The mesh-only tests need >= 4 devices and skip otherwise — the CI mesh leg
runs this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(one subprocess smoke below exercises the mesh path even in a
single-device tier-1 run).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compressors as C
from repro.core.quantizer import PQConfig, quantize, quantize_stateful
from repro.data.synthetic import make_federated_image_data
from repro.federated import (AsyncBuffer, AutoscalePlan, Deadline,
                             DropSlowestK, FederatedTrainer, FullSync,
                             TraceAutoscaler, lognormal_fleet, make_executor,
                             make_policy)
from repro.federated.executor import (MeshExecutor, StackedExecutor,
                                      available_executors)
from repro.federated.trace import RoundRecord, Trace
from repro.models.paper_models import FemnistCNN
from repro.optim import sgd

MESH_DEVICES = 4
needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < MESH_DEVICES,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")

PQ = PQConfig(num_subvectors=288, num_clusters=4, kmeans_iters=2)


def _trainer(executor="stacked", policy=None, fleet=None, per_client=True,
             **kw):
    data = make_federated_image_data(num_clients=8, seed=0)
    model = FemnistCNN(pq=PQ, lam=1e-4,
                       client_batch=8 if per_client else 0)
    return FederatedTrainer(model, sgd(0.03), data, cohort=4, client_batch=8,
                            fleet=fleet, policy=policy, executor=executor,
                            **kw)


def _straggler_fleet():
    return lognormal_fleet(8, median_uplink_bps=2e6, seed=3)


# ---------------------------------------------------------------------------
# registry + spec parsing
# ---------------------------------------------------------------------------

def test_registry_lists_both_backends():
    assert set(available_executors()) >= {"stacked", "mesh"}


def test_make_executor_specs():
    assert isinstance(make_executor("stacked"), StackedExecutor)
    assert isinstance(make_executor(None), StackedExecutor)
    ex = make_executor("mesh(shards=2)")
    assert isinstance(ex, MeshExecutor) and ex.shards == 2
    inst = StackedExecutor()
    assert make_executor(inst) is inst
    with pytest.raises(ValueError, match="unknown executor"):
        make_executor("hamster_wheel")
    with pytest.raises(ValueError, match="key=value"):
        make_executor("mesh(4)")


def test_executor_instance_cannot_be_shared_across_trainers():
    """Sharing one executor instance would cross-wire the first trainer to
    the second's model/optimizer — bind() must refuse re-targeting."""
    ex = StackedExecutor()
    _trainer(executor=ex)
    with pytest.raises(ValueError, match="already bound"):
        _trainer(executor=ex)


# ---------------------------------------------------------------------------
# stacked backend: bitwise preservation of the pre-engine trainer
# ---------------------------------------------------------------------------

def test_stacked_spec_variants_bitwise_identical():
    """Default construction, the explicit spec and an instance all select
    the same bitwise trajectory under a straggler policy (the stacked path
    is the pre-engine behavior: test_scheduler.py pins run() == the manual
    pre-PR round loop on the ideal profile)."""
    key = jax.random.PRNGKey(0)
    results = []
    for executor in ("stacked", StackedExecutor()):
        tr = _trainer(executor=executor, policy=DropSlowestK(1),
                      fleet=_straggler_fleet(), per_client=False)
        state, hist = tr.run(3, key)
        results.append((state, [h["loss"] for h in hist]))
    (s1, l1), (s2, l2) = results
    assert l1 == l2
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_routes_through_executor_bitwise():
    """round() through the stacked executor == the historical fused-batch
    step on the identically sampled cohort."""
    key = jax.random.PRNGKey(0)
    tr = _trainer(per_client=False)
    state = tr.init_state(key)
    s1, m1 = tr.round(state, jax.random.fold_in(key, 1))

    tr2 = _trainer(per_client=False)
    state2 = tr2.init_state(key)
    batch = tr2.cohort_batch(jax.random.fold_in(key, 1))
    s2, m2 = tr2.executor._step(state2, batch)
    assert float(m1["loss"]) == float(m2["loss"])
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# stacked-vs-mesh parity (forced multi-device mesh)
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("policy_fn,heterogeneous", [
    (FullSync, False),
    (lambda: DropSlowestK(1), True),
    (lambda: Deadline(6.0), True),
    (lambda: AsyncBuffer(2), True),
])
def test_mesh_reproduces_stacked(policy_fn, heterogeneous):
    """executor='mesh' reproduces executor='stacked' round metrics
    (loss allclose), final params (allclose), participant sets, traced
    bytes — for every scheduler policy — and records shard placement."""
    fleet = _straggler_fleet() if heterogeneous else None
    key = jax.random.PRNGKey(0)
    ts = _trainer("stacked", policy_fn(), fleet)
    ss, hs = ts.run(2, key)
    tm = _trainer("mesh", policy_fn(), fleet)
    sm, hm = tm.run(2, key)

    np.testing.assert_allclose([h["loss"] for h in hs],
                               [h["loss"] for h in hm], rtol=5e-4)
    np.testing.assert_allclose([h["ce"] for h in hs],
                               [h["ce"] for h in hm], rtol=5e-4)
    for a, b in zip(jax.tree.leaves(ss.params), jax.tree.leaves(sm.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-5)
    rs, rm = ts.last_trace, tm.last_trace
    assert [r.participants for r in rs] == [r.participants for r in rm]
    assert [r.uplink_bytes for r in rs] == [r.uplink_bytes for r in rm]
    assert [r.downlink_bytes for r in rs] == [r.downlink_bytes for r in rm]
    assert rm.meta["executor"] == "mesh"
    assert rm.meta["executor_shards"] == len(jax.devices())
    # placement recorded: multi-participant rounds span more than one shard
    assert all(max(r.shards, default=0) > 0
               for r in rm if len(r.participants) > 1)
    assert all(set(r.shards) == {0} for r in rs)  # stacked: single device


@needs_mesh
def test_mesh_placement_contiguous_blocks():
    tr = _trainer("mesh")
    ex = tr.executor
    from repro.federated.scheduler import Arrival
    parts = [Arrival(client=c, version=0, t_arrival=0.0) for c in range(5)]
    placed = ex.place(parts)
    # 5 participants on 4 shards -> 8 padded slots, 2 per shard
    assert [a.shard for a in placed] == [0, 0, 1, 1, 2]
    assert [a.client for a in placed] == [0, 1, 2, 3, 4]


@needs_mesh
def test_mesh_cut_state_round_trip_matches_stacked():
    """Per-client EF memories and warm-start codebooks absorbed from mesh
    rounds match the stacked path's (the client-keyed lineage survives the
    device round-trip)."""
    key = jax.random.PRNGKey(0)
    ts = _trainer("stacked", warm_start=True, error_feedback=True)
    ts.run(3, key)
    tm = _trainer("mesh", warm_start=True, error_feedback=True)
    tm.run(3, key)
    assert set(ts._client_q) == set(tm._client_q)
    assert set(ts._ef_memory) == set(tm._ef_memory)
    for cid in ts._client_q:
        a, b = ts._client_q[cid], tm._client_q[cid]
        assert int(a.rounds) == int(b.rounds)
        np.testing.assert_allclose(np.asarray(a.codebooks),
                                   np.asarray(b.codebooks),
                                   rtol=5e-3, atol=5e-4)
    for cid in ts._ef_memory:
        np.testing.assert_allclose(np.asarray(ts._ef_memory[cid]),
                                   np.asarray(tm._ef_memory[cid]),
                                   rtol=5e-3, atol=5e-4)


def test_mesh_smoke_via_subprocess():
    """Even a single-device tier-1 run exercises the mesh backend once:
    a child process with forced host devices runs one stacked-vs-mesh
    round and asserts loss parity."""
    code = r"""
import jax, numpy as np
from repro.core.quantizer import PQConfig
from repro.data.synthetic import make_federated_image_data
from repro.federated import FederatedTrainer
from repro.models.paper_models import FemnistCNN
from repro.optim import sgd
assert len(jax.devices()) == 4, jax.devices()
data = make_federated_image_data(num_clients=4, seed=0)
pq = PQConfig(num_subvectors=1152, num_clusters=2, kmeans_iters=1)
losses = []
for ex in ("stacked", "mesh"):
    model = FemnistCNN(pq=pq, lam=1e-4, client_batch=4)
    tr = FederatedTrainer(model, sgd(0.03), data, cohort=2, client_batch=4,
                          executor=ex)
    _, hist = tr.run(1, jax.random.PRNGKey(0))
    losses.append(hist[0]["loss"])
np.testing.assert_allclose(losses[0], losses[1], rtol=5e-4)
print("MESH_SMOKE_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MESH_SMOKE_OK" in proc.stdout


# ---------------------------------------------------------------------------
# per-client warm-start keying on the stacked path (satellite)
# ---------------------------------------------------------------------------

def test_stacked_warm_start_keyed_by_client_survives_reshuffle():
    """DropSlowestK reshuffles cohort composition every round; per-client
    keying must keep each client's codebook lineage instead of resetting
    it with the cohort, and first-time clients are seeded warm."""
    tr = _trainer(policy=DropSlowestK(1), fleet=_straggler_fleet(),
                  warm_start=True)
    tr.run(5, jax.random.PRNGKey(0))
    assert len(tr._client_q) > 0
    rounds = [int(q.rounds) for q in tr._client_q.values()]
    # lineage continued across reshuffled cohorts for repeat participants
    assert max(rounds) >= 2
    # cohort-global slot unused: the per-client path owns the state
    assert tr._global_q is None
    # round 2 onward ran warm: a fresh gather succeeds for ANY cohort
    # (first-timers seeded from the latest absorbed codebook)
    st = tr._gather_client_q([0, 1, 2, 3, 4, 5])
    assert st is not None and st.codebooks.shape[0] == 6


def test_cohort_global_model_keeps_global_slot():
    """client_batch=0 models quantize the whole cohort with one codebook:
    the lineage stays in the cohort-global slot (historical behavior)."""
    tr = _trainer(warm_start=True, per_client=False)
    tr.run(3, jax.random.PRNGKey(0))
    assert tr._global_q is not None
    assert int(tr._global_q.rounds) == 3
    assert tr._client_q == {}


# ---------------------------------------------------------------------------
# stateful downlink hook (satellite: pq-delta covers both directions)
# ---------------------------------------------------------------------------

def test_downlink_stateful_cold_matches_stateless():
    comp = C.PQCompressor(cfg=PQConfig(num_subvectors=8, num_clusters=4,
                                       kmeans_iters=2))
    z = jax.random.normal(jax.random.PRNGKey(0), (12, 64))
    gt = jax.random.normal(jax.random.PRNGKey(1), (12, 64))
    _, vjp = jax.vjp(lambda x: C.downlink(x, None, None, comp), z)
    np.testing.assert_array_equal(np.asarray(comp.compress(gt).recon),
                                  np.asarray(vjp(gt)[0]))


def test_downlink_stateful_warm_uses_state_codebooks():
    """warm_iters=0 pins Lloyd to the incoming state's codebooks exactly:
    the backward reconstruction must equal quantization under those
    codebooks, and the state gets a zero cotangent."""
    cfg = PQConfig(num_subvectors=8, num_clusters=4, kmeans_iters=3,
                   warm_iters=0)
    comp = C.PQCompressor(cfg=cfg)
    z = jax.random.normal(jax.random.PRNGKey(0), (12, 64))
    gt = jax.random.normal(jax.random.PRNGKey(1), (12, 64))
    gref = jax.random.normal(jax.random.PRNGKey(2), (12, 64))
    _, state = quantize_stateful(gref, cfg)

    out, vjp = jax.vjp(
        lambda x, s: C.downlink(x, None, s, comp), z, state)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(z))  # identity
    gz, gstate = vjp(gt)
    expect = quantize(gt, cfg, state=state).dequantized
    np.testing.assert_allclose(np.asarray(gz), np.asarray(expect),
                               rtol=1e-6, atol=1e-7)
    assert float(jnp.abs(gstate.codebooks).max()) == 0.0  # carry, no grad


def test_trainer_measures_downlink_delta_bytes():
    tr = _trainer(downlink_compressor="pq", codebook_delta_bits=8)
    state = tr.init_state(jax.random.PRNGKey(0))
    _, down = tr.measure_round_bytes(state, jax.random.PRNGKey(1))
    meta = tr.last_codebook_meta
    assert down == meta["downlink_bytes_delta_codebook"]
    assert meta["downlink_codebook_bytes_delta"] < \
        meta["downlink_codebook_bytes_full"]
    assert meta["downlink_codebook_bytes_reduction"] > 1.0
    # both directions measured: the uplink keys keep their historical names
    assert meta["uplink_bytes_delta_codebook"] > 0


# ---------------------------------------------------------------------------
# trace-driven autoscaler: deterministic rules on canned traces
# ---------------------------------------------------------------------------

def _rec(i, dur, participants=4, dropped=0, loss=None, up=1000, down=1000):
    return RoundRecord(
        round=i, t_start=float(i * 10), t_end=float(i * 10) + dur,
        participants=tuple(range(participants)),
        dropped=tuple(range(100, 100 + dropped)),
        uplink_bytes=up, downlink_bytes=down,
        metrics={} if loss is None else {"loss": loss})


def _trace(durs, losses=None, dropped=0, up=1000, down=1000):
    losses = losses or [None] * len(durs)
    t = Trace()
    for i, (d, l) in enumerate(zip(durs, losses)):
        t.append(_rec(i, d, dropped=dropped, loss=l, up=up, down=down))
    return t


def test_autoscaler_is_deterministic():
    trace = _trace([1, 1, 1, 1, 1, 1, 1, 5],
                   losses=[5, 4.8, 4.6, 4.4, 4.2, 4.0, 3.8, 3.6])
    ctl = TraceAutoscaler(window=8)
    plan = AutoscalePlan(cohort=4)
    outs = [ctl.recommend(trace, plan) for _ in range(3)]
    assert outs[0] == outs[1] == outs[2]


def test_autoscaler_straggler_tail_bounds_rounds():
    trace = _trace([1, 1, 1, 1, 1, 1, 1, 5])
    ctl = TraceAutoscaler(window=8, tail_hi=1.8, deadline_slack=1.5)
    plan = ctl.recommend(trace, AutoscalePlan(cohort=4))
    assert plan.policy.startswith("deadline:")
    assert float(plan.policy.split(":")[1]) == pytest.approx(1.5)  # 1.5*p50
    assert plan.cohort == 4
    assert "straggler tail" in plan.reason


def test_autoscaler_backs_off_aggressive_policy():
    trace = _trace([2] * 8, dropped=3)        # 3 of 7 lost: 43% > 30%
    ctl = TraceAutoscaler(window=8)
    plan = ctl.recommend(trace, AutoscalePlan(cohort=4, policy="deadline:2"))
    assert plan.policy == "deadline:4"        # loosened, cohort untouched
    plan2 = ctl.recommend(trace,
                          AutoscalePlan(cohort=4, policy="drop_slowest:2"))
    assert plan2.policy == "drop_slowest:1"


def test_autoscaler_bytes_budget_escalates_codec_then_cohort():
    trace = _trace([1] * 8, up=4000, down=4000)
    ctl = TraceAutoscaler(window=8, bytes_budget_per_round=1000.0)
    p0 = AutoscalePlan(cohort=8)
    p1 = ctl.recommend(trace, p0)
    assert p1.downlink == "scalarq(bits=8)" and p1.cohort == 8
    p2 = ctl.recommend(trace, p1)
    assert p2.downlink == "chain:topk(k=0.1)+scalarq(bits=8)"
    p3 = ctl.recommend(trace, p2)
    assert p3.cohort == 4                     # ladder exhausted: shed clients


def test_autoscaler_grows_when_healthy_shrinks_on_plateau():
    improving = _trace([1] * 8, losses=[5.0 - 0.2 * i for i in range(8)])
    ctl = TraceAutoscaler(window=8)
    grown = ctl.recommend(improving, AutoscalePlan(cohort=4))
    assert grown.cohort == 8

    flat = _trace([1] * 8, losses=[3.0] * 8)
    shrunk = ctl.recommend(flat, AutoscalePlan(cohort=8))
    assert shrunk.cohort == 4

    steady = ctl.recommend(flat, AutoscalePlan(cohort=2))
    assert steady.cohort == 2 and steady.reason == "steady"


def test_autoscaler_empty_trace_is_noop():
    ctl = TraceAutoscaler()
    plan = AutoscalePlan(cohort=4)
    assert ctl.recommend(Trace(), plan) == plan


def test_make_policy_round_trips_specs():
    assert isinstance(make_policy("full_sync"), FullSync)
    assert make_policy("drop_slowest:2").k == 2
    assert make_policy("deadline:6.5").seconds == 6.5
    assert make_policy("async:3").buffer_size == 3
    with pytest.raises(ValueError, match="unknown policy"):
        make_policy("psychic")


def test_trace_windowed_observations():
    trace = _trace([1, 1, 1, 1, 1, 1, 1, 5],
                   losses=[5, 4.8, 4.6, 4.4, 4.2, 4.0, 3.8, 3.6],
                   dropped=1)
    assert trace.duration_percentile(50.0) == pytest.approx(1.0)
    assert trace.tail_ratio() > 2.0
    assert trace.drop_rate() == pytest.approx(8 / (8 + 32))
    assert trace.bytes_per_round() == pytest.approx(2000.0)
    assert trace.loss_slope() == pytest.approx(-0.2)
    assert trace.window(3) == trace.records[-3:]
    assert Trace().tail_ratio() == 1.0 and Trace().loss_slope() == 0.0
