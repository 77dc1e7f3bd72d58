"""Round assembly: the cohort's keys come from one program and its batches
are joined in one program, bitwise what per-client ``fold_in``s and per-leaf
``jnp.concatenate``s give, for ``round()``, ``cohort_batch()`` and every
scheduler policy through ``run()``; after a warm-up round, ``round()`` makes
neither eager op."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import make_federated_image_data
from repro.federated import (AsyncBuffer, DropSlowestK, FederatedTrainer,
                             FullSync, lognormal_fleet, runtime,
                             sample_clients)
from repro.models.paper_models import FemnistCNN
from repro.optim import sgd

NUM_CLIENTS, COHORT, BATCH, SEED = 8, 4, 8, 0


class Recorder:
    """A trainer whose dataset logs every (client, key) it is asked for and
    whose executor logs the parts and stacked batches it is handed."""

    def __init__(self, policy=None, fleet=None, data=None):
        data = data or make_federated_image_data(num_clients=NUM_CLIENTS,
                                                 seed=0)
        self.sample = data.sample_batch
        self.samples, self.parts, self.stacked = [], [], []

        def sample(cid, key, batch, **kw):
            self.samples.append((int(cid), key))
            return self.sample(cid, key, batch, **kw)

        self.tr = FederatedTrainer(
            FemnistCNN(pq=None), sgd(0.03),
            dataclasses.replace(data, sample_batch=sample), cohort=COHORT,
            client_batch=BATCH, quantize=False, seed=SEED, fleet=fleet,
            policy=policy)
        stack = self.tr.stack_batches
        execute = self.tr.executor.execute

        def stack_batches(parts):
            out = stack(parts)
            self.stacked.append(out)
            return out

        def execute_logged(state, parts, *args):
            self.parts.append(list(parts))
            return execute(state, parts, *args)

        self.tr.stack_batches = stack_batches
        self.tr.executor.execute = execute_logged

    def expected(self, cid, key):
        return self.sample(cid, key, BATCH)


def _eq(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _concat(parts):
    return {k: jnp.concatenate([p[k] for p in parts], axis=0)
            for k in parts[0]}


def test_round_keys_and_batch_bitwise():
    rec = Recorder()
    key = jax.random.PRNGKey(7)
    state = rec.tr.init_state(key)
    rng = np.random.default_rng(SEED)
    for r in range(3):
        rec.samples.clear()
        round_key = jax.random.fold_in(key, r)
        state, _ = rec.tr.round(state, round_key)
        ids = sample_clients(rng, NUM_CLIENTS, COHORT)
        assert [c for c, _ in rec.samples] == [int(c) for c in ids]
        want = [jax.random.fold_in(round_key, int(c)) for c in ids]
        _eq([k for _, k in rec.samples], want)
        parts = [rec.expected(int(c), k) for c, k in zip(ids, want)]
        _eq(rec.parts[-1], parts)
        _eq(rec.stacked[-1], _concat(parts))


def test_cohort_batch_bitwise():
    rec = Recorder()
    key = jax.random.PRNGKey(3)
    batch = rec.tr.cohort_batch(key)
    ids = sample_clients(np.random.default_rng(SEED), NUM_CLIENTS, COHORT)
    want = [jax.random.fold_in(key, int(c)) for c in ids]
    _eq([k for _, k in rec.samples], want)
    _eq(batch, _concat([rec.expected(int(c), k) for c, k in zip(ids, want)]))


@pytest.mark.parametrize("policy,fleet", [
    (FullSync, None),
    (lambda: DropSlowestK(1),
     lambda: lognormal_fleet(NUM_CLIENTS, dropout_prob=0.3, seed=3)),
    (lambda: AsyncBuffer(2), lambda: lognormal_fleet(NUM_CLIENTS, seed=3)),
], ids=["full_sync", "drop_slowest", "async_buffer"])
def test_run_keys_and_batches_bitwise(monkeypatch, policy, fleet):
    """Each participant's key is fold_in(fold_in(key, version + 1), cid),
    whatever the survivor count or the mix of versions in a flush."""
    seen = []
    run = runtime.Scheduler.run

    def run_logged(self, steps, *, execute, **kw):
        def logged(update_idx, participants, weights):
            seen.append([(a.client, a.version) for a in participants])
            return execute(update_idx, participants, weights)
        return run(self, steps, execute=logged, **kw)

    monkeypatch.setattr(runtime.Scheduler, "run", run_logged)
    rec = Recorder(policy(), fleet and fleet())
    key = jax.random.PRNGKey(11)
    rec.tr.run(6, key)
    assert len(seen) == len(rec.parts) >= 4
    # the wire measurement's one sample comes first
    _eq(rec.samples[0][1], jax.random.fold_in(key, 0))
    samples = iter(rec.samples[1:])
    stacked = iter(rec.stacked)
    for who, got_parts in zip(seen, rec.parts):
        want = [jax.random.fold_in(jax.random.fold_in(key, v + 1), c)
                for c, v in who]
        got = [next(samples) for _ in who]
        assert [c for c, _ in got] == [c for c, _ in who]
        _eq([k for _, k in got], want)
        parts = [rec.expected(c, k) for (c, _), k in zip(who, want)]
        _eq(got_parts, parts)
        if not isinstance(rec.tr.policy, AsyncBuffer):
            _eq(next(stacked), _concat(parts))
    assert next(samples, None) is None
    if isinstance(rec.tr.policy, DropSlowestK):
        assert len({len(w) for w in seen}) > 1      # survivor counts vary
    if isinstance(rec.tr.policy, AsyncBuffer):
        assert any(len({v for _, v in w}) > 1 for w in seen)  # mixed versions


def test_warm_round_makes_no_eager_fold_in_or_concatenate(monkeypatch):
    """A warm round() derives its keys and joins its batches without one
    eager program per client or per leaf: calls made while tracing may only
    come in the first round. The dataset is a host lookup, as the
    benchmark's pool is, so every count is the round driver's own."""
    made = make_federated_image_data(num_clients=NUM_CLIENTS, seed=0)
    table = [made.sample_batch(c, jax.random.PRNGKey(c), BATCH)
             for c in range(NUM_CLIENTS)]
    lookup = dataclasses.replace(made, sample_batch=lambda c, k, b: table[c])
    rec = Recorder(data=lookup)
    counts = {"eager": 0, "traced": 0}

    def counted(fn):
        def wrapper(*args, **kw):
            traced = any(isinstance(x, jax.core.Tracer)
                         for x in jax.tree.leaves((args, kw)))
            counts["traced" if traced else "eager"] += 1
            return fn(*args, **kw)
        return wrapper

    monkeypatch.setattr(jax.random, "fold_in", counted(jax.random.fold_in))
    monkeypatch.setattr(jnp, "concatenate", counted(jnp.concatenate))
    key = jax.random.PRNGKey(0)
    state = rec.tr.init_state(key)
    counts.update(eager=0, traced=0)
    state, _ = rec.tr.round(state, key)
    assert counts["eager"] == 0, counts
    counts.update(eager=0, traced=0)
    state, metrics = rec.tr.round(state, key)
    jax.block_until_ready(metrics)
    assert counts == {"eager": 0, "traced": 0}
    # the wrappers do see the module's calls: the per-client helper's one
    rec.tr.client_batch_for(0, key)
    assert counts == {"eager": 1, "traced": 0}
