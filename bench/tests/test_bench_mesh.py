"""The four-chip mesh cell's harness on four virtual CPU devices, in a
process of its own: a sound run comes out correct, and a run with the
timed path broken underneath comes out not correct, for each fault the
mesh cell can have: a state returned unchanged, half of the cohort left
out, and the exchange of gradients between chips left out."""

import json
import os
import subprocess
import sys

import pytest

from benchkit import REPO

SCRIPT = r'''
import contextlib, json, sys, time
from pathlib import Path
sys.path[:0] = [sys.argv[2], sys.argv[3], sys.argv[4]]
import jax
import jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", False)
import benchkit
from bench import calibrate, harness

# the one-chip cell's limits: the mesh cell has none read on four chips yet
root, wl = benchkit.make_root(
    Path(sys.argv[1]), "femnist_cnn", mix="mesh4_cohort40", chips=4,
    limits=benchkit.load_json("limits/femnist_cnn.cohort10.json"))


def broken(build, fault):
    """The cell's trainer builder, with the executor broken underneath."""
    def build_broken(cfg, mix, seed, data):
        tr = build(cfg, mix, seed, data)
        execute = tr.executor.execute
        if fault == "state_unchanged":
            def run(state, parts, *a, **k):
                keep = jax.tree.map(jnp.copy, state)
                return keep, execute(state, parts, *a, **k)[1]
        else:   # half_batch: the mean over the first half of the cohort
            def run(state, parts, *a, **k):
                return execute(state, parts[:len(parts) // 2], *a, **k)
        tr.executor.execute = run
        return tr
    return build_broken


out = {}
for fault in ("sound", "state_unchanged", "half_batch", "no_exchange"):
    ctx = calibrate.no_exchange() if fault == "no_exchange" \
        else contextlib.nullcontext()
    real = harness.load_module
    if fault in ("state_unchanged", "half_batch"):
        def load(path, name, _f=fault):
            mod = real(path, name)
            if name.startswith("bench_config_"):
                mod.build_trainer = broken(mod.build_trainer, _f)
            return mod
        harness.load_module = load
    with ctx:
        res = harness.run(root, wl, 5, 0.5, False,
                          t_start=time.perf_counter(), require_tpu=False)
    harness.load_module = real
    out[fault] = {"correct": res["correct"], "checks": res["checks"],
                  "count": res["device"]["count"]}
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def mesh_runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh4")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(tmp), str(REPO),
         str(REPO / "src"), str(REPO / "bench" / "tests")],
        cwd=tmp, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_sound_run_is_correct(mesh_runs):
    assert mesh_runs["sound"]["correct"], mesh_runs["sound"]["checks"]
    assert mesh_runs["sound"]["count"] == 4


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_mesh_broken_timed_path_is_not_correct(mesh_runs, fault):
    assert not mesh_runs[fault]["correct"], mesh_runs[fault]["checks"]
