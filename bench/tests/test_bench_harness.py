"""The harness end to end on the CPU, at small sizes, from a directory it
has never seen: it finds a configuration, a traffic mix, a reference,
limits and metric readers by name; sound runs come out correct; a run with
the timed path broken underneath, and the control, come out not correct."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchkit import BENCH, REPO, make_root


def run_cell(root, workload, seed=5, seconds=0.5):
    from bench import harness
    return harness.run(root, workload, seed, seconds, False,
                       t_start=time.perf_counter(), require_tpu=False)


def test_finds_every_file_by_name(tmp_path, cpu_jax):
    from bench import harness
    root, wl = make_root(tmp_path, "femnist_cnn", name="unseen")
    cell = harness.locate(root, wl)
    for mod in (cell.builder, cell.reference):
        assert str(tmp_path) in mod.__file__
    assert cell.mix["cohort"] == 2 and cell.cfg["name"] == "femnist_cnn"
    reader = cell.metric_reader("mfu")
    assert str(tmp_path) in reader.__file__ and callable(reader.read)


@pytest.mark.parametrize("config", ["femnist_cnn", "starcoder2_3b_d6"])
def test_sound_run_is_correct(tmp_path, cpu_jax, config):
    root, wl = make_root(tmp_path, config)
    res = run_cell(root, wl)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"updates_per_s", "setup_s"}
    assert res["metrics"]["updates_per_s"]["value"] > 0


BROKEN = {
    # a step that returns its state unchanged
    "state_unchanged": '''
def _break(tr):
    import jax
    loss = lambda s, b: tr.model.loss(s.params, b)[0]
    tr.executor._step = jax.jit(lambda s, b: (s, {"loss": loss(s, b)}))
''',
    # half of the batch left out, the mean taken over the rest
    "half_batch": '''
def _break(tr):
    stack = tr.stack_batches
    tr.stack_batches = lambda parts: stack(parts[:len(parts) // 2])
''',
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
@pytest.mark.parametrize("config", ["femnist_cnn", "starcoder2_3b_d6"])
def test_broken_timed_path_is_not_correct(tmp_path, cpu_jax, config, fault):
    src = (BENCH / "configs" / f"{config}.py").read_text() + BROKEN[fault] \
        + '''
_build = build_trainer


def build_trainer(cfg, mix, seed, data):
    tr = _build(cfg, mix, seed, data)
    _break(tr)
    return tr
'''
    root, wl = make_root(tmp_path, config, builder_src=src)
    res = run_cell(root, wl)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("config", ["femnist_cnn", "starcoder2_3b_d6"])
def test_control_is_not_correct(tmp_path, cpu_jax, config):
    """The reference in the precision below the configuration's, put in the
    program's place, fails at least one of the cell's limits."""
    from bench import calibrate, harness
    root, wl = make_root(tmp_path, config)
    cell = harness.locate(root, wl)
    line = calibrate.readings(root, wl, 9)
    limits = cell.limits["limits"]
    assert any(line["control"][k] > limits[k] for k in limits), line
    assert any(line["half_batch"][k] > limits[k] for k in limits), line


def _run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "femnist_cnn.cohort10",
         "--seed", "3", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run_cli(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    import shutil
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())
