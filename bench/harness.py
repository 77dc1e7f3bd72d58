"""The benchmark harness: one run of one cell.

Everything that belongs to a cell is found by name under the benchmark's
directory (``bench/``):

  * ``BENCHMARK.json`` names the cell's configuration and traffic mix;
  * ``configs/<config>.json`` holds the configuration's sizes and
    ``configs/<config>.py`` builds the program's trainer from them;
  * ``traffic/<mix>.json`` holds the traffic parameters (``traffic.py``);
  * ``reference/<config>.py`` is the configuration's plain reference;
  * ``limits/<cell>.json`` holds the limits that decide ``correct``;
  * ``metrics/<metric>.py`` reads one per-layer metric from a traced run;
  * ``peaks.json`` holds the chips' published peaks, keyed by device kind.

A run loads the cell and makes its traffic and weights from the seed, then
drives the program's ``FederatedTrainer.round`` through its first three
server updates (which compile it) and reads what those updates did. It then
measures ``round`` in a loop for the given seconds, reads the peak memory,
frees the program's state, runs the reference over the same three updates
and compares. The last line of standard output is one JSON object.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Any, Dict, List

CHECKED_STEPS = 3        # server updates the reference follows
IN_FLIGHT = 2            # updates dispatched ahead of the one waited for
GRAD_GATE = 1e-3         # leaves whose reference gradient is under this
#                          share of the median leaf's are not compared


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json``, with every file it names."""
    name: str
    chips: int
    cfg: Dict[str, Any]
    builder: ModuleType
    mix: Dict[str, Any]
    limits: Dict[str, Any]
    reference: ModuleType
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{name}.py",
                           f"bench_metric_{name}")


def locate(root: Path, workload: str) -> Cell:
    """Find a cell's files by the names ``BENCHMARK.json`` gives."""
    from bench import traffic
    manifest = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg_file = root / configs[w["config"]]["file"]
    bench_dir = cfg_file.parent.parent
    cfg = read_json(cfg_file)
    return Cell(
        name=workload, chips=int(w["chips"]), cfg=cfg,
        builder=load_module(cfg_file.with_suffix(".py"),
                            f"bench_config_{w['config']}"),
        mix=traffic.load(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(bench_dir / "limits" / f"{workload}.json"),
        reference=load_module(bench_dir / "reference" / f"{w['config']}.py",
                              f"bench_reference_{w['config']}"),
        end_to_end=manifest["end_to_end"],
        per_layer=[m for m in manifest["per_layer"]
                   if workload in m.get("workloads", [workload])],
        bench_dir=bench_dir)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache`` (the directory is
    part of the cache key, so it never moves). Every program is cached,
    however quick its compile, so that set-up is the same in every run."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(cell: Cell, peaks: Dict[str, Any], require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips, JAX found "
                         f"{len(devs)}")
    devs = devs[:cell.chips]
    if require_tpu and devs[0].device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {devs[0].device_kind!r} "
                         f"in peaks.json; known: {sorted(peaks)}")
    return devs


class CompileCounter:
    """Counts JAX's compile and cache-load events while ``on``."""
    NAMES = ("backend_compile", "cache_retrieval")

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, *_args, **_kw):
        if self.on and any(n in event for n in self.NAMES):
            self.count += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._seen)


# ---------------------------------------------------------------------------
# the program's readings over its first updates
# ---------------------------------------------------------------------------

def _readers(cell: Cell, frozen):
    """Jitted readings of the program's state: per-leaf norms of the first
    gradient as the optimizer holds it, and of the parameters' change since
    the seed's initial parameters. Those are made anew from the seed by the
    same program that made them (inlined into another program, the TPU can
    round a bfloat16 weight one step apart), not kept."""
    import jax
    from bench import params as P
    opt = cell.cfg["optimizer"]
    diff = jax.jit(P.diff_norms)

    def change(params, key):
        return diff(params, P.make(frozen, key))

    if opt["name"] == "sgd":    # p1 = p0 - lr g
        def grad(state, key):
            return {k: v / opt["lr"]
                    for k, v in change(state.params, key).items()}
    elif opt["name"] == "adam":  # m1 = (1 - b1) g, m0 = 0
        m_norms = jax.jit(P.leaf_norms)

        def grad(state, key):
            return {k: v / (1.0 - opt.get("b1", 0.9))
                    for k, v in m_norms(state.opt_state["m"]).items()}
    else:
        raise ValueError(f"no first-gradient reading for {opt['name']!r}")
    return grad, change


def _host(tree):
    import jax
    return jax.tree.map(float, jax.device_get(tree))


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: List[str]) -> Dict[str, float]:
    """Per leaf, |‖prog‖ − ‖ref‖| over the larger of its reference norm and
    the median leaf's."""
    med = statistics.median(ref[k] for k in keep)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: List[str]) -> float:
    """The worst leaf's gap."""
    return max(leaf_gaps(prog, ref, keep).values())


def compared_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is nought to rounding (a key bias
    under softmax) move under Adam by round-off alone: leave them out by
    the reference's gradient, not by name."""
    med = statistics.median(ref_grad.values())
    return sorted(k for k, v in ref_grad.items() if v >= GRAD_GATE * med)


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    keep = compared_leaves(ref["grad"])
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    return {"loss": loss,
            "grad": leaf_gap(prog["grad"], ref["grad"], keep),
            "change": leaf_gap(prog["change"], ref["change"], keep)}


def reference_readings(cell: Cell, seed: int, batches, *, mode="highest",
                       half_batch=False) -> Dict[str, Any]:
    from bench import params as P
    from bench import traffic
    frozen = P.freeze(cell.builder.param_layout(cell.cfg))
    key = traffic.seed_key(seed)
    losses, grad, final = cell.reference.run(
        cell.cfg, cell.mix, P.make(frozen, key), batches, mode=mode,
        half_batch=half_batch)
    change = P.diff_norms(final, P.make(frozen, key))
    del final
    return {"loss": _host(losses), "grad": _host(grad),
            "change": _host(change)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Program:
    """The cell's trainer, its state, its traffic pool and its readings."""
    trainer: Any
    state: Any
    pool: Any
    readings: Dict[str, Any]


def start_program(cell: Cell, seed: int, annotate=None) -> Program:
    """Make the traffic and weights from the seed, build the trainer, and
    drive it through its first ``CHECKED_STEPS`` updates (the compile and
    warm-up), reading what they did."""
    import jax
    from bench import params as P
    from bench import traffic
    frozen = P.freeze(cell.builder.param_layout(cell.cfg))
    key = traffic.seed_key(seed)
    vocab = cell.cfg.get("arch", {}).get("vocab_size", 0)
    pool = traffic.Pool(traffic.make_batches(cell.mix, seed, vocab=vocab),
                        annotate=annotate)
    trainer = cell.builder.build_trainer(cell.cfg, cell.mix, seed, pool)
    P.check_layout(jax.eval_shape(P.make, frozen, key),
                   jax.eval_shape(trainer.model.init, key))
    from repro.core.fedlite import TrainState
    state = TrainState.create(P.make(frozen, key), trainer.optimizer)
    grad_of, change_of = _readers(cell, frozen)
    losses, grad = [], None
    for step in range(CHECKED_STEPS):
        state, metrics = trainer.round(state, key)
        losses.append(metrics["loss"])
        if step == 0:
            grad = grad_of(state, key)
    change = change_of(state.params, key)
    readings = {"loss": _host(losses), "grad": _host(grad),
                "change": _host(change)}
    jax.block_until_ready(state)
    return Program(trainer, state, pool, readings)


def checked_batches(cell: Cell, program: Program):
    """The stacked cohort batches the first ``CHECKED_STEPS`` updates were
    fed, rebuilt from the pool's log of requests."""
    cohort = int(cell.mix["cohort"])
    req = program.pool.requests
    return [program.pool.cohort_batch(req[s * cohort:(s + 1) * cohort])
            for s in range(CHECKED_STEPS)]


def measure(program: Program, seconds: float, key, annotate=None):
    """Call ``round`` in a loop for ``seconds``, keeping ``IN_FLIGHT``
    updates queued on the device; block on the last state. Returns
    (updates, elapsed seconds, device losses, host seconds in round)."""
    import jax
    ann = annotate or (lambda _name: contextlib.nullcontext())
    trainer, state = program.trainer, program.state
    losses, host_round = [], 0.0
    with ann("window"):
        t0 = time.perf_counter()
        while True:
            a = time.perf_counter()
            with ann("round"):
                state, metrics = trainer.round(state, key)
            host_round += time.perf_counter() - a
            losses.append(metrics["loss"])
            if len(losses) > IN_FLIGHT:
                with ann("block"):
                    losses[-1 - IN_FLIGHT].block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with ann("block"):
            jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
    program.state = state
    return len(losses), elapsed, losses, host_round


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, require_tpu: bool = True) -> Dict[str, Any]:
    """One run; returns the result object (the caller prints it)."""
    import jax
    from bench import traffic
    from bench import tracing
    cell = locate(root, workload)
    peaks = read_json(cell.bench_dir / "peaks.json")["devices"]
    enable_compile_cache(root)
    devs = devices_for(cell, peaks, require_tpu)
    annotate = jax.profiler.TraceAnnotation if trace else None
    counter = CompileCounter()
    try:
        program = start_program(cell, seed, annotate)
        setup_s = time.perf_counter() - t_start
        key = traffic.seed_key(seed)
        trace_dir = cell.bench_dir / "out" / "trace" / workload
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        counter.on = True
        try:
            n, elapsed, losses, host_round = measure(program, seconds, key,
                                                     annotate)
        finally:
            counter.on = False
            if trace:
                jax.profiler.stop_trace()
    finally:
        counter.close()
    losses = [float(x) for x in jax.device_get(losses)]
    failed = sum(not math.isfinite(x) for x in losses)
    stats = [d.memory_stats() or {} for d in devs]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    result: Dict[str, Any] = {"correct": False, "attempted": n,
                              "failed": failed, "metrics": {},
                              "device": device}
    if trace:
        tr = tracing.load(str(trace_dir), cell.chips)
        window = tr.window()
        busy = [tracing.busy_ns(evs, window) for evs in tr.devices.values()]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (window[1] - window[0]) * 1e-9
        ctx = SimpleNamespace(
            trace=tr, window=window, window_s=device["window_s"],
            busy_s=device["busy_s"], updates=n, host_round_s=host_round,
            compiles=counter.count, chips=cell.chips,
            peaks=peaks.get(device["kind"]), cfg=cell.cfg, mix=cell.mix,
            builder=cell.builder)
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        first = next(iter(tr.devices.values()))
        result["breakdown"] = {
            "device_ops": tracing.top_ops(first, window),
            "idle_gaps": sorted(
                ([k, v] for k, v in tracing.label_gaps(
                    tracing.gaps(first, window), tr.host).items()),
                key=lambda kv: -kv[1])[:10]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = {"updates_per_s": n / elapsed, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    # the reference runs once the window has closed, the peak memory has
    # been read and the program's state is freed
    batches = checked_batches(cell, program)
    prog = program.readings
    del program
    gc.collect()
    gaps = compare(prog, reference_readings(cell, seed, batches))
    limits = cell.limits["limits"]
    result["correct"] = failed == 0 and all(
        gaps[k] <= limits[k] for k in limits)
    result["checks"] = {k: {"value": gaps[k], "limit": limits[k]}
                        for k in limits}
    return result


def report(result: Dict[str, Any], out=sys.stdout, err=sys.stderr) -> None:
    """The numbers compared, beside their limits, as the last lines on
    standard error; the result as the last line on standard output."""
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
