"""Readings that a cell's limits are set from, many seeds in one process.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 [--faults 3]

For each seed: the program's first three updates against the reference
(the lower reading) and the control (the reference computed in the
precision below the configuration's, named in the cell's
``limits/<cell>.json``) against the reference. On the first ``--faults``
seeds also the faults, each against the reference (the upper readings): a
step that leaves half of the batch out (the reference put in the program's
place) and, in a cell on the mesh executor, the program with the exchange
of gradients between chips left out. A state left unchanged reads 1 on
``change`` by construction and needs no run. Prints one JSON line per seed
(with the per-leaf gaps) and appends them to
``bench/out/calibrate-<cell>.jsonl``. The benchmark's own runs never run
this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def no_exchange():
    """The mesh step traced with its cross-chip ``psum`` left out: each
    chip keeps the gradient of its own clients."""
    import jax
    psum = jax.lax.psum
    jax.lax.psum = lambda x, *_a, **_k: x
    try:
        yield
    finally:
        jax.lax.psum = psum


def _program(cell, seed):
    from bench import harness
    program = harness.start_program(cell, seed)
    batches = harness.checked_batches(cell, program)
    readings = program.readings
    del program
    gc.collect()
    return readings, batches


def _gaps(harness, got, ref):
    keep = harness.compared_leaves(ref["grad"])
    out = harness.compare(got, ref)
    out["leaves"] = {k: harness.leaf_gaps(got[k], ref[k], keep)
                     for k in ("grad", "change")}
    return out


def readings(root, workload, seed, faults=True):
    from bench import harness
    cell = harness.locate(root, workload)
    prog, batches = _program(cell, seed)
    ref = harness.reference_readings(cell, seed, batches)
    low = harness.reference_readings(cell, seed, batches,
                                     mode=cell.limits["control"])
    line = {"seed": seed, "program": _gaps(harness, prog, ref),
            "loss": ref["loss"], "control": _gaps(harness, low, ref)}
    if faults:
        half = harness.reference_readings(cell, seed, batches,
                                          half_batch=True)
        line["half_batch"] = harness.compare(half, ref)
        if cell.mix.get("executor") == "mesh":
            with no_exchange():
                cut, _ = _program(cell, seed)
            line["no_exchange"] = harness.compare(cut, ref)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3,
                    help="read the faults on this many of the first seeds")
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness
    cell = harness.locate(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    peaks = harness.read_json(cell.bench_dir / "peaks.json")["devices"]
    harness.devices_for(cell, peaks, require_tpu=True)
    out_dir = cell.bench_dir / "out"
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"calibrate-{args.workload}.jsonl", "a") as f:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            line = readings(ROOT, args.workload, seed, i < args.faults)
            line["seconds"] = time.perf_counter() - t
            f.write(json.dumps(line) + "\n")
            f.flush()
            brief = dict(line)
            for k in ("program", "control"):
                brief[k] = {m: v for m, v in line[k].items() if m != "leaves"}
            print(json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
