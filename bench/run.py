"""The benchmark's entry point: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout, on a machine that holds the chips the
cell asks for. It exits non-zero, and prints no result, without a TPU or
with fewer chips than the cell needs. The last line of standard output is
the result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics), ``device``
and ``checks`` (each number compared with its limit); with ``--trace 1``
also ``breakdown``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness
    result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
