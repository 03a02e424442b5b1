"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, when
traced, ``breakdown``; the numbers compared for ``correct`` come last,
each beside its limit, and are repeated as the last lines of standard
error. Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)

    import jax
    devices = jax.devices()
    chips = cell["workload"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: this cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). The "
              "benchmark measures the chip and never falls back.",
              file=sys.stderr)
        return 2
    from benchmarks.chip.bench import measure
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
