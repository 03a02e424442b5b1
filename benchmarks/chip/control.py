"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds <n>,<n>,... --seconds <s> [--control]

For each seed, in one process: a run of the cell as ``run.py`` makes it,
then the plain reference over the served logits and tokens of its sample
and, with ``--control``, the reference computed in int8 and in fp8 in the
program's place, each judged by the same ``verdict`` as the program. One
JSON line per seed on standard output: the program's numbers, checks and
``correct``, each control's, and the run's end-to-end metrics. The
benchmark's own runs never run the controls.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU; the readings are the chip's",
              file=sys.stderr)
        return 2
    from benchmarks.chip.bench import measure
    from benchmarks.chip.reference import CONTROLS
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        r = measure(cell, seed, args.seconds, False, t,
                    controls=CONTROLS if args.control else ())
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": r["correct"], "checks": r["checks"],
            "program": {"widest_logit_gap": r["widest_logit_gap"],
                        "off_argmax": r["off_argmax_share"]},
            "controls": r.get("controls"),
            "compared": r["served_tokens_compared"],
            "requests_compared": r["requests_compared"],
            "mix_requests_finished": r["mix_requests_finished"],
            "failed": r["failed"], "metrics": r["metrics"],
            "window_programs_compiled": r["window_programs_compiled"],
            "memory_peak_bytes": r["device"]["memory_peak_bytes"],
            "phases_s": r["phases_s"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
