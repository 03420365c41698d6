#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

runs one cell of BENCHMARK.json on the machine it is started on and prints,
as the LAST line of its standard output, one JSON object with `correct`,
`attempted`, `failed`, `metrics` and `device` (and `breakdown` with
--trace 1), and last in it `compared`: each number `correct` rests on
beside its limit, which are also the last lines on standard error.
Everything else worth reading goes on earlier lines. Without the chips the
cell asks for it exits non-zero and prints no result.

This process stays off JAX: one child reaches the chip (benchmark/harness/
train_child.py). What kind of load a cell is, is the `driver` key of its
traffic file; the driver is benchmark/drivers/<driver>.py. What model
family it runs is its configuration's `model_type`; all the benchmark knows
of a family is benchmark/families/<model_type>.py.

Options beyond the contract's four, for builders and tests only:
  --rehearse     tiny shapes on the CPU, no metrics (`device` says cpu)
  --control P    also follow the steps with the reference at precision P
                 (fp8) and print its numbers beside the program's
  --fault F      break the timed path underneath (noop_step), to see
                 `correct` come out false
  --keep DIR     copy the reduced trace and the child's record into DIR
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.harness import spec as spec_lib  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    args.start_time = START
    try:
        manifest = spec_lib.load_manifest(ROOT)
        cell = spec_lib.find_cell(manifest, args.workload, ROOT)
        run = spec_lib.load_driver(cell["traffic"]["driver"], ROOT)
    except (spec_lib.SpecError, OSError, KeyError, ValueError) as e:
        print(f"[bench] {type(e).__name__}: {e}", flush=True)
        return 2
    code, line = run(cell, args, manifest)
    if line is not None:
        print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
