"""Readings that set the limits of a cell's check, at the cell's own
size, on the chip.

    python benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \\
        [--iters N]

For each seed it makes the cell's inputs as a run does and prints one
JSON line with the numbers the check compares, read for ``control``:
the reference with every product in bfloat16 (one MXU pass) in place of
the program, against the reference at full float32 precision: the step
down in precision that would tempt a change.

``--iters`` is the number of iterations a deconvolution run's window
reaches (its final iterate is compared).  The benchmark's own runs do
not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
from run import ROOT, chip_devices  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="N")
    args = ap.parse_args(argv)
    cell = cells.resolve(cells.load_benchmark(ROOT), args.workload,
                         args.rehearse)
    import jax
    chip_devices(jax, cell, bool(args.rehearse))
    import generate
    ref = cells.component("reference", cell.config["problem"])
    out = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        inputs = generate.make(cell.config, generate.seed_key(seed))
        observed = {"idx": ref.sample(cell, seed)}
        exact = ref.reference(cell, inputs, observed, args.iters, "highest")
        line = {"workload": cell.name, "seed": seed, "iters": args.iters,
                "control": ref.compare(
                    ref.reference(cell, inputs, observed, args.iters,
                                  "bfloat16"), exact),
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


if __name__ == "__main__":
    main()
