#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's gap and the
control's, over many seeds, in one process on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed this runs the cell as ``run.py`` does, for ``--seconds`` (its
own load and sizes, a short window), with the control in the program's
place in the check: the same float64 reference computed in float32, the
precision below the configuration's, on the inputs of the sampled
requests.  The harness's own decision then has to read ``correct`` false.
The program's widest gap on the same requests is read beside it.  The
lower reading of the limit is the largest program gap, the upper the
smallest control gap.  Prints one JSON line per seed and a summary line;
exits 1 when a seed's control reads correct.  The benchmark's own runs do
not run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import numpy as np

    import harness

    bench = harness.Bench(ROOT)
    lower, upper, passed = 0.0, float("inf"), []
    for seed in args.seeds:
        try:
            r = harness.run_cell(bench, args.workload, seed, args.seconds,
                                 False, T_PROCESS, control=np.float32)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        c = r["checks"]
        lower = max(lower, c["program_gap"]["value"])
        upper = min(upper, c["gap"]["value"])
        if r["correct"]:
            passed.append(seed)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "device": r["device"], "checks": c}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": lower, "upper": upper,
                      "control_read_correct": passed}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
