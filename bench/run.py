#!/usr/bin/env python3
"""Run one benchmark cell on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Sets up the cell named in ``BENCHMARK.json``,
warms every shape its window uses, serves its closed loop through
``PlanService`` for ``--seconds``, checks the replies against the plain
reference, and prints one JSON object as the last line of standard output
(the numbers compared, each beside its limit, are also the last lines of
standard error).  ``--trace 1`` reports the per-layer metrics from the
program's host spans and a profiler trace of the window; ``--trace 0`` the
end-to-end metrics, with tracing off.

Exits non-zero, printing no result, when JAX finds no accelerator or fewer
chips than the cell asks for, or when the system under test is missing.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # every compile lands in the checkout's own cache, at a fixed path
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import harness

    def log(**record):
        print(json.dumps(record), flush=True)

    try:
        result = harness.run_cell(
            harness.Bench(ROOT), args.workload, args.seed, args.seconds,
            bool(args.trace), T_PROCESS, log=log,
        )
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    checks = result["checks"]
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
