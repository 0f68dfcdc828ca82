#!/usr/bin/env python3
"""Run one cell of the AÇAI cache benchmark on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<mix>.json`).  With `--trace 0` the result carries the
cell's end-to-end metrics, with `--trace 1` its per-layer metrics, read
from a profiler trace of the window by `bench/metrics/<metric>.py`.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), then `checks`, each number the check compared beside its
limit; the same numbers are the last lines of standard error.  Off a TPU,
or with fewer chips than the cell asks for, it exits non-zero before any
work and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profile in this directory (default: a "
                         "temporary directory, removed once it is read)")
    args = ap.parse_args()

    from bench import registry

    bench = registry.load_benchmark()
    cell = registry.find_cell(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from bench import harness

    result = harness.run(bench, args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START, out_dir=args.trace_dir)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
