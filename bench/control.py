#!/usr/bin/env python3
"""The readings the correctness limits of one cell are set from, in one
process: the program's numbers on a dozen seeds or more, and the
control's on the first few.  The control is the reference computed in
bfloat16 (one MXU pass on bfloat16 inputs) put in the program's place on
the same kept steps; it has to come out not correct.

    python3 bench/control.py --workload sift1m-ivf.backlog --seconds 6 \
        --seeds 12 --control-seeds 3

Set-up is paid once: the catalog and the index are built for the first
seed, and each later seed draws its own requests and starts the cache
from its own initial state, as a fresh run would.  One JSON line per
seed, then a summary line with the largest program reading and the
smallest control reading of each number.

With `--fault <name>`, a fault of `bench/faults.py` is planted under the
timed path first: the "program" readings are then the fault's, which
have to come out above the limits.
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
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--fault", default=None,
                    help="plant this fault of bench/faults.py first")
    args = ap.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.fault:
        from bench import faults

        faults.plant(args.fault)
    readings = collect(args.workload, args.seconds, args.seeds,
                       args.control_seeds, args.first_seed)
    print(json.dumps(summary(readings)))
    return 0


def collect(workload, seconds, n_seeds, n_control, first_seed,
            cfg_override=None, traffic_override=None, bench=None):
    """[{seed, program: numbers, control: numbers or None}] over seeds."""
    import jax

    from repro.core import policy

    from bench import gen, harness, registry

    reg = registry.Registry()
    cell = registry.find_cell(bench or registry.load_benchmark(), workload)
    cfg = {**reg.json("configs", cell["config"]), **(cfg_override or {})}
    traffic = {**reg.json("traffic", cell["traffic"]),
               **(traffic_override or {})}
    clock = [T_START]

    def t_mark():
        now = time.perf_counter()
        dt, clock[0] = now - clock[0], now
        return dt

    out = []
    none = harness._annotate(False)
    data = None
    for i in range(n_seeds):
        seed = first_seed + 7919 * i
        seeds = gen.sub_seeds(seed)
        if data is None:
            data = harness.build(cfg, traffic, seeds, {}, t_mark)
            cache = data["cache"]
        else:
            ids = gen.request_ids(data["popularity"],
                                  traffic["pool_requests"], seeds["requests"])
            data["pool"] = gen.gather_rows(data["catalog"], ids)
            cache.state = cache._placed(policy.init_state(
                cfg["rows"], cache.cfg, seed=seeds["cache"]))
        srv = harness.Server(cache, data["pool"], cfg["batch"], none)
        for j in range(traffic["warmup_batches"]):
            jax.block_until_ready(srv.serve(j))
        keep_at = harness._keep_times(seconds, traffic["check_batches"],
                                      seeds["check"])
        if traffic["arrivals"] == "backlog":
            harness.run_backlog(srv, seconds, keep_at, none)
        else:
            due = harness.schedule(traffic, cfg, seconds, seeds["arrivals"])
            harness.run_open_loop(srv, due, keep_at, none, j0=srv.steps)
        prog, info = harness.check(reg, cfg, data, srv.kept, cache.index,
                                   seeds)
        ctl = None
        if i < n_control:
            ctl, _ = harness.check(reg, cfg, data, srv.kept, cache.index,
                                   seeds, control=True)
        row = {"seed": seed, "program": prog, "control": ctl, **info}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def summary(readings) -> dict:
    names = readings[0]["program"].keys()
    ctl = [r["control"] for r in readings if r["control"]]
    return {
        "seeds": len(readings), "control_seeds": len(ctl),
        "program_max": {n: max(r["program"][n] for r in readings)
                        for n in names},
        "control_min": {n: min(c[n] for c in ctl) for n in names} if ctl
        else None,
    }


if __name__ == "__main__":
    sys.exit(main())
