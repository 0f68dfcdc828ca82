#!/usr/bin/env python3
"""Find the knee of one configuration: its capacity under a backlog, then
open-loop Poisson load at several shares of that capacity, in one process
(set-up and compiles are paid once).

    python3 bench/sweep.py --config sift1m-ivf --seed 1 --seconds 8 \
        --shares 0.5,0.7,0.8,0.9,0.95,1.0,1.05

For each offered rate it prints the achieved rate, p50/p95 latency and
whether the backlog grew (p95 of the window's second half against its
first); the knee is the highest rate that sustains its load without a
growing backlog.  The rate a `share_of_knee` traffic mix offers is
`knee_rps` in the configuration file, written there from this table.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--shares", default="0.5,0.7,0.8,0.9,0.95,1.0,1.05")
    args = ap.parse_args()

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    import os

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench import gen, harness, registry

    reg = registry.Registry()
    cfg = reg.json("configs", args.config)
    backlog = reg.json("traffic", "backlog")
    seeds = gen.sub_seeds(args.seed)
    splits = {}
    clock = [T_START]

    def t_mark():
        now = time.perf_counter()
        dt, clock[0] = now - clock[0], now
        return dt

    data = harness.build(cfg, backlog, seeds, splits, t_mark)
    none = harness._annotate(False)
    srv = harness.Server(data["cache"], data["pool"], cfg["batch"], none)
    for j in range(3):
        jax.block_until_ready(srv.serve(j))
    cap = harness.run_backlog(srv, args.seconds, [], none)
    cap_rps = cap["served_rps"]
    rows = [{"offered_rps": None, "share": None, "kind": "backlog",
             "achieved_rps": cap_rps}]
    print(f"capacity (backlog, {args.seconds} s): {cap_rps:.2f} req/s",
          flush=True)
    for share in [float(s) for s in args.shares.split(",")]:
        rate = share * cap_rps
        due = gen.poisson_schedule(rate, args.seconds, seeds["arrivals"],
                                   multiple=cfg["batch"])
        out = harness.run_open_loop(srv, due, [], none, j0=srv.steps)
        lat = out["latency_ms"]
        half = lat.size // 2
        row = {"kind": "poisson", "share": share, "offered_rps": rate,
               "achieved_rps": out["requests"] / out["elapsed"],
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "p95_first_half_ms": float(np.percentile(lat[:half], 95)),
               "p95_second_half_ms": float(np.percentile(lat[half:], 95)),
               "gen_lag_p95_ms": float(np.percentile(out["gen_lag_ms"], 95))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"config": args.config, "capacity_rps": cap_rps,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
