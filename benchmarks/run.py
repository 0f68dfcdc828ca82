# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark driver.

Default: reduced CPU-friendly sizes (minutes).  ``--full`` = paper-scale.
``--suite NAME`` runs a single harness; ``--list`` prints every
registered suite, experiment grid, policy and trace scenario.

The fig1..fig8 suites are thin wrappers over named grids of the
config-driven experiment harness (``benchmarks/experiments.py``); the
``experiments`` suite is the canonical cross-policy × cross-scenario
sweep and writes BENCH_experiments.json at the repo root.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", "--suite", dest="only", default=None,
                    help="run a single suite (e.g. --suite experiments)")
    ap.add_argument("--trace", default=None,
                    help="restrict to one trace scenario (sift|amazon "
                         "aliases or any registered scenario name)")
    ap.add_argument("--list", action="store_true",
                    help="print registered suites/grids/policies/traces")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import cpu_host_devices

    # the sharded suites build meshes of up to 8 shards in this process
    cpu_host_devices(8)
    enable_compile_cache()
    from benchmarks import (answer_cache_bench, backends_bench, churn_bench,
                            distributed_bench,
                            experiments, fig1_gain_vs_requests,
                            fig2_gain_vs_h, fig3_gain_vs_cf, fig4_gain_vs_k,
                            fig5_sensitivity, fig6_mirror_maps, fig7_dissect,
                            fig8_rounding, kernel_bench, regret,
                            resilience_bench, serve_bench, serving_bench)

    # name -> (fn, trace kinds, BENCH json the suite writes at the repo
    # root, or None for CSV-only suites) — the third column is what
    # ``--list`` prints and ``scripts/check_bench_schema.py`` validates
    suites = {
        "fig1": (fig1_gain_vs_requests.main, ["sift", "amazon"], None),
        "fig2": (fig2_gain_vs_h.main, ["sift"], None),
        "fig3": (fig3_gain_vs_cf.main, ["sift"], None),
        "fig4": (fig4_gain_vs_k.main, ["sift"], None),
        "fig5": (fig5_sensitivity.main, ["sift"], None),
        "fig6": (fig6_mirror_maps.main, ["sift"], None),
        "fig7": (fig7_dissect.main, ["sift", "amazon"], None),
        "fig8": (fig8_rounding.main, ["amazon"], None),
        "regret": (regret.main, ["sift"], None),
        "kernels": (kernel_bench.main, ["sift"], None),
        "serve": (serve_bench.main, ["sift"], None),
        # batched request pipeline: the B∈{1,8,64} throughput trajectory,
        # tracked per PR
        "pipeline": (serve_bench.pipeline_main, ["sift"],
                     "BENCH_pipeline.json"),
        # sharded multi-device replay, in process (8 virtual devices on
        # the CPU): shards∈{1,4,8} × B∈{8,64}
        "distributed": (distributed_bench.main, ["sift"],
                        "BENCH_distributed.json"),
        # unified-index-API sweep: every registered backend × B∈{8,64},
        # NAG + p50 latency + recall vs flat
        "backends": (backends_bench.main, ["sift"], "BENCH_backends.json"),
        # unified-policy-API sweep: every registered policy × every
        # registered trace scenario
        "experiments": (experiments.main, [None], "BENCH_experiments.json"),
        # mutable-catalog sweep: rolling_catalog churn rates × policies +
        # the refresh-amortization curve
        "churn": (churn_bench.main, ["sift"], "BENCH_churn.json"),
        # resilient serving tier: fault scenarios × policies through the
        # retry/degrade ladder (DESIGN.md §11)
        "resilience": (resilience_bench.main, ["sift"],
                       "BENCH_resilience.json"),
        # online serving engine: arrival processes × offered loads ×
        # policies through the queue/batch-former/admission path
        # (DESIGN.md §12); asserts the fixed-window bitwise pin against
        # make_replay_batched every run
        "serving": (serving_bench.main, ["sift"], "BENCH_serving.json"),
        # answer-cache tier: exact top-k memoization hit-rate/latency/NAG
        # across zipf / flash_crowd / rolling_catalog × cache on/off
        # (DESIGN.md §13); asserts NAG-neutrality (bitwise gain, state and
        # served-id parity vs the pass-through arm) every run
        "answer_cache": (answer_cache_bench.main, ["sift"],
                         "BENCH_answer_cache.json"),
    }

    if args.list:
        print("registered suites:")
        for name, (_fn, kinds, bench_json) in suites.items():
            ks = ",".join(k or "all-traces" for k in kinds)
            out = f" -> {bench_json}" if bench_json else ""
            print(f"  {name:12s} ({ks}){out}")
        print(experiments.list_grids())
        return

    print("name,us_per_call,derived")
    failures = 0
    for name, (fn, kinds, _bench_json) in suites.items():
        if args.only and args.only != name:
            continue
        for kind in ([args.trace] if args.trace else kinds):
            t0 = time.time()
            try:
                fn(args.full, kind)
                print(f"# {name}/{kind} done in {time.time() - t0:.0f}s",
                      file=sys.stderr)
            except SystemExit as e:  # a suite (or its subprocess wrapper)
                # called sys.exit: a non-zero/None-coded exit is a dead
                # suite and must fail the run, not silently end it
                if e.code not in (0, None):
                    failures += 1
                    print(f"# {name}/{kind} FAILED (exit {e.code}) "
                          f"after {time.time() - t0:.0f}s",
                          file=sys.stderr)
            except Exception:  # noqa: BLE001 — keep the suite running
                failures += 1
                print(f"# {name}/{kind} FAILED after {time.time() - t0:.0f}s",
                      file=sys.stderr)
                traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
