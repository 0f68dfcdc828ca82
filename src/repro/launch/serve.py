"""Serving driver: continuous-batching decode with the AÇAI semantic cache
in front (the paper's edge-inference deployment).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
      --requests 40

`--policy` selects the semantic-cache tier's cache policy through the
unified policy registry (DESIGN.md §9) and `--policy-opt key=value`
(repeatable) passes spec params; `--remote-index` selects AÇAI's
remote-catalog index backend through the index registry (DESIGN.md §8)
and `--index-opt key=value` passes builder kwargs, e.g.:

  ... --policy sim_lru --policy-opt k_prime=8 --policy-opt augmented=true
  ... --remote-index nsw --index-opt beam=64 --index-opt steps=24
  ... --remote-index ivf --index-opt nlist=256 --index-opt nprobe=16
  ... --mesh-shards 4 --remote-index ivf_sharded --index-opt nlist=64

`--answer-cache CAP` fronts AÇAI's index with the exact answer-memo tier
(DESIGN.md §13) — repeated queries serve their memoized top-k without
touching the fused scans, bitwise identical to the uncached path — and
`--answer-cache-opt key=value` passes `AnswerCacheSpec` fields:

  ... --remote-index flat --answer-cache 4096
  ... --remote-index ivf --answer-cache 1024 --answer-cache-opt hit_ms=0.1
  ... --remote-index nsw --answer-cache 512 \
      --answer-cache-opt idle_unload_ms=50

`--churn-rate R` exercises the mutable catalog (DESIGN.md §10): the cache
starts on the warm `--churn-warm` fraction of the catalog and R insert+
expire events fire per request (a rolling window — new results admitted
online via SemanticCachedLM.add_documents, the oldest expired via
remove_documents), for any policy and index backend:

  ... --churn-rate 0.2 --remote-index ivf --index-opt nlist=32

`--mesh-shards P` serves the semantic-cache tier through the sharded
multi-device path (catalog + cache state sharded over a (1, P) mesh,
repro.core.distributed) — on hosts without accelerators it forces P
host-platform placeholder devices, so the XLA flag must be set before any
jax import (same discipline as launch/dryrun.py).  Churn composes with
the mesh (DESIGN.md §15): mutation is routed to owner shards and serving
goes through the sharded exact masked scan, so `--churn-rate` on a mesh
only rejects `--remote-index` (sharded index backends don't mutate
online yet):

  ... --mesh-shards 2 --churn-rate 0.2 --catalog 512

The `--remote-fault-*` flags inject a deterministic fault schedule into
the remote tier and route every request through the resilient serving
path (DESIGN.md §11): retries with capped backoff, optional hedging
(`--hedge-ms`), per-request deadlines (`--deadline-ms`), a circuit
breaker, and graceful degradation to the best local candidates when the
remote is down.  Works for any policy:

  ... --remote-fault-rate 0.2 --deadline-ms 250
  ... --remote-fault-outage 10:30 --policy sim_lru
  ... --remote-fault-latency-ms 40 --hedge-ms 80 --retries 3

`--arrival` switches the semantic-cache tier from fixed-batch querying to
the online serving engine (DESIGN.md §12): requests arrive on the virtual
clock per the chosen process, queue, and are coalesced by the dynamic
batch former (`--batch` max size, `--batch-window-ms` max wait); the
load is set as a fraction of the deterministic service model's capacity
(`--offered-load`), admission control sheds on a queue cap
(`--queue-cap`) and on hopeless deadlines (`--shed-deadline-ms`), and
`--slo-ms` reports goodput at that latency SLO:

  ... --arrival poisson --offered-load 0.8 --batch-window-ms 5 --slo-ms 25
  ... --arrival flash_crowd --offered-load 1.2 --queue-cap 64
  ... --arrival closed_loop --slo-ms 25
"""

from __future__ import annotations

import sys

def _sniff_mesh_shards(argv):
    """Pre-argparse peek at --mesh-shards (both `--mesh-shards P` and
    `--mesh-shards=P` forms); malformed values are left for argparse to
    report properly."""
    for i, tok in enumerate(argv):
        val = None
        if tok == "--mesh-shards" and i + 1 < len(argv):
            val = argv[i + 1]
        elif tok.startswith("--mesh-shards="):
            val = tok.split("=", 1)[1]
        if val is not None:
            try:
                return int(val)
            except ValueError:
                return 0
    return 0


if __name__ == "__main__":  # before jax starts a backend
    from repro.launch.mesh import cpu_host_devices

    _p = _sniff_mesh_shards(sys.argv)
    if _p > 1:
        cpu_host_devices(_p)

import argparse  # noqa: E402
import time      # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import ARCHS, SMOKE_ARCHS
from repro.core.policy_api import (PolicySpec, parse_policy_opts,
                                   registered_policies)
from repro.index.base import (IndexSpec, parse_index_opts,
                              registered_backends)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, shard_devices
from repro.models import init_params
from repro.serve import SemanticCachedLM, ServeEngine, generate
from repro.serve.arrivals import ARRIVAL_KINDS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--catalog", type=int, default=512)
    ap.add_argument("--cache-size", type=int, default=64)
    ap.add_argument("--mesh-shards", type=int, default=0,
                    help="shard the semantic-cache tier over a (1, P) mesh "
                         "(0 = single-device batched pipeline)")
    ap.add_argument("--remote-index", default="exact",
                    choices=("exact",) + registered_backends(),
                    help="remote-catalog index backend for the semantic "
                         "cache ('exact' = perfect-recall candidates)")
    ap.add_argument("--index-opt", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="index builder kwarg (repeatable), e.g. nlist=256")
    ap.add_argument("--answer-cache", type=int, default=None, metavar="CAP",
                    help="answer-cache tier entry budget (DESIGN.md §13): "
                         "memoize exact top-k index answers in front of "
                         "the fused scans (0 = pass-through machinery; "
                         "needs --remote-index, acai only)")
    ap.add_argument("--answer-cache-opt", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="AnswerCacheSpec field (repeatable), e.g. "
                         "hit_ms=0.1 idle_unload_ms=50")
    ap.add_argument("--policy", default="acai",
                    choices=registered_policies(),
                    help="semantic-cache policy (unified policy registry, "
                         "DESIGN.md §9)")
    ap.add_argument("--policy-opt", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="policy spec param (repeatable), e.g. k_prime=8 "
                         "augmented=true")
    ap.add_argument("--churn-rate", type=float, default=0.0,
                    help="catalog churn: insert+expire events per request "
                         "(rolling window over the catalog, DESIGN.md §10; "
                         "0 = frozen catalog)")
    ap.add_argument("--churn-warm", type=float, default=0.5,
                    help="fraction of --catalog live at start under churn "
                         "(the rest inserts over the run)")
    srv = ap.add_argument_group(
        "online serving (DESIGN.md §12; --arrival switches the semantic-"
        "cache tier onto the queued engine with dynamic batch formation)")
    srv.add_argument("--arrival", default="off",
                     choices=("off",) + ARRIVAL_KINDS,
                     help="arrival process driving the request queue "
                          "('off' = fixed-batch querying)")
    srv.add_argument("--offered-load", type=float, default=0.8,
                     help="open-loop arrival rate as a fraction of the "
                          "service model's max-batch capacity (1.0 = "
                          "critically loaded)")
    srv.add_argument("--batch-window-ms", type=float, default=5.0,
                     help="batch former max wait (virtual ms) before a "
                          "partial batch dispatches; 0 = pure size "
                          "trigger (the fixed-window/offline-equivalent "
                          "configuration)")
    srv.add_argument("--slo-ms", type=float, default=None,
                     help="latency SLO (virtual ms): report goodput = "
                          "served fraction meeting it")
    srv.add_argument("--queue-cap", type=int, default=None,
                     help="admission control: shed arrivals beyond this "
                          "queue depth")
    srv.add_argument("--shed-deadline-ms", type=float, default=None,
                     help="admission control: shed queued requests whose "
                          "estimated completion would exceed this budget")
    srv.add_argument("--arrival-seed", type=int, default=0,
                     help="arrival-schedule seed (same seed = same "
                          "schedule, bit for bit)")
    res = ap.add_argument_group(
        "resilient serving (DESIGN.md §11; any flag here switches the "
        "semantic-cache tier onto the resilient remote path)")
    res.add_argument("--remote-fault-rate", type=float, default=0.0,
                     help="per-attempt transient error probability")
    res.add_argument("--remote-fault-corrupt", type=float, default=0.0,
                     help="per-attempt corrupt-payload (NaN) probability")
    res.add_argument("--remote-fault-latency-ms", type=float, default=5.0,
                     help="median remote fetch latency (lognormal)")
    res.add_argument("--remote-fault-outage", action="append", default=[],
                     metavar="START:END",
                     help="hard outage window in request indices "
                          "(repeatable), e.g. 10:30")
    res.add_argument("--remote-fault-seed", type=int, default=0,
                     help="fault-schedule seed (same seed = same faults)")
    res.add_argument("--deadline-ms", type=float, default=None,
                     help="per-request deadline budget (virtual ms); a "
                          "late success counts as a miss")
    res.add_argument("--hedge-ms", type=float, default=None,
                     help="fire a hedged second request this far into a "
                          "slow attempt (tail-latency insurance)")
    res.add_argument("--retries", type=int, default=None,
                     help="extra attempts after the first (default 2)")
    args = ap.parse_args()
    enable_compile_cache()

    try:
        policy_spec = PolicySpec(args.policy,
                                 parse_policy_opts(args.policy_opt))
    except ValueError as e:
        raise SystemExit(str(e))
    if args.policy != "acai":
        if args.remote_index != "exact":
            raise SystemExit(
                f"--policy {args.policy} serves from the exact server "
                f"oracle; --remote-index only applies to acai")
        if args.mesh_shards > 1:
            raise SystemExit(
                f"--policy {args.policy} is a sequential baseline; "
                f"--mesh-shards only applies to acai")

    index_spec = None
    if args.remote_index != "exact":
        try:
            index_spec = IndexSpec(args.remote_index,
                                   parse_index_opts(args.index_opt))
        except ValueError as e:
            raise SystemExit(str(e))
        sharded = args.remote_index in registered_backends(sharded=True)
        if sharded and args.mesh_shards <= 1:
            raise SystemExit(
                f"--remote-index {args.remote_index} is a sharded backend: "
                f"pass --mesh-shards P (P > 1)")
        if not sharded and args.mesh_shards > 1:
            raise SystemExit(
                f"--remote-index {args.remote_index} is single-device; with "
                f"--mesh-shards use one of "
                f"{('exact',) + registered_backends(sharded=True)}")
    elif args.index_opt:
        raise SystemExit("--index-opt needs --remote-index")

    # answer-cache tier (DESIGN.md §13): memoize exact index answers
    from repro.serve import AnswerCacheSpec, parse_answer_cache_opts

    answer_cache = None
    if args.answer_cache is not None:
        if args.policy != "acai":
            raise SystemExit(
                f"--policy {args.policy} serves oracle-exact (memoized) "
                f"answers by construction; --answer-cache only applies "
                f"to acai")
        if args.mesh_shards > 1:
            raise SystemExit(
                "--answer-cache needs the single-device cache (the "
                "sharded step owns candidate generation)")
        if index_spec is None:
            raise SystemExit(
                "--answer-cache fronts an index backend: pass "
                "--remote-index (flat = the exact fused scan)")
        try:
            answer_cache = AnswerCacheSpec(
                capacity=args.answer_cache,
                **parse_answer_cache_opts(args.answer_cache_opt))
        except (TypeError, ValueError) as e:
            raise SystemExit(str(e))
    elif args.answer_cache_opt:
        raise SystemExit("--answer-cache-opt needs --answer-cache")

    if args.churn_rate < 0 or not 0.0 < args.churn_warm <= 1.0:
        raise SystemExit("--churn-rate must be >= 0 and --churn-warm in (0, 1]")
    if args.churn_rate > 0 and args.mesh_shards > 1 and index_spec is not None:
        raise SystemExit(
            "--churn-rate on a sharded mesh serves through the exact "
            "masked scan (DESIGN.md §15): drop --remote-index (mutating a "
            "sharded index backend online is a ROADMAP open item)")

    # resilient remote tier (DESIGN.md §11): any fault/deadline/hedge flag
    # switches the semantic-cache tier onto the resilient serving path
    from repro.serve.remote import FaultSpec, FaultyRemote, \
        parse_outage_windows
    from repro.serve.resilience import ResilienceConfig, RetryConfig

    faulty = (args.remote_fault_rate > 0 or args.remote_fault_corrupt > 0
              or args.remote_fault_outage
              or args.remote_fault_latency_ms != 5.0
              or args.remote_fault_seed != 0)
    resilient = (faulty or args.deadline_ms is not None
                 or args.hedge_ms is not None or args.retries is not None)
    remote = resilience = None
    if resilient:
        if args.mesh_shards > 1:
            raise SystemExit(
                "the resilient serving path needs the single-device cache "
                "(a fault-aware sharded step is a ROADMAP open item)")
        try:
            fault = FaultSpec(
                error_rate=args.remote_fault_rate,
                corrupt_rate=args.remote_fault_corrupt,
                latency_ms=args.remote_fault_latency_ms,
                outages=parse_outage_windows(args.remote_fault_outage),
                seed=args.remote_fault_seed)
        except ValueError as e:
            raise SystemExit(str(e))
        remote = FaultyRemote(fault)
        retry = (RetryConfig(max_retries=args.retries)
                 if args.retries is not None else RetryConfig())
        resilience = ResilienceConfig(deadline_ms=args.deadline_ms,
                                      retry=retry, hedge_ms=args.hedge_ms)

    mesh = None
    if args.mesh_shards > 1:
        try:
            devices = shard_devices(args.mesh_shards)
        except ValueError as e:
            raise SystemExit(f"--mesh-shards: {e}")
        if args.catalog % args.mesh_shards:
            raise SystemExit("--catalog must divide by --mesh-shards")
        mesh = make_mesh((1, args.mesh_shards), ("data", "model"),
                         devices=devices)

    cfg = (SMOKE_ARCHS if args.smoke else ARCHS)[args.arch]
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    rng = np.random.default_rng(0)

    # --- continuous batching engine -------------------------------------
    engine = ServeEngine(params, cfg, batch=args.batch,
                         s_max=args.prompt_len + args.max_tokens + 8)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab, args.prompt_len),
                           jnp.int32) for _ in range(args.requests)]
    t0 = time.time()
    for i, p in enumerate(prompts):
        engine.submit(i, p, args.max_tokens)
    steps = 0
    while engine.step():
        steps += 1
    dt = time.time() - t0
    total_tokens = sum(len(t) for t in engine.done.values())
    print(f"continuous batching: {len(engine.done)} requests, "
          f"{total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens / dt:.1f} tok/s), {steps} engine steps")

    # --- semantic cache tier ---------------------------------------------
    catalog = jnp.asarray(rng.normal(size=(args.catalog, cfg.d_model)),
                          jnp.float32)
    catalog = catalog / jnp.linalg.norm(catalog, axis=1, keepdims=True)
    payloads = [f"cached-result-{i}" for i in range(args.catalog)]

    def gen_fn(prompt_tokens):
        return generate(params, cfg, prompt_tokens[None], steps=4)

    # under churn the cache starts on the warm prefix of the catalog and
    # the cold rows stream in online (one expiry per insert: a rolling
    # window, the mutable-catalog regime of DESIGN.md §10)
    n_warm = (max(int(round(args.churn_warm * args.catalog)), 1)
              if args.churn_rate > 0 else args.catalog)
    if mesh is not None and n_warm % args.mesh_shards:
        # the sharded slab keeps its capacity a multiple of the mesh
        # (owner-shard routing is block arithmetic); round the warm
        # window up — --catalog already divides by --mesh-shards
        n_warm += args.mesh_shards - n_warm % args.mesh_shards
    lm = SemanticCachedLM(params, cfg, catalog[:n_warm], payloads[:n_warm],
                          gen_fn, h=args.cache_size, k=4, mesh=mesh,
                          index_spec=index_spec, policy_spec=policy_spec,
                          remote=remote, resilience=resilience,
                          answer_cache=answer_cache)
    insert_ptr, expire_ptr, acc = n_warm, 0, 0.0
    events = 0
    for i in range(args.requests):
        acc += args.churn_rate
        while acc >= 1.0 and insert_ptr < args.catalog:
            lm.add_documents(catalog[insert_ptr][None], [payloads[insert_ptr]])
            lm.remove_documents([expire_ptr])
            insert_ptr += 1
            expire_ptr += 1
            events += 1
            acc -= 1.0
        toks = jnp.asarray(rng.integers(0, cfg.vocab, args.prompt_len),
                           jnp.int32)
        lm.query(toks)
    s = lm.stats
    tier = (f"sharded x{args.mesh_shards}" if mesh is not None
            else "single-device")
    tier += f", policy={lm.policy_spec.to_dict()}"
    if args.policy == "acai":
        tier += f", index={(index_spec.to_dict() if index_spec else 'exact')}"
    if args.churn_rate > 0:
        tier += f", churn={args.churn_rate:g} ({events} insert/expire events)"
    print(f"semantic cache ({tier}): {s.requests} requests, "
          f"{s.served_local}/{s.requests * lm.k} objects local, "
          f"{s.generated} generations, NAG={lm.nag:.3f}")
    if lm.answer_cache is not None:
        st = lm.answer_cache.stats()
        print(f"answer cache (capacity={st['capacity']}): "
              f"hit rate {st['hit_rate']:.3f} "
              f"({st['hits']}/{st['hits'] + st['misses']}), "
              f"{st['entries']} entries, {st['invalidations']} "
              f"invalidations (remove={st['inv_remove']} "
              f"add={st['inv_add']} refresh={st['inv_refresh']}), "
              f"{st['scans_skipped']} scans skipped of "
              f"{st['scans'] + st['scans_skipped']}, "
              f"unloads={st['unloads']} reloads={st['reloads']}")
    if resilient:
        ses = lm.policy.session
        c = ses.counters
        pct = ses.latency_percentiles()
        print(f"resilience (fault={fault.to_dict()}): "
              f"{c.remote_failures} remote failures, {c.retries} retries, "
              f"{c.degraded} degraded, {c.shed} shed, "
              f"{c.deadline_misses} deadline misses, {c.hedges} hedges, "
              f"{c.fast_fails} breaker fast-fails, "
              f"{ses.breaker.transitions} breaker transitions, "
              f"p50={pct['p50_ms']:.1f}ms p99={pct['p99_ms']:.1f}ms")

    # --- online serving engine (DESIGN.md §12) ---------------------------
    if args.arrival != "off":
        from repro.serve import embed_prompt
        from repro.serve.arrivals import ArrivalSpec
        from repro.serve.queue import (AdmissionConfig, BatchFormerConfig,
                                       ServiceModel, serve_trace_online)

        service = ServiceModel()
        rate = args.offered_load * service.capacity_rps(args.batch)
        try:
            arrival = ArrivalSpec(kind=args.arrival, rate_rps=max(rate, 1.0),
                                  seed=args.arrival_seed)
        except ValueError as e:
            raise SystemExit(str(e))
        # arrival-side requests reuse the LM's own embedding map, so the
        # engine serves exactly what the semantic-cache tier would see
        prompt_batch = jnp.stack([
            jnp.asarray(rng.integers(0, cfg.vocab, args.prompt_len),
                        jnp.int32) for _ in range(args.requests)])
        reqs = jax.jit(jax.vmap(embed_prompt, in_axes=(None, 0)))(
            params, prompt_batch)
        out = serve_trace_online(
            lm.policy, np.asarray(reqs), arrival,
            former=BatchFormerConfig(
                max_batch=args.batch,
                max_wait_ms=(args.batch_window_ms
                             if args.batch_window_ms > 0 else None)),
            admission=AdmissionConfig(queue_cap=args.queue_cap,
                                      deadline_ms=args.shed_deadline_ms),
            service=service, slo_ms=args.slo_ms)
        line = (f"online serving (arrival={args.arrival} "
                f"load={args.offered_load:g} window={args.batch_window_ms:g}ms"
                f"): {out['served']}/{out['requests']} served, "
                f"{out['shed_total']} shed, mean batch "
                f"{out['mean_batch']:.2f}, p50={out['p50_ms']:.1f}ms "
                f"p99={out['p99_ms']:.1f}ms p999={out['p999_ms']:.1f}ms "
                f"(queue p50={out['queue_p50_ms']:.1f}ms)")
        if args.slo_ms is not None:
            line += f", goodput@{args.slo_ms:g}ms={out['goodput_slo']:.3f}"
        print(line)


if __name__ == "__main__":
    main()
