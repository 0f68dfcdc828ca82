"""One benchmark run of one cell: set-up, the measured window, the trace,
the correctness check, and the result.

The program is driven only through its serving entry point,
`repro.core.policy.AcaiCache.serve_update_batch`, with host arrays of B
requests; batches form by size only, so one compiled step serves the
window.  Two arrival kinds drive it (`arrivals` in the traffic file):

* `backlog`: every request is queued from the start; batches go out back
  to back, at most `INFLIGHT` ahead of the device, until `seconds` have
  passed; the window ends when the last one completes.
* `poisson`: open loop; requests fall due on a seeded
  schedule (`bench/gen.py`), a batch goes out when its last request is
  due, and a request's latency runs from its due time to the host-seen
  completion of its batch.

The check (`bench/reference.py`) reruns a seeded sample of the window's
steps from the state the program had before each, once the window has
closed and the program's state is freed.
"""

from __future__ import annotations

import contextlib
import gc
import json
import queue
import shutil
import sys
import threading
import time

import jax
import numpy as np

from bench import gen, reference, registry, tracereduce

CHECKED = ("cost_gap", "gain_gap", "state_gap")
INFLIGHT = 2    # backlog: batches dispatched ahead of the device


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Compiles:
    """Counts JAX's compile events (tracing, lowering, backend compiles and
    compile-cache loads) while `on`."""

    def __init__(self):
        self.on, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, secs: float, **_):
        if self.on and ("compile" in event or "jaxpr_trace" in event
                        or "cache_retrieval" in event):
            self.events.append(event)


class GcPauses:
    """Times the garbage collector's sweeps until `stop`."""

    def __init__(self):
        self.t0, self.by_gen = {}, {}
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict):
        gen = info["generation"]
        if phase == "start":
            self.t0[gen] = time.perf_counter()
        elif gen in self.t0:
            dt = time.perf_counter() - self.t0.pop(gen)
            self.by_gen.setdefault(gen, []).append(dt)

    def stop(self):
        gc.callbacks.remove(self._on)

    def summary(self) -> str:
        return "gc sweeps in the window: " + (", ".join(
            f"gen{g} {len(v)} (max {max(v) * 1e3:.1f} ms, total "
            f"{sum(v) * 1e3:.1f} ms)" for g, v in sorted(self.by_gen.items()))
            or "none")


def _annotate(on: bool):
    if on:
        return lambda name: jax.profiler.TraceAnnotation(name)
    return lambda name: contextlib.nullcontext()


# -- set-up ------------------------------------------------------------------

def build(cfg: dict, traffic: dict, seeds: dict, splits: dict, t_mark):
    """The catalog, the request pool and the cache; returns a dict of them.
    `t_mark(name)` books the time since the previous mark under `name`."""
    from repro.core import oma, policy
    from repro.index import IndexSpec

    catalog = gen.make_catalog(cfg["rows"], cfg["dim"], cfg["catalog_seed"])
    c_f = gen.fetch_cost(catalog, kth=cfg["c_f_kth"],
                         sample=cfg["c_f_sample"])
    pop = traffic["popularity"]
    assert pop["model"] == "barycentric_zipf", pop
    lam = gen.popularity_from_distances(
        gen.barycentric_distances(catalog), pop["zipf_a"])
    ids = gen.request_ids(lam, traffic["pool_requests"], seeds["requests"])
    pool = gen.gather_rows(catalog, ids)
    splits["data"] = t_mark()

    spec = dict(cfg["index"])
    backend = spec.pop("backend")
    acfg = policy.AcaiConfig(
        h=cfg["h"], k=cfg["k"], c_f=c_f, c_remote=cfg["c_remote"],
        c_local=cfg["c_local"],
        oma=oma.OMAConfig(eta=cfg["eta_times_c_f"] / c_f),
        index=None if backend == "exact" else IndexSpec(backend, spec))
    cache = policy.AcaiCache(catalog, acfg, seed=seeds["cache"])
    jax.block_until_ready(cache.state)
    splits["index_build"] = t_mark()
    return {"catalog": catalog, "c_f": c_f, "pool": pool, "cache": cache,
            "eta": cfg["eta_times_c_f"] / c_f, "popularity": lam}


class Server:
    """Calls the program's serving entry point and keeps what the check
    needs of the steps it was asked to keep."""

    def __init__(self, cache, pool: np.ndarray, batch: int, span):
        self.cache, self.pool, self.b = cache, pool, batch
        self.n_batches = pool.shape[0] // batch
        self.steps = 0            # calls so far, set-up included
        self.kept = []            # steps the check reruns
        self.span = span

    def batch(self, j: int) -> np.ndarray:
        j %= self.n_batches
        return self.pool[j * self.b:(j + 1) * self.b]

    def serve(self, j: int, keep: bool = False):
        reqs = self.batch(j)
        before = self.cache.state
        with self.span("bench.dispatch"):
            m = self.cache.serve_update_batch(reqs)
        if keep:
            self.kept.append({"step": self.steps, "reqs": reqs,
                              "before": before, "after": self.cache.state,
                              "metrics": m})
        self.steps += 1
        return m


def _log_slowest(what: str, values: np.ndarray, at: np.ndarray,
                 top: int = 3) -> None:
    """The window's `top` largest `values` with the second of the window
    each began at: where a stall in the window shows."""
    if values.size == 0:
        return
    idx = np.argsort(values)[::-1][:top]
    log(f"slowest {what}: " + ", ".join(
        f"{values[i]:.1f} at {at[i]:.2f} s" for i in idx)
        + f"; median {np.median(values):.1f}")


def _keep_times(seconds: float, count: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return sorted(rng.uniform(0.0, seconds, count))


# -- the measured window -------------------------------------------------------

def run_backlog(srv: Server, seconds: float, keep_at, span):
    """Back-to-back batches for `seconds`; returns host timings."""
    inflight, metrics, host_ms, at = [], [], [], []
    keep_at = list(keep_at)
    j = 0
    t0 = time.perf_counter()
    with span("bench.window"):
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if len(inflight) >= INFLIGHT:
                with span("bench.wait"):
                    jax.block_until_ready(inflight.pop(0))
                now = time.perf_counter()
            keep = False
            while keep_at and now - t0 >= keep_at[0]:
                keep_at.pop(0)
                keep = True
            t = time.perf_counter()
            m = srv.serve(j, keep)
            host_ms.append((time.perf_counter() - t) * 1e3)
            at.append(t - t0)
            inflight.append(m)
            metrics.append(m)
            j += 1
        with span("bench.wait"):
            jax.block_until_ready(inflight)
        t_end = time.perf_counter()
    _log_slowest("host ms per call", np.asarray(host_ms), np.asarray(at))
    return {"requests": j * srv.b, "elapsed": t_end - t0,
            "served_rps": j * srv.b / (t_end - t0), "dispatch_ms": host_ms,
            "metrics": metrics, "batches": j}


def schedule(traffic: dict, cfg: dict, seconds: float, seed: int):
    """Due times of the window's requests.  A Poisson mix draws its
    arrivals from its `arrival_seed` and lets `seed` order the batches'
    runs of them (`gen.poisson_schedule`)."""
    kind = traffic["arrivals"]
    rate = traffic_rate(traffic, cfg)
    if kind == "poisson":
        return gen.poisson_schedule(rate, seconds, traffic["arrival_seed"],
                                    multiple=cfg["batch"], order_seed=seed)
    raise ValueError(f"unknown arrival kind {kind!r}")


def traffic_rate(traffic: dict, cfg: dict) -> float:
    """Offered requests per second: a number in the traffic file, or a
    share of the knee recorded in the configuration file."""
    rate = traffic["rate"]
    if "rps" in rate:
        return float(rate["rps"])
    return float(rate["share_of_knee"]) * float(cfg["knee_rps"])


def run_open_loop(srv: Server, due: np.ndarray, keep_at, span, j0: int = 0):
    """Open loop over the schedule `due` (seconds from the window's
    start); batches of B form by size.  Returns host timings per request
    and per batch."""
    b = srv.b
    n_batches = due.shape[0] // b
    formed = due[b - 1::b][:n_batches]
    dispatched = np.zeros(n_batches)
    done = np.zeros(n_batches)
    host_ms, metrics = [], []
    keep_at = list(keep_at)
    pending: queue.Queue = queue.Queue()

    def completer():
        while True:
            item = pending.get()
            if item is None:
                return
            i, m = item
            jax.block_until_ready(m)
            done[i] = time.perf_counter()

    thread = threading.Thread(target=completer, daemon=True)
    t0 = time.perf_counter()
    thread.start()
    with span("bench.window"):
        for i in range(n_batches):
            target = t0 + formed[i]
            with span("bench.fill"):
                while True:
                    left = target - time.perf_counter()
                    if left <= 0:
                        break
                    time.sleep(left - 5e-4 if left > 1e-3 else 0)
            now = time.perf_counter()
            keep = False
            while keep_at and now - t0 >= keep_at[0]:
                keep_at.pop(0)
                keep = True
            dispatched[i] = now
            m = srv.serve(j0 + i, keep)
            host_ms.append((time.perf_counter() - now) * 1e3)
            metrics.append(m)
            pending.put((i, m))
        pending.put(None)
        with span("bench.wait"):
            thread.join()
    _log_slowest("service ms (dispatch to completion)",
                 (done - dispatched) * 1e3, dispatched - t0)
    _log_slowest("host ms per call", np.asarray(host_ms), dispatched - t0)
    req_batch = np.arange(n_batches * b) // b
    due_abs = t0 + due[:n_batches * b]
    latency = (done[req_batch] - due_abs) * 1e3
    return {"requests": n_batches * b, "latency_ms": latency,
            "queue_wait_ms": (dispatched[req_batch] - due_abs) * 1e3,
            "gen_lag_ms": (dispatched - (t0 + formed)) * 1e3,
            "dispatch_ms": host_ms, "metrics": metrics,
            "batches": n_batches,
            "elapsed": float(done.max() - t0)}


# -- the check -----------------------------------------------------------------

def check(reg, cfg: dict, data: dict, kept: list, index, seeds: dict,
          precision: str = "float32", control: bool = False) -> tuple:
    """Reruns each kept step on the reference; returns (numbers, info).
    With `control`, the reference computed in bfloat16 stands in for the
    program's outputs."""
    params = reference.StepParams(
        k=cfg["k"], c_remote=cfg["c_remote"], c_local=cfg["c_local"],
        h=cfg["h"], c_f=data["c_f"], eta=data["eta"])
    spec = dict(cfg["index"])
    backend = spec.pop("backend")
    remote_mod = reg.module("remote", backend)
    catalog = data["catalog"]
    ref = reference.Reference(
        catalog, params, remote_mod.Remote(catalog, spec, index, precision))
    ctl = None
    if control:
        ctl = reference.Reference(
            catalog, params,
            remote_mod.Remote(catalog, spec, index, "bfloat16"), "bfloat16")
    exact = (None if backend == "flat" else
             reg.module("remote", "flat").Remote(catalog, {}, None, precision))

    keys = reference.round_keys(seeds["cache"], [k["step"] for k in kept])
    numbers = {name: 0.0 for name in CHECKED}
    tied = fill_ties = checked = 0
    hits = []
    for k in kept:
        y0 = np.asarray(k["before"].y)
        x0 = np.asarray(k["before"].x)
        u = np.asarray(jax.random.uniform(keys[k["step"]], y0.shape,
                                          np.float32))
        r = ref.step(k["reqs"], y0, x0, u)
        if ctl is not None:
            prog = ctl.step(k["reqs"], y0, x0, u)
        else:
            m = k["metrics"]
            prog = {"cost": np.asarray(m.cost),
                    "gain_int": np.asarray(m.gain_int),
                    "gain_frac": np.asarray(m.gain_frac),
                    "y": np.asarray(k["after"].y),
                    "x": np.asarray(k["after"].x)}
        got = reference.compare(prog, r, cfg["h"])
        for name in CHECKED:
            numbers[name] = max(numbers[name], got[name])
        tied += got["tied"]
        fill_ties += got["fill_ties"]
        checked += len(k["reqs"])
        top = cfg["k"]
        best = r["remote_ids"] if exact is None else exact.topk(
            np.asarray(k["reqs"]), top + 1, np.zeros(len(k["reqs"])))[1]
        hits += [len(set(a[:top]) & set(b[:top])) / top
                 for a, b in zip(r["remote_ids"], best)]
    if ctl is not None and hasattr(ctl.remote, "assign"):
        numbers.update(ref.remote.verify(own=ctl.remote.assign()))
    else:
        numbers.update(ref.remote.verify())
    info = {"checked_steps": len(kept), "checked_requests": checked,
            "tied_requests": tied, "fill_ties": fill_ties,
            "recall_at_k": float(np.mean(hits)) if hits else None}
    return numbers, info


# -- one run -------------------------------------------------------------------

def run(bench: dict, cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, reg=None, out_dir=None, control: bool = False,
        cfg_override=None, traffic_override=None) -> dict:
    """One run of `cell_name`; returns the result object.  `t_start` is the
    process's start on the host clock.  `cfg_override` and
    `traffic_override` replace entries of the cell's files (tests)."""
    reg = reg or registry.Registry()
    cell = registry.find_cell(bench, cell_name)
    cfg = {**reg.json("configs", cell["config"]), **(cfg_override or {})}
    traffic = {**reg.json("traffic", cell["traffic"]),
               **(traffic_override or {})}
    seeds = gen.sub_seeds(seed)
    splits = {}
    clock = [t_start]

    def t_mark():
        now = time.perf_counter()
        dt, clock[0] = now - clock[0], now
        return dt

    dev = jax.devices()[0]
    splits["tpu_init"] = t_mark()
    compiles = Compiles()
    data = build(cfg, traffic, seeds, splits, t_mark)
    cache = data["cache"]
    b = cfg["batch"]
    span = _annotate(trace)
    srv = Server(cache, data["pool"], b, span)
    jax.block_until_ready(srv.serve(0))
    splits["compile_or_load"] = t_mark()
    kind = traffic["arrivals"]
    for j in range(1, traffic["warmup_batches"]):
        jax.block_until_ready(srv.serve(j))
    if kind != "backlog" and traffic.get("warmup_seconds", 0) > 0:
        warm = schedule(traffic, cfg, traffic["warmup_seconds"],
                        seeds["arrivals"] ^ 0x5A5A)
        run_open_loop(srv, warm, [], _annotate(False),
                      j0=traffic["warmup_batches"])
    # everything built so far lives for the whole run: keep it out of the
    # collector's sweeps, so that a sweep in the window walks only what the
    # window allocates (a long-lived serving process is set up the same way)
    gc.collect()
    gc.freeze()
    splits["warmup"] = t_mark()
    compiled_before = _tracked()

    keep_at = _keep_times(seconds, traffic["check_batches"], seeds["check"])
    trace_dir = None
    if trace:
        trace_dir = _trace_dir(out_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    compiles.on = True
    pauses = GcPauses()
    j0 = srv.steps
    if kind == "backlog":
        out = run_backlog(srv, seconds, keep_at, span)
    else:
        due = schedule(traffic, cfg, seconds, seeds["arrivals"])
        out = run_open_loop(srv, due, keep_at, span, j0=j0)
    compiles.on = False
    pauses.stop()
    window_compiles = len(compiles.events) + _tracked() - compiled_before
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    total_gain = float(sum(np.asarray(m.gain_int, np.float64).sum()
                           for m in out["metrics"]))
    nag = total_gain / (cfg["k"] * data["c_f"] * max(out["requests"], 1))
    log(f"setup split (s): " + json.dumps(
        {k: round(v, 3) for k, v in splits.items()}))
    log(f"window: {out['batches']} batches, {out['requests']} requests in "
        f"{out['elapsed']:.3f} s; compiles inside the window: "
        f"{window_compiles} ({sorted(set(compiles.events))}); "
        f"{pauses.summary()}")

    e2e = {"setup_s": setup_s}
    if kind == "backlog":
        e2e["served_rps"] = out["served_rps"]
    else:
        e2e["latency_p50_ms"] = float(np.percentile(out["latency_ms"], 50))
        e2e["latency_p95_ms"] = float(np.percentile(out["latency_ms"], 95))

    reduced = None
    if trace:
        t = time.perf_counter()
        reduced = tracereduce.reduce(tracereduce.load(_xplane(trace_dir)))
        log(f"trace reduced in {time.perf_counter() - t:.1f} s")
        if out_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    shapes = (_kernel_shapes(reg, cfg, cache, srv, out, j0) if trace
              else None)

    # the program's state goes before the reference runs; what the check
    # needs of it (kept steps, the index's verified parameters) stays
    index = cache.index
    del cache, srv.cache
    data.pop("cache")
    out_metrics = out.pop("metrics")
    del out_metrics
    gc.collect()
    t = time.perf_counter()
    numbers, info = check(reg, cfg, data, srv.kept, index, seeds,
                          control=control)
    ref_s = time.perf_counter() - t
    limits = cfg["limits"]
    correct = all(numbers[k] <= limits[k] for k in numbers)
    log(f"answers: NAG {nag:.6f}, recall@{cfg['k']} "
        f"{info['recall_at_k']}, {info['checked_requests']} requests in "
        f"{info['checked_steps']} steps checked, "
        f"{info['tied_requests']} left out as ties, "
        f"{info['fill_ties']} with a tie at the fill's end; reference "
        f"{ref_s:.1f} s")

    platform = dev.platform
    result = {
        "correct": bool(correct),
        "attempted": int(out["requests"]),
        "failed": 0,
        "metrics": {},
        "device": {"platform": platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak},
    }
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] +
             bench["per_layer"]}
    if not trace:
        for m in bench["end_to_end"]:
            if registry.applies(m, cell_name) and m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    else:
        ctx = {"cell": cell, "config": cfg, "traffic": traffic,
               "trace": reduced, "host": out, "shapes": shapes,
               "peaks": _peaks(dev.device_kind), "registry": reg,
               "batches": out["batches"]}
        for m in bench["per_layer"]:
            if registry.applies(m, cell_name):
                v = reg.module("metrics", m["name"]).read(ctx)
                if v is not None:
                    result["metrics"][m["name"]] = {"value": float(v),
                                                    "unit": units[m["name"]]}
        if reduced is not None:
            result["device"]["busy_s"] = reduced["busy_ns"] / 1e9
            result["device"]["window_s"] = reduced["window_ns"] / 1e9
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in sorted(numbers)}
    return result


def _tracked() -> int:
    from repro.index.base import tracked_compiles

    return tracked_compiles()


def _trace_dir(out_dir):
    import pathlib
    import tempfile

    base = pathlib.Path(out_dir) if out_dir else pathlib.Path(
        tempfile.mkdtemp(prefix="bench_trace_"))
    base.mkdir(parents=True, exist_ok=True)
    return base


def _xplane(trace_dir) -> str:
    import pathlib

    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return str(found[-1])


def _peaks(kind: str) -> dict:
    table = json.loads((registry.BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def _kernel_shapes(reg, cfg: dict, cache, srv, out, j0) -> dict:
    """The sizes the kernel counts read: per step of the window, B
    requests against the catalog; for an IVF index also the mean number
    of catalog rows in the probed lists."""
    shapes = {"batch": cfg["batch"], "rows": cfg["rows"], "dim": cfg["dim"],
              "k": cfg["c_remote"], "itemsize": 4}
    spec = dict(cfg["index"])
    backend = spec.pop("backend")
    remote = reg.module("remote", backend)
    if hasattr(remote, "probed_rows"):
        reqs = np.concatenate([srv.batch(j) for j in
                               range(j0, j0 + out["batches"])])
        shapes["probed_rows"] = remote.probed_rows(
            reqs, cache.index, int(spec["nprobe"]), cfg["batch"])
    return shapes
