"""The plain reference of one AÇAI serving step, and the comparison that
decides `correct`.

A step takes a batch of B requests and the cache state (y, x) before it,
and gives per request the integral cost C(r, x) and gain G(r, x) of the
answer served from x, the fractional gain G(r, y), and the state after
it.  The reference computes each of these from the paper's equations:

* candidates: the exact top-c_remote catalog rows of the remote index
  (`bench/remote/<backend>.py`) and the top-c_local rows among the cached
  ones (x = 1), first occurrence kept where the two lists share a row;
* serving (Eq. 2): a candidate is offered at d if cached, at d + c_f if
  fetched; the answer is the k cheapest offers, C(r, x) their sum;
  G(r, x) = C(r, empty cache) - C(r, x);
* fractional gain (Eq. 7): candidate i offers weight y_i at d_i and
  1 - y_i at d_i + c_f; filling k units cheapest first costs C(r, y) and
  G(r, y) = C(r, empty cache) - C(r, y); the subgradient is Eq. (55);
* OMA (Alg. 1): z = y exp(eta * sum of the batch's subgradients), the
  negentropy projection onto {0 <= y <= 1, sum y = h} (solved exactly in
  float64), clipped at 1e-12;
* coupled rounding (Alg. 2) with the step's key: the key chain starts at
  PRNGKey(cache seed), split once at set-up and once per step.

Distances come from the device in float32 (or in bfloat16 for the
control); the rest runs on the host in NumPy.  Nothing here imports the
program.

A request whose candidate lists end in a tie (the distance just past a
list's end lies within rounding of the last one kept) may be answered
either way by a sound program.  Such requests are left out of the
per-request numbers, and the rows they touch out of the state number;
their count is reported beside the numbers.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BIG = np.float32(1e9)          # cost of an empty candidate slot
Y_FLOOR = 1e-12                # the projection's floor on y
EXP_CLIP = 60.0                # bound on the exponent of the ascent step
ROUND_DELTA = 1e-9             # floor on the rounding's denominators
# The projection's scale is a sum over N entries: in float32 the program's
# y after a step differs from the float64 reference's by a few 1e-6 of
# itself.  A uniform closer than ROUND_TIE (relative) to its threshold
# may round either way.
ROUND_TIE = 3e-5
# The projection's scale is a float32 sum over N entries in the program:
# its y may differ from the reference's by one common factor this close to
# 1 (up to 3.4e-5 seen on a v5e at N = 1M); beyond it the factor counts.
SCALE_TIE = 1e-4
# The fractional fill stops where the running sum of weights reaches k;
# float32 sums in another order can put that boundary one entry over,
# which moves the subgradient of every candidate of the request.
FILL_TIE = 2e-4
EXTRA = 8                      # rows read past each list's end (ties)
# Two float32 distances closer than TIE_ULPS float32 ulps of the squared
# norms they are computed from are a tie: the distance formula
# |q|^2 - 2 q.x + |x|^2 rounds to a few such ulps.
TIE_ULPS = 32
EPS32 = float(np.finfo(np.float32).eps)
PRECISIONS = ("float32", "bfloat16")


def block_rows(n: int, cap: int) -> int:
    """The largest divisor of n no larger than cap: blocks of catalog rows
    that tile it exactly, so no padded copy of a large catalog is made."""
    for rows in range(min(n, cap), 0, -1):
        if n % rows == 0:
            return rows
    return n


@dataclasses.dataclass(frozen=True)
class StepParams:
    k: int
    c_remote: int
    c_local: int
    h: int
    c_f: float
    eta: float        # per-request rate: a step ascends eta * sum of the
                      # batch's subgradients


# -- distances on the device ---------------------------------------------------

def sq_dists(q, x, precision: str):
    """(B, d) x (M, d) -> (B, M) squared L2 as |q|^2 - 2 q.x + |x|^2, in
    float32 (all matmul passes) or bfloat16 (inputs rounded to bfloat16,
    one pass, float32 sums)."""

    if precision == "float32":
        q, x = q.astype(jnp.float32), x.astype(jnp.float32)
        dots = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    else:
        q = q.astype(jnp.bfloat16)
        x = x.astype(jnp.bfloat16)
        dots = jnp.matmul(q, x.T, preferred_element_type=jnp.float32)
        q, x = q.astype(jnp.float32), x.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    xn = jnp.sum(x * x, axis=1)[None, :]
    return jnp.maximum(qn - 2.0 * dots + xn, 0.0)


def diff_dists(q, rows, precision: str):
    """(d,) x (M, d) -> (M,) squared L2 as the sum of squared differences."""

    if precision == "float32":
        diff = rows.astype(jnp.float32) - q.astype(jnp.float32)[None, :]
    else:
        diff = (rows.astype(jnp.bfloat16)
                - q.astype(jnp.bfloat16)[None, :]).astype(jnp.float32)
    return jnp.sum(diff * diff, axis=1)


@partial(jax.jit, static_argnames=("k", "precision"))
def _local_topk(q, catalog, cached, k: int, precision: str):
    rows = catalog[jnp.clip(cached, 0, None)]
    d = sq_dists(q, rows, precision)
    d = jnp.where((cached >= 0)[None, :], d, jnp.inf)
    neg, pos = jax.lax.top_k(-d, k)
    return -neg, jnp.where(jnp.isfinite(neg), cached[pos], -1)


def local_topk(q, catalog, cached: np.ndarray, k: int, precision: str):
    """Top-k cached rows per request: (dists (B, k), ids (B, k)), id -1
    where fewer than k rows are cached.  The cached-id list is padded to
    a multiple of 1024 so that few shapes compile."""
    size = max(4096, -(-max(cached.size, k) // 1024) * 1024)
    pad = np.full(size, -1, np.int32)
    pad[:cached.size] = cached
    d, i = _local_topk(q, catalog, jnp.asarray(pad), k, precision)
    return np.asarray(d), np.asarray(i)


def _tie(d_sorted: np.ndarray, keep: int, tol: np.ndarray) -> np.ndarray:
    """Rows of (B, keep + EXTRA) ascending distances whose list of `keep`
    ends in a tie."""
    return (d_sorted[:, keep] - d_sorted[:, keep - 1]) <= tol


# -- one step on the host ------------------------------------------------------

def candidate_slab(remote_ids, remote_d, local_ids, local_d, n: int):
    """(ids, d, valid) of shape (B, c_remote + c_local): the remote list
    then the local one, an id kept at its first occurrence only; empty
    slots (id < 0) and repeats are invalid, with distance BIG."""
    ids = np.concatenate([remote_ids, local_ids], axis=1).astype(np.int64)
    d = np.concatenate([remote_d, local_d], axis=1).astype(np.float32)
    valid = ids >= 0
    for b in range(ids.shape[0]):
        _, first = np.unique(ids[b], return_index=True)
        seen = np.zeros(ids.shape[1], bool)
        seen[first] = True
        valid[b] &= seen
    return np.where(valid, ids, n), np.where(valid, d, BIG), valid


def serve_and_gain(d, valid, xc, yc, k: int, c_f: float):
    """Per request: C(r, x), G(r, x), G(r, y), the subgradient of G(r, y)
    on the candidate slots, in float32, and whether the fill's boundary is
    a tie (a running sum of weights within FILL_TIE of k).  d (B, C) with
    BIG on invalid slots; xc, yc (B, C) the cache state of each slot's
    row, 0 on invalid slots."""
    f32 = np.float32
    c_f = f32(c_f)
    b, c = d.shape
    offer = np.where(valid, d + c_f * (f32(1) - xc), BIG).astype(f32)
    cost = np.sort(offer, axis=1)[:, :k].sum(axis=1, dtype=f32)
    empty = np.sort(d, axis=1)[:, :k].sum(axis=1, dtype=f32) + f32(k) * c_f
    gain_int = np.maximum(empty - cost, f32(0))

    costs = np.concatenate([d, d + c_f], axis=1).astype(f32)
    weights = np.concatenate([yc, f32(1) - yc], axis=1).astype(f32)
    order = np.argsort(costs, axis=1, kind="stable")
    cs = np.take_along_axis(costs, order, axis=1)
    ws = np.take_along_axis(weights, order, axis=1)
    s = np.cumsum(ws, axis=1, dtype=f32)
    taken = np.clip(f32(k) - (s - ws), f32(0), ws)
    frac_cost = (cs * taken).sum(axis=1, dtype=f32)
    gain_frac = empty - frac_cost

    # Eq. (55): raising y_l moves weight from l's remote offer to its local
    # one; it gains (marginal offer - d_l) while l's local offer is within
    # the filled prefix and its remote one is not, c_f when both are.
    pos = np.argsort(order, axis=1, kind="stable")
    lpos, rpos = pos[:, :c], pos[:, c:]
    last_short = (s < f32(k)).sum(axis=1) - 1           # last prefix < k
    bnd = np.minimum(rpos - 1, last_short[:, None])
    upper = np.take_along_axis(cs, np.clip(bnd + 1, 0, 2 * c - 1), axis=1)
    g = np.where(lpos <= bnd, upper - d, f32(0))
    g = np.where(valid, np.maximum(g, f32(0)), f32(0)).astype(f32)
    fill_tie = (np.abs(s - f32(k)) <= FILL_TIE).any(axis=1)
    return cost, gain_int, gain_frac, g, fill_tie


def project_capped_simplex(z: np.ndarray, h: int) -> np.ndarray:
    """argmin of the negentropy Bregman divergence to z over
    {0 <= y <= 1, sum y = h}: y = min(1, s z) for the s at which the sum
    is h, found exactly in float64 from z sorted descending."""
    z = np.maximum(np.asarray(z, np.float64), 0.0)
    if h >= z.size:
        return np.ones_like(z)
    zs = -np.sort(-z)
    head = zs[:h + 1]
    tail = np.cumsum(zs[::-1])[::-1][:h + 1]           # sum_{i >= m} zs
    m = np.arange(h + 1, dtype=np.float64)
    s_m = (h - m) / np.maximum(tail, 1e-300)
    prev = np.concatenate([[np.inf], head[:-1]])
    ok = (head * s_m <= 1.0 + 1e-12) & (prev * s_m >= 1.0 - 1e-12)
    s = s_m[int(np.argmax(ok))]
    return np.minimum(1.0, z * s)


def coupled_round(u: np.ndarray, x: np.ndarray, y: np.ndarray,
                  y_new: np.ndarray) -> np.ndarray:
    """Alg. 2: evict a cached row with probability -dy / y, fetch an
    absent one with probability dy / (1 - y), from uniforms u."""
    f32 = np.float32
    delta = (y_new - y).astype(f32)
    p_evict = -delta / np.maximum(y, f32(ROUND_DELTA))
    p_fetch = delta / np.maximum(f32(1) - y, f32(ROUND_DELTA))
    evict = (x > 0.5) & (delta < 0) & (u < p_evict)
    fetch = (x < 0.5) & (delta > 0) & (u < p_fetch)
    return np.where(evict, f32(0), np.where(fetch, f32(1), x)).astype(f32)


def rounding_ties(u: np.ndarray, x: np.ndarray, y: np.ndarray,
                  y_new: np.ndarray) -> np.ndarray:
    """Rows whose uniform lies within ROUND_TIE of y_new's rounding of its
    threshold in `coupled_round`: a sound program may round them either
    way."""
    f32 = np.float32
    delta = (y_new - y).astype(f32)
    below = np.maximum(y, f32(ROUND_DELTA))
    above = np.maximum(f32(1) - y, f32(ROUND_DELTA))
    p = np.where(x > 0.5, -delta / below, delta / above)
    tol = ROUND_TIE * y_new / np.where(x > 0.5, below, above)
    return np.abs(u - p) <= tol


def round_keys(cache_seed: int, steps):
    """{step: key} of the rounding key of each given step (0-based, set-up
    steps included): PRNGKey(seed) split once at set-up, then at every
    step (key, round key) = split(key)."""

    want = sorted(set(int(s) for s in steps))
    out = {}
    key = jax.random.split(jax.random.PRNGKey(cache_seed))[0]
    step = 0
    for target in want:
        while step <= target:
            key, k_round = jax.random.split(key)
            if step == target:
                out[target] = k_round
            step += 1
    return out


class Reference:
    """The reference step for one deployment.  `remote` is the
    deployment's remote scan (`bench/remote/<backend>.py`), already built
    for the same precision."""

    def __init__(self, catalog, params: StepParams, remote,
                 precision: str = "float32"):
    
        assert precision in PRECISIONS, precision
        self.catalog, self.p, self.remote = catalog, params, remote
        self.precision = precision
        self.n = int(catalog.shape[0])
        self.row_norm_max = float(jnp.max(jnp.sum(catalog * catalog, axis=1)))

    def tie_tol(self, reqs: np.ndarray) -> np.ndarray:
        qn = (reqs.astype(np.float64) ** 2).sum(axis=1)
        return TIE_ULPS * EPS32 * (qn + self.row_norm_max)

    def step(self, reqs: np.ndarray, y: np.ndarray, x: np.ndarray, u):
        """One batch from (y, x); u are the step's rounding uniforms (N,).
        Returns a dict of the step's outputs and of its ties."""
    
        p = self.p
        q = jnp.asarray(reqs, jnp.float32)
        tol = self.tie_tol(reqs)
        rd, ri, r_amb, r_rows = self.remote.topk(q, p.c_remote + EXTRA, tol)
        cached = np.flatnonzero(x > 0.5).astype(np.int32)
        ld, li = local_topk(q, self.catalog, cached, p.c_local + EXTRA,
                            self.precision)
        amb = r_amb | _tie(rd, p.c_remote, tol) | _tie(ld, p.c_local, tol)
        ids, d, valid = candidate_slab(ri[:, :p.c_remote], rd[:, :p.c_remote],
                                       li[:, :p.c_local], ld[:, :p.c_local],
                                       self.n)
        safe = np.clip(ids, 0, self.n - 1)
        xc = np.where(valid, x[safe], 0).astype(np.float32)
        yc = np.where(valid, y[safe], 0).astype(np.float32)
        cost, gain_int, gain_frac, g, fill_tie = serve_and_gain(
            d, valid, xc, yc, p.k, p.c_f)
        g_sum = np.zeros(self.n, np.float32)
        np.add.at(g_sum, safe[valid], g[valid])
        step = np.clip(np.float32(p.eta) * g_sum, -EXP_CLIP, EXP_CLIP)
        z = y.astype(np.float32) * np.exp(step.astype(np.float32))
        y_new = np.clip(project_capped_simplex(z, p.h), Y_FLOOR, 1.0)
        y_new = y_new.astype(np.float32)
        u = np.asarray(u)
        x_new = coupled_round(u, x, y, y_new)

        # rows whose update a tie may change: every row a tied request
        # reads, past its lists' ends too
        touched = np.zeros(self.n, bool)
        for b in np.flatnonzero(amb | fill_tie):
            for rows in (ri[b], li[b], r_rows[b]):
                rows = np.asarray(rows)
                touched[rows[rows >= 0]] = True
        return {"cost": cost, "gain_int": gain_int, "gain_frac": gain_frac,
                "y": y_new, "x": x_new, "tied": amb, "tied_rows": touched,
                "round_ties": rounding_ties(u, x, y, y_new),
                "fill_ties": int(fill_tie.sum()),
                "remote_ids": ri[:, :p.c_remote]}


# -- the comparison ------------------------------------------------------------

def compare(prog: dict, ref: dict, h: int) -> dict:
    """The numbers compared for one checked batch: the widest relative gap
    of C(r, x) and of the gains (over C(r, x)) on requests without ties,
    and the L1 distance of the state after the step, y and x together,
    per unit of capacity, on rows no tie touches (for x, no rounding tie
    either; for y, after the projection's common factor, SCALE_TIE)."""
    keep = ~ref["tied"]
    cost_r = np.maximum(ref["cost"].astype(np.float64), 1e-30)
    gap = lambda a, b: np.abs(np.asarray(a, np.float64)
                              - np.asarray(b, np.float64)) / cost_r
    cost_gap = gap(prog["cost"], ref["cost"])
    gain_gap = np.maximum(gap(prog["gain_int"], ref["gain_int"]),
                          gap(prog["gain_frac"], ref["gain_frac"]))
    rows = ~ref["tied_rows"]
    y_p = np.asarray(prog["y"], np.float64)[rows]
    y_r = ref["y"].astype(np.float64)[rows]
    # the common factor of the uncapped rows, fitted, and its excess over
    # the projection's rounding counted as if every unit of capacity moved
    free = (y_p < 1.0) & (y_r < 1.0)
    mass = y_r[free].sum()
    scale = y_p[free].sum() / mass if mass > 0 else 1.0
    y_r = np.where(free, scale * y_r, y_r)
    y_l1 = (np.abs(y_p - y_r).sum()
            + h * max(0.0, abs(scale - 1.0) - SCALE_TIE))
    x_rows = rows & ~ref["round_ties"]
    x_diff = int((np.asarray(prog["x"])[x_rows] != ref["x"][x_rows]).sum())
    return {
        "cost_gap": float(cost_gap[keep].max()) if keep.any() else 0.0,
        "gain_gap": float(gain_gap[keep].max()) if keep.any() else 0.0,
        "state_gap": float((y_l1 + x_diff) / h),
        "tied": int((~keep).sum()),
        "fill_ties": ref.get("fill_ties", 0),
    }
