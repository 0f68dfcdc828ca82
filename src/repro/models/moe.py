"""Mixture-of-Experts layer: top-k routing, capacity-bounded scatter
dispatch, expert-parallel sharding over the `model` mesh axis.

Covers mixtral (8e top-2), jamba (16e top-2) and deepseek-v3 (1 shared +
256 routed top-8, sigmoid routing à la DeepSeek).  Dispatch uses the
scatter/gather formulation: an (E, C, d) expert buffer — NOT the dense
(T, E, C) GShard one-hot tensor, which is infeasible at 256 experts — so
memory is O(E*C*d) and the SPMD partitioner lowers the token->expert
exchange to all-to-alls when E is sharded.

Load-balancing auxiliary loss follows Switch (fraction-dot-probability).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, dtype_of, init_mlp, mlp
from repro.sharding.ctx import annotate, _current as _ctx


def init_moe(key, cfg: ModelConfig):
    dt = dtype_of(cfg)
    e_ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 3 + cfg.n_shared_experts)
    d = cfg.d_model
    e = cfg.n_experts
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        # stacked expert weights: (E, d, f) / (E, f, d)
        "wi": (jax.random.normal(ks[1], (e, d, e_ff)) / jnp.sqrt(d)).astype(dt),
        "wg": (jax.random.normal(ks[2], (e, d, e_ff)) / jnp.sqrt(d)).astype(dt),
        "wo": (jax.random.normal(ks[0], (e, e_ff, d)) / jnp.sqrt(e_ff)).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[3], cfg, e_ff * cfg.n_shared_experts)
    return p


def _moe_two_stage(p, xf, cfg: ModelConfig):
    """§Perf variant: per-data-shard dispatch (cfg.moe_dp blocks).

    Token->buffer scatter positions are computed WITHIN each data shard, so
    the scatter is shard-local (no cross-shard indices) and the expert
    einsum runs on a (dp x E) grid that matches the (data x model) mesh
    exactly: the only surviving communication is the output-combine
    all-reduce over `model` — the same pattern as a TP FFN — instead of
    the partitioner's last-resort replicate-and-all-reduce of the whole
    (E, C, d) buffer (~1600x less collective traffic on deepseek-v3)."""
    t, d = xf.shape
    dp = cfg.moe_dp
    e, k = cfg.n_experts, cfg.experts_per_token
    tl = t // dp
    xb = annotate(xf.reshape(dp, tl, d), ("batch", None, None))

    logits = xb.astype(jnp.float32) @ p["router"]            # (dp, tl, E)
    if cfg.attn_type == "mla":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(scores, k)                     # (dp, tl, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    if cfg.capacity_factor <= 0:
        capl = tl * k
    else:
        capl = int(max((tl * k * cfg.capacity_factor) // e, min(tl, 8)))

    flat_e = eidx.reshape(dp, tl * k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)       # (dp, tlk, E)
    pos = jnp.sum(jnp.cumsum(onehot, axis=1) * onehot, -1) - 1
    keep = pos < capl
    tok = jnp.repeat(jnp.arange(tl), k)

    def scatter_block(xfb, fe, po, kp):
        buf = jnp.zeros((e, capl, d), xfb.dtype)
        return buf.at[fe, jnp.clip(po, 0, capl - 1)].add(
            jnp.where(kp[:, None], xfb[tok], 0))

    buf = jax.vmap(scatter_block)(xb, flat_e, pos, keep)      # (dp,E,C,d)
    buf = annotate(buf, ("batch", "model", None, None))

    hidden = jax.nn.silu(jnp.einsum("pecd,edf->pecf", buf, p["wg"])) * \
        jnp.einsum("pecd,edf->pecf", buf, p["wi"])
    hidden = annotate(hidden, ("batch", "model", None, None))
    out_buf = jnp.einsum("pecf,efd->pecd", hidden, p["wo"])
    out_buf = annotate(out_buf, ("batch", "model", None, None))

    def combine_block(ob, fe, po, kp, gv):
        # gather per k-slot and pre-sum over slots: the cross-shard
        # all-reduce then carries (tl, d) once instead of (tl*k, d) —
        # XLA's all-reduce reassociation merges the k partial sums.
        fe_s = fe.reshape(tl, k)
        po_s = jnp.clip(po, 0, capl - 1).reshape(tl, k)
        kp_s = kp.reshape(tl, k)
        acc = jnp.zeros((tl, d), ob.dtype)
        for j in range(k):
            g_j = ob[fe_s[:, j], po_s[:, j]]                  # (tl, d)
            g_j = jnp.where(kp_s[:, j][:, None], g_j, 0)
            acc = acc + g_j * gv[:, j][:, None].astype(ob.dtype)
        return acc

    out = jax.vmap(combine_block)(out_buf, flat_e, pos, keep, gate)
    out = annotate(out, ("batch", None, None)).reshape(t, d)

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xf)

    probs = jax.nn.softmax(logits.reshape(t, e), axis=-1)
    f = jnp.mean(jax.nn.one_hot(eidx.reshape(t, k)[:, 0], e,
                                dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(f * jnp.mean(probs, axis=0))
    return out, aux


def _moe_shard_map(p, xf, cfg: ModelConfig):
    """§Perf final MoE form: explicit shard_map, expert-weights-stationary.

    Activations are data-sharded and model-REPLICATED (the transformer's
    activation layout), expert weights are E-sharded over `model`: so no
    token ever needs to move — each chip runs the slots owned by its local
    experts for its local tokens and a single (T_local, d) bf16 psum over
    `model` combines.  Collective cost per layer = one TP-style all-reduce
    (+ the usual FSDP weight all-gathers) instead of the partitioner's
    replicated-buffer all-reduces: measured 48x less collective traffic on
    deepseek-v3 train_4k (EXPERIMENTS.md §Perf).

    Capacity is enforced per (data-shard, local-expert) — a strictly more
    local variant of the capacity constraint (noted in DESIGN.md §5)."""
    from jax.sharding import PartitionSpec as P

    ctx = _ctx()
    mesh = ctx["mesh"]
    batch_ax = ctx["batch_axes"]
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes.get("model", 1)
    n_batch = 1
    for a in batch_ax:
        n_batch *= sizes.get(a, 1)
    e_loc = e // n_model
    tl = t // n_batch
    if cfg.capacity_factor <= 0:
        capl = tl * k
    else:
        capl = int(max((tl * k * cfg.capacity_factor) // e, min(tl, 8)))
    fsdp = cfg.fsdp

    def local_fn(xl, router, wi, wg, wo):
        # xl (tl, d); router (d/F, E); wi/wg (e_loc, d/F, f); wo (e_loc, f, d/F)
        if fsdp:
            router = jax.lax.all_gather(router, "data", axis=0, tiled=True)
            wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        my = jax.lax.axis_index("model")
        logits = xl.astype(jnp.float32) @ router            # (tl, E)
        scores = (jax.nn.sigmoid(logits) if cfg.attn_type == "mla"
                  else jax.nn.softmax(logits, axis=-1))
        gate, eidx = jax.lax.top_k(scores, k)               # (tl, k)
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

        flat_e = eidx.reshape(-1)
        local_e = flat_e - my * e_loc
        mine = (local_e >= 0) & (local_e < e_loc)
        safe_e = jnp.clip(local_e, 0, e_loc - 1)
        onehot = jax.nn.one_hot(safe_e, e_loc, dtype=jnp.int32) * \
            mine[:, None].astype(jnp.int32)
        pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, -1) - 1
        keep = mine & (pos >= 0) & (pos < capl)
        tok = jnp.repeat(jnp.arange(tl), k)

        buf = jnp.zeros((e_loc, capl, d), xl.dtype)
        buf = buf.at[safe_e, jnp.clip(pos, 0, capl - 1)].add(
            jnp.where(keep[:, None], xl[tok], 0))

        hidden = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wi)
        out_buf = jnp.einsum("ecf,efd->ecd", hidden, wo)     # (e_loc,C,d)

        gathered = out_buf[safe_e, jnp.clip(pos, 0, capl - 1)]
        gathered = jnp.where(keep[:, None], gathered, 0)
        weighted = gathered * gate.reshape(-1)[:, None].astype(xl.dtype)
        out_l = jnp.zeros((tl, d), xl.dtype).at[tok].add(weighted)
        out_l = jax.lax.psum(out_l, "model")                 # the ONLY comm

        # switch aux loss (per shard; psum-averaged outside)
        probs = jax.nn.softmax(logits, axis=-1)
        f = jnp.mean(jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32), 0)
        aux = e * jnp.sum(f * jnp.mean(probs, axis=0))
        aux = jax.lax.pmean(aux, batch_ax)
        return out_l, aux

    from jax import shard_map

    fs = "data" if fsdp else None
    mapped = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_ax, None), P(fs, None),
                  P("model", fs, None), P("model", fs, None),
                  P("model", None, fs)),
        out_specs=(P(batch_ax, None), P()),
        check_vma=False,
    )
    out, aux = mapped(xf, p["router"], p["wi"], p["wg"], p["wo"])
    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xf)
    return out, aux


def moe_ffn(p, x, cfg: ModelConfig):
    """x: (B, S, d) -> (out, aux_loss)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    xf = x.reshape(t, d)

    if cfg.moe_dp > 1 and t % cfg.moe_dp == 0:
        ctx = _ctx()
        if (ctx is not None and e % dict(zip(
                ctx["mesh"].axis_names, ctx["mesh"].devices.shape)).get(
                    "model", 1) == 0):
            out, aux = _moe_shard_map(p, xf, cfg)
            return out.reshape(b, s, d), aux
        out, aux = _moe_two_stage(p, xf, cfg)
        return out.reshape(b, s, d), aux

    logits = xf.astype(jnp.float32) @ p["router"]          # (T, E)
    if cfg.attn_type == "mla":  # deepseek-style sigmoid scoring
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(scores, k)        # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # capacity per expert; capacity_factor <= 0 = dropless (cap = t*k, for
    # tests/small models); otherwise floor at min(t, 8) so decode batches
    # (t = B tokens) never round to a 1-token capacity.
    if cfg.capacity_factor <= 0:
        cap = t * k
    else:
        cap = int(max((t * k * cfg.capacity_factor) // e, min(t, 8)))

    # position of each (token, slot) inside its expert's buffer
    flat_expert = expert_idx.reshape(-1)                    # (T*k,)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot          # running count
    position = jnp.sum(pos_in_e, axis=-1) - 1               # (T*k,)
    keep = position < cap

    # scatter tokens into (E, C, d) buffers; the buffer shards E over
    # 'model' and capacity over 'batch' so the dispatch spike stays
    # O(E/tp * C/dp * d) per chip (the all-to-all happens here).
    tok_of_slot = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[flat_expert, jnp.clip(position, 0, cap - 1)].add(
        jnp.where(keep[:, None], xf[tok_of_slot], 0))
    buf = annotate(buf, ("model", "batch", None))

    # expert FFN (einsum over stacked weights; E shards over `model`)
    hidden = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    hidden = annotate(hidden, ("model", "batch", None))
    out_buf = jnp.einsum("ecf,efd->ecd", hidden, p["wo"])   # (E, C, d)
    out_buf = annotate(out_buf, ("model", "batch", None))

    # gather back + combine with gate weights
    gathered = out_buf[flat_expert, jnp.clip(position, 0, cap - 1)]  # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    weighted = gathered * gate_vals.reshape(-1)[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[tok_of_slot].add(weighted)

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xf)

    # Switch load-balance loss: E * sum_e f_e * p_e
    probs = jax.nn.softmax(logits, axis=-1)
    f = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0)
    pbar = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * pbar)
    return out.reshape(b, s, d), aux
