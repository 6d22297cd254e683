"""Mixture-of-Experts layer: token-choice top-k, sort-based dropless-ish
dispatch into per-expert capacity buckets, expert-parallel over the "model"
mesh axis.

The dispatch pipeline (all dense jnp, GSPMD-shardable):
  router probs -> top-k -> flatten (token,k) -> stable sort by expert id ->
  slot = rank-within-expert (overflow beyond capacity dropped) ->
  scatter tokens into (E, cap, D) buckets -> batched expert GEMMs ->
  gather back, weight by gate, sum over k.

FLOPs ~= tokens * top_k * capacity_factor * expert-FFN cost, matching the
paper-config MoE budgets (OLMoE 64e top-8, DeepSeekMoE 2 shared + 64 top-6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime.sharding import current_context, shard


def moe_param_specs(cfg) -> dict:
    # Expert parallelism takes the "model" axis; the per-expert FFN dim is
    # small (1-1.4k) and stays unsharded -- sharding both would map one mesh
    # axis onto two dimensions of the same tensor.
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    specs = {
        "router": ((d, e), ("embed_p", "expert")),
        "w_gate": ((e, d, f), ("expert", "embed_p", None)),
        "w_up": ((e, d, f), ("expert", "embed_p", None)),
        "w_down": ((e, f, d), ("expert", None, "embed_p")),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        specs.update({
            "shared_w_gate": ((d, fs), ("embed_p", "ffn")),
            "shared_w_up": ((d, fs), ("embed_p", "ffn")),
            "shared_w_down": ((fs, d), ("ffn", "embed_p")),
        })
    return specs


def expert_capacity(n_tokens: int, cfg) -> int:
    cap = int(n_tokens * cfg.moe_top_k * cfg.moe_capacity_factor
              // cfg.n_experts)
    return max(8, (cap + 7) // 8 * 8)


def moe_ffn(params: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).

    With a bound mesh whose expert axis is >1, dispatch runs inside
    shard_map: tokens are replicated across the expert (model) axis, so each
    shard builds capacity buckets for *its own* experts locally and only the
    combined output crosses the wire (one psum).  Letting GSPMD partition
    the naive scatter instead replicates the full global bucket tensor
    (measured 6.6 TB/device/step of all-reduce on olmoe train_4k -- see
    EXPERIMENTS.md SPerf iteration 1).
    """
    import os
    ctx = current_context()
    if ctx is not None and not os.environ.get("REPRO_MOE_DENSE"):
        mesh, rules = ctx
        expert_axes = rules.mesh_axes("expert", mesh)
        if expert_axes is not None:
            ax = expert_axes if isinstance(expert_axes, str) \
                else expert_axes[0]
            if cfg.n_experts % mesh.shape[ax] == 0 and mesh.shape[ax] > 1:
                return _moe_ffn_shard_map(params, x, cfg, mesh, rules, ax)
    return _moe_ffn_dense(params, x, cfg)


def _shared_experts(params: dict, xt: jax.Array) -> jax.Array:
    sh = jax.nn.silu(xt @ params["shared_w_gate"]) * (
        xt @ params["shared_w_up"])
    sh = shard(sh, None, "ffn")
    return sh @ params["shared_w_down"]


def _route(params: dict, xt: jax.Array, cfg):
    """Router probs -> (normalized gates (T,k), expert ids (T,k), aux)."""
    t = xt.shape[0]
    e, k = cfg.n_experts, cfg.moe_top_k
    logits = (xt @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    ce = jnp.zeros(e).at[expert_idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)
    return gate_vals, expert_idx, aux


def _moe_ffn_shard_map(params, x, cfg, mesh, rules, expert_ax: str
                       ) -> tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    n_shards = mesh.shape[expert_ax]
    e_loc = e // n_shards
    bspec = rules.mesh_axes("batch", mesh)

    def local_fn(x_loc, router, wg, wu, wd, *shared_w):
        bl, sl, _ = x_loc.shape
        t = bl * sl
        xt = x_loc.reshape(t, d)
        gate_vals, expert_idx, aux = _route({"router": router}, xt, cfg)

        shard_id = jax.lax.axis_index(expert_ax)
        cap = expert_capacity(t, cfg)
        flat_expert = expert_idx.reshape(-1)                 # (T*k,)
        owner = flat_expert // e_loc
        owned = owner == shard_id
        local_expert = jnp.where(owned, flat_expert - shard_id * e_loc,
                                 e_loc)                      # e_loc = "drop"
        order = jnp.argsort(local_expert, stable=True)
        sorted_local = local_expert[order]
        counts = jnp.zeros(e_loc + 1, jnp.int32).at[sorted_local].add(1)
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(t * k) - starts[sorted_local]

        # Owned pairs sort to the front; everything this shard will compute
        # lives in the first  M = e_loc*cap  sorted positions (anything
        # beyond is over capacity or foreign), so gather/scatter traffic is
        # M*D instead of T*k*D -- 1/n_shards of the naive cost
        # (EXPERIMENTS.md SPerf iteration 2).
        m = min(e_loc * cap, t * k)
        take = order[:m]
        le_m = sorted_local[:m]
        rk_m = rank[:m]
        keep_m = (le_m < e_loc) & (rk_m < cap)
        token_m = take // k
        slot = jnp.where(keep_m, le_m * cap + jnp.minimum(rk_m, cap - 1),
                         e_loc * cap)

        xg = jnp.where(keep_m[:, None], xt[token_m], 0.0)    # (M, D)
        buckets = jnp.zeros((e_loc * cap + 1, d), xt.dtype)
        buckets = buckets.at[slot].add(xg)
        bk = buckets[:-1].reshape(e_loc, cap, d)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bk, wg)) * \
            jnp.einsum("ecd,edf->ecf", bk, wu)
        yb = jnp.einsum("ecf,efd->ecd", h, wd)
        y_flat = jnp.concatenate(
            [yb.reshape(e_loc * cap, d), jnp.zeros((1, d), yb.dtype)])

        gate_flat = gate_vals.reshape(-1)[take]              # (M,)
        gathered = y_flat[slot] * (gate_flat * keep_m)[:, None]
        y = jnp.zeros((t, d), yb.dtype).at[token_m].add(
            gathered.astype(yb.dtype))
        if shared_w:
            # Shared experts ride in the same psum: each expert shard holds
            # a 1/n_shards slice of the shared FFN dim, computes its partial
            # contribution locally, and the routed-output reduction sums it
            # -- zero additional collectives (DeepSeekMoE's always-on
            # experts would otherwise cost 2 ARs/layer outside shard_map).
            swg, swu, swd = shared_w
            hs = jax.nn.silu(xt @ swg) * (xt @ swu)
            y = y + (hs @ swd).astype(y.dtype)
        y = jax.lax.psum(y, expert_ax)      # sum expert-shard contributions
        if bspec is not None:
            # Per-shard routing stats -> deterministic cluster-wide aux.
            aux = jax.lax.pmean(aux, bspec)
        return y.reshape(bl, sl, d), aux

    in_specs = [P(bspec, None, None), P(None, None),
                P(expert_ax, None, None), P(expert_ax, None, None),
                P(expert_ax, None, None)]
    args = [x, params["router"], params["w_gate"], params["w_up"],
            params["w_down"]]
    if cfg.n_shared_experts:
        in_specs += [P(None, expert_ax), P(None, expert_ax),
                     P(expert_ax, None)]
        args += [params["shared_w_gate"], params["shared_w_up"],
                 params["shared_w_down"]]
    out_specs = (P(bspec, None, None), P())
    y, aux = jax.shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_specs, check_vma=False)(*args)
    return y, aux


def _moe_ffn_dense(params: dict, x: jax.Array, cfg
                   ) -> tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.moe_top_k
    xt = x.reshape(t, d)

    # ---- routing ----------------------------------------------------------
    logits = (xt @ params["router"]).astype(jnp.float32)        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)              # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balancing auxiliary loss (Switch-style).
    me = probs.mean(axis=0)                                      # (E,)
    ce = jnp.zeros(e).at[expert_idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # ---- dispatch: sort (token,k) pairs by expert -------------------------
    cap = expert_capacity(t, cfg)
    flat_expert = expert_idx.reshape(-1)                         # (T*k,)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    # Rank within expert group = position - first position of that expert.
    counts = jnp.zeros(e, jnp.int32).at[sorted_expert].add(1)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * k) - starts[sorted_expert]
    keep = rank < cap
    slot = sorted_expert * cap + jnp.minimum(rank, cap - 1)      # (T*k,)
    token_of = order // k                                        # source token

    buckets = jnp.zeros((e * cap, d), xt.dtype)
    buckets = buckets.at[slot].add(
        jnp.where(keep[:, None], xt[token_of], 0.0))
    buckets = buckets.reshape(e, cap, d)
    buckets = shard(buckets, "expert", None, None)

    # ---- expert computation (batched GEMMs over the expert axis) ----------
    h_gate = jnp.einsum("ecd,edf->ecf", buckets, params["w_gate"])
    h_up = jnp.einsum("ecd,edf->ecf", buckets, params["w_up"])
    h = jax.nn.silu(h_gate) * h_up
    h = shard(h, "expert", None, None)
    y_buckets = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    y_buckets = shard(y_buckets, "expert", None, None)
    y_flat = y_buckets.reshape(e * cap, d)

    # ---- combine ----------------------------------------------------------
    gathered = y_flat[slot] * keep[:, None]                      # (T*k, D)
    inv = jnp.argsort(order, stable=True)                        # undo sort
    per_pair = gathered[inv].reshape(t, k, d)
    out = jnp.einsum("tkd,tk->td", per_pair,
                     gate_vals.astype(per_pair.dtype))

    # ---- shared experts (always-on) ---------------------------------------
    if cfg.n_shared_experts:
        sh = jax.nn.silu(xt @ params["shared_w_gate"]) * (
            xt @ params["shared_w_up"])
        sh = shard(sh, None, "ffn")
        out = out + sh @ params["shared_w_down"]
    return out.reshape(b, s, d), aux
