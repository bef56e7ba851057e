"""Mixture-of-Experts FFN with dropless, sort-based dispatch.

This is where the paper's contribution enters the LM stack (DESIGN §4): the
token->expert assignment is an unstructured sparse matrix whose row lengths
(tokens per expert) are as skewed as a power-law graph's degrees. Dispatch =
sort tokens by expert (the conversion phase) + grouped GEMM over equal-cost
tiles (the balanced multiply phase). Two compute paths:

  * XLA:     jax.lax.ragged_dot (differentiable, shardable under GSPMD)
  * Pallas:  repro.kernels.ops.moe_group_matmul (serving path / TPU)
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init

Array = jax.Array


class MoEConfig(NamedTuple):
    d_model: int
    d_ff: int                 # per-expert hidden
    n_experts: int
    top_k: int
    use_kernel: bool = False  # Pallas grouped GEMM instead of ragged_dot
    router_aux_weight: float = 0.01


def moe_init(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s_in, s_out = d ** -0.5, f ** -0.5
    return {
        "router": dense_init(ks[0], d, E, dtype=dtype),
        "w_gate": jax.random.normal(ks[1], (E, d, f), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (E, d, f), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (E, f, d), dtype) * s_out,
    }


def _grouped_matmul(xs: Array, w: Array, group_sizes: Array,
                    use_kernel: bool) -> Array:
    if use_kernel:
        from repro.kernels import ops as kops
        interpret = jax.default_backend() != "tpu"
        return kops.moe_group_matmul(xs, w, group_sizes,
                                     interpret=interpret)
    return jax.lax.ragged_dot(xs, w, group_sizes.astype(jnp.int32))


def moe_apply(p, cfg: MoEConfig, x: Array) -> Tuple[Array, Array]:
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar)."""
    B, S, d = x.shape
    T = B * S
    k = cfg.top_k
    E = cfg.n_experts
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # [T, E]
    top_p, top_e = jax.lax.top_k(probs, k)                        # [T, k]
    top_w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # ---- conversion phase: sort (token, slot) pairs by expert ----
    slot_expert = top_e.reshape(-1)                               # [T*k]
    slot_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    order = jnp.argsort(slot_expert)                              # stable
    xs = xf[slot_token[order]]                                    # [T*k, d]
    group_sizes = jnp.zeros((E,), jnp.int32).at[slot_expert].add(1)

    # ---- balanced multiply phase: grouped GEMMs (SwiGLU expert FFN) ----
    g = _grouped_matmul(xs, p["w_gate"], group_sizes, cfg.use_kernel)
    u = _grouped_matmul(xs, p["w_up"], group_sizes, cfg.use_kernel)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(xs.dtype)
    out_slots = _grouped_matmul(h, p["w_down"], group_sizes, cfg.use_kernel)

    # ---- carry-out fixup: weighted scatter back to tokens ----
    w_sorted = top_w.reshape(-1)[order].astype(jnp.float32)
    tok_sorted = slot_token[order]
    y = jnp.zeros((T, d), jnp.float32).at[tok_sorted].add(
        out_slots.astype(jnp.float32) * w_sorted[:, None])

    # switch-style load-balance loss (the paper's imbalance metric as a
    # differentiable penalty)
    frac_tokens = group_sizes.astype(jnp.float32) / jnp.maximum(T * k, 1)
    mean_prob = probs.mean(axis=0)
    aux = cfg.router_aux_weight * E * jnp.sum(frac_tokens * mean_prob)
    return y.reshape(B, S, d).astype(x.dtype), aux


def expert_load_stats(p, cfg: MoEConfig, x: Array) -> dict:
    """Routing imbalance diagnostics (max/mean tokens per expert etc.) — the
    MoE analogue of the paper's nnz-per-row variance (Table 5.1)."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
    _, top_e = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    counts = jnp.zeros((cfg.n_experts,), jnp.int32
                       ).at[top_e.reshape(-1)].add(1)
    mean = counts.mean()
    return {"counts": counts,
            "max_over_mean": counts.max() / jnp.maximum(mean, 1),
            "variance": jnp.var(counts.astype(jnp.float32))}


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (beyond-paper optimization, EXPERIMENTS §Perf)
# ---------------------------------------------------------------------------
def moe_apply_ep(p, cfg: MoEConfig, x: Array, *, ep_axis: str = "model",
                 batch_axes: Tuple[str, ...] = ("data",),
                 capacity_factor: float = 1.3) -> Tuple[Array, Array]:
    """shard_map EP dispatch: experts live sharded over ``ep_axis``;
    activations are already replicated across it, so each EP rank selects
    the (token, slot) pairs routed to ITS experts (a fixed local capacity =
    the merge-path 'uniform quantum' trick: every rank does the same-shape
    work), runs the grouped GEMMs locally, and one psum over ``ep_axis``
    plays the paper's carry-out combine. Replaces the global argsort+gather
    that GSPMD lowers to catastrophic all-to-alls (baseline cells in
    EXPERIMENTS §Roofline)."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k

    def local(xl, router_w, w_gate, w_up, w_down):
        # xl [B_loc, S, d]; w_* [E_loc, ...]; router replicated
        ep_rank = jax.lax.axis_index(ep_axis)
        n_ep = jax.lax.axis_size(ep_axis)
        e_loc = w_gate.shape[0]
        Bl = xl.shape[0]
        T = Bl * S
        xf = xl.reshape(T, d)
        logits = xf.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        slot_e = top_e.reshape(-1)                       # [T*k]
        slot_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        slot_w = top_w.reshape(-1).astype(jnp.float32)
        mine = (slot_e >= ep_rank * e_loc) & (slot_e < (ep_rank + 1) * e_loc)
        local_e = jnp.where(mine, slot_e - ep_rank * e_loc, e_loc)
        # fixed local capacity: same-shape work on every rank
        cap = int(capacity_factor * T * k / max(E // e_loc, 1))
        cap = min(max(-(-cap // 128) * 128, 128), T * k)
        order = jnp.argsort(jnp.where(mine, local_e, e_loc + 1))[:cap]
        sel_e = local_e[order]
        sel_valid = sel_e < e_loc
        xs = xf[slot_t[order]] * sel_valid[:, None].astype(xf.dtype)
        group_sizes = jnp.zeros((e_loc,), jnp.int32).at[sel_e].add(
            sel_valid.astype(jnp.int32))
        g = jax.lax.ragged_dot(xs, w_gate, group_sizes)
        u = jax.lax.ragged_dot(xs, w_up, group_sizes)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(xs.dtype)
        out = jax.lax.ragged_dot(h, w_down, group_sizes)
        w_sel = slot_w[order] * sel_valid.astype(jnp.float32)
        y = jnp.zeros((T, d), jnp.float32).at[slot_t[order]].add(
            out.astype(jnp.float32) * w_sel[:, None])
        y = jax.lax.psum(y, ep_axis)                     # combine
        # aux loss: routing stats are identical across EP ranks but LOCAL to
        # each dp shard — pmean over the batch axes gives the exact global
        # token-averages (equal shard sizes)
        frac = jnp.zeros((E,), jnp.float32).at[slot_e].add(1.0) \
            / jnp.maximum(T * k, 1)
        mean_prob = probs.mean(0)
        if batch_axes:
            frac = jax.lax.pmean(frac, batch_axes)
            mean_prob = jax.lax.pmean(mean_prob, batch_axes)
        aux = cfg.router_aux_weight * E * jnp.sum(frac * mean_prob)
        # drop accounting: slots routed to me beyond cap are dropped
        dropped = jnp.maximum(mine.sum() - sel_valid.sum(), 0)
        dropped = jax.lax.psum(dropped, ep_axis)
        return y.reshape(Bl, S, d).astype(xl.dtype), aux, dropped

    bspec = P(batch_axes, None, None) if batch_axes else P(None, None, None)
    y, aux, dropped = jax.shard_map(
        local,
        in_specs=(bspec, P(None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None)),
        out_specs=(bspec, P(), P()),
        check_vma=False,
    )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


def moe_apply_ep_tp(p, cfg: MoEConfig, x: Array, *, ep_axis: str = "model",
                    batch_axes: Tuple[str, ...] = ("data",)
                    ) -> Tuple[Array, Array]:
    """Expert-TP dispatch for archs whose expert count does NOT divide the
    model axis (mixtral: 8e on a 16-wide axis): every rank holds a 1/n_ep
    slice of EVERY expert's d_ff, the dispatch (sort + ragged_dot) runs
    fully locally and losslessly, and the partial w_down outputs psum over
    the axis. Same single-collective structure as moe_apply_ep, zero drops,
    at the cost of every rank sorting all local slots."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k

    def local(xl, router_w, w_gate, w_up, w_down):
        Bl = xl.shape[0]
        T = Bl * S
        xf = xl.reshape(T, d)
        logits = xf.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        slot_e = top_e.reshape(-1)
        slot_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        order = jnp.argsort(slot_e)
        xs = xf[slot_t[order]]
        group_sizes = jnp.zeros((E,), jnp.int32).at[slot_e].add(1)
        g = jax.lax.ragged_dot(xs, w_gate, group_sizes)   # [T*k, f_loc]
        u = jax.lax.ragged_dot(xs, w_up, group_sizes)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(xs.dtype)
        out = jax.lax.ragged_dot(h, w_down, group_sizes)  # partial over f
        w_sel = top_w.reshape(-1)[order].astype(jnp.float32)
        y = jnp.zeros((T, d), jnp.float32).at[slot_t[order]].add(
            out.astype(jnp.float32) * w_sel[:, None])
        y = jax.lax.psum(y, ep_axis)
        frac = group_sizes.astype(jnp.float32) / jnp.maximum(T * k, 1)
        mean_prob = probs.mean(0)
        if batch_axes:
            frac = jax.lax.pmean(frac, batch_axes)
            mean_prob = jax.lax.pmean(mean_prob, batch_axes)
        aux = cfg.router_aux_weight * E * jnp.sum(frac * mean_prob)
        return y.reshape(Bl, S, d).astype(xl.dtype), aux

    bspec = P(batch_axes, None, None) if batch_axes else P(None, None, None)
    y, aux = jax.shard_map(
        local,
        in_specs=(bspec, P(None, None), P(None, None, ep_axis),
                  P(None, None, ep_axis), P(None, ep_axis, None)),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux
