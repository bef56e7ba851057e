"""Train / serve step builders + abstract input specs for every
(architecture x shape) cell — ShapeDtypeStruct stand-ins, no allocation."""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, get_config
from repro.models.model import (ModelConfig, decode_step, init_cache,
                                init_params, loss_fn, prefill)
from repro.optim import Optimizer, make_optimizer, warmup_cosine
from .mesh import dp_axes
from . import shardings as shd


class TrainState(NamedTuple):
    params: Any
    opt: Any


def default_optimizer(cfg: ModelConfig) -> Optimizer:
    # jamba-398B cannot hold AdamW state on v5e even ZeRO-sharded over a pod
    # (DESIGN §5): use factored second moments there.
    name = "adafactor" if cfg.d_model >= 8192 else "adamw"
    return make_optimizer(name, warmup_cosine(3e-4, 2000, 100_000))


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    grad_accum: int = 1):
    """Train step; grad_accum > 1 splits the batch into microbatches and
    accumulates grads under a scan — activation memory scales 1/n_micro
    while the collective schedule (one optimizer update, one grad
    reduction) is unchanged (§Perf iteration 7)."""
    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        tokens = batch["tokens"]
        vis = batch.get("vision_embeds")

        if grad_accum == 1:
            def lfn(params):
                return loss_fn(params, cfg, tokens, vision_embeds=vis)
            (loss, metrics), grads = jax.value_and_grad(
                lfn, has_aux=True)(state.params)
        else:
            B = tokens.shape[0]
            assert B % grad_accum == 0, (B, grad_accum)
            mb = B // grad_accum
            tok_m = tokens.reshape(grad_accum, mb, *tokens.shape[1:])
            vis_m = None if vis is None else vis.reshape(
                grad_accum, mb, *vis.shape[1:])

            def micro(carry, inp):
                g_acc, l_acc, ce_acc, aux_acc = carry
                t = inp[0]
                v = inp[1] if vis is not None else None

                def lfn(params):
                    return loss_fn(params, cfg, t, vision_embeds=v)
                (l, m), g = jax.value_and_grad(lfn, has_aux=True)(
                    state.params)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l, ce_acc + m["ce"],
                        aux_acc + m["aux"]), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            xs = (tok_m,) if vis is None else (tok_m, vis_m)
            (g_sum, l_sum, ce_sum, aux_sum), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32)), xs)
            inv = 1.0 / grad_accum
            grads = jax.tree_util.tree_map(lambda g: g * inv, g_sum)
            loss = l_sum * inv
            metrics = {"ce": ce_sum * inv, "aux": aux_sum * inv}

        params, opt, om = optimizer.update(grads, state.opt, state.params)
        out = {"loss": loss, "ce": metrics["ce"], "aux": metrics["aux"],
               **om}
        return TrainState(params, opt), out

    return train_step


def make_decode_step(cfg: ModelConfig):
    def serve_step(params, caches, token, pos):
        return decode_step(params, cfg, token, caches, pos)
    return serve_step


def make_prefill(cfg: ModelConfig, S_max: int):
    def prefill_step(params, tokens, vision_embeds=None):
        return prefill(params, cfg, tokens, S_max,
                       vision_embeds=vision_embeds)
    return prefill_step


# ---------------------------------------------------------------------------
# abstract inputs per cell
# ---------------------------------------------------------------------------
def _text_len(cfg: ModelConfig, seq: int) -> int:
    """VLM archs spend part of the context on vision tokens so the total
    context equals the assigned seq_len exactly."""
    return seq - (cfg.vision_tokens if cfg.frontend == "vision" else 0)


def abstract_params(cfg: ModelConfig, mesh: Mesh, profile: str = "tp"):
    shapes = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    return shd.with_shardings(
        shapes, shd.param_shardings(shapes, mesh, profile))


def abstract_train_state(cfg: ModelConfig, optimizer: Optimizer,
                         mesh: Mesh, profile: str = "tp") -> TrainState:
    p = abstract_params(cfg, mesh, profile)
    opt_shape = jax.eval_shape(optimizer.init, p)
    opt = shd.with_shardings(
        opt_shape, shd.opt_state_shardings(opt_shape, p, mesh, profile))
    return TrainState(p, opt)


def input_specs(arch: str, shape_name: str, mesh: Mesh,
                cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, zero
    allocation) for every input of the cell's step function."""
    cfg = cfg or get_config(arch)
    spec = SHAPES[shape_name]
    B, S = spec.batch, spec.seq
    bs = shd.batch_sharding(mesh, B)
    out: Dict[str, Any] = {"kind": spec.kind, "cfg": cfg}

    if spec.kind == "train":
        St = _text_len(cfg, S)
        batch = {"tokens": jax.ShapeDtypeStruct((B, St), jnp.int32,
                                                sharding=bs)}
        if cfg.frontend == "vision":
            batch["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.vision_dim), jnp.bfloat16,
                sharding=bs)
        out["batch"] = batch
    elif spec.kind == "prefill":
        St = _text_len(cfg, S)
        out["tokens"] = jax.ShapeDtypeStruct((B, St), jnp.int32,
                                             sharding=bs)
        if cfg.frontend == "vision":
            out["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.vision_dim), jnp.bfloat16,
                sharding=bs)
        out["s_max"] = S
    else:  # decode: one new token against a seq_len KV cache
        cache_shape = jax.eval_shape(
            lambda: init_cache(cfg, B, S, dtype=jnp.bfloat16))
        cache = shd.with_shardings(
            cache_shape, shd.cache_shardings(cache_shape, mesh, B))
        out["caches"] = cache
        out["token"] = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=bs)
        out["pos"] = jax.ShapeDtypeStruct(
            (B,), jnp.int32,
            sharding=NamedSharding(mesh, P(dp_axes(mesh))
                                   if B % shd._axis_size(
                                       mesh, tuple(dp_axes(mesh))) == 0
                                   else P()))
    return out


def _moe_mode(cfg, mesh, kind: str = "train") -> str:
    """EP when experts divide the model axis; dropless expert-TP otherwise
    (§Perf iterations 1 and 5). Decode keeps the baseline dispatch: a
    handful of tokens per device cannot amortize the shard_map dispatch
    (measured regression, §Perf iteration 6)."""
    if kind == "decode":
        return ""
    if cfg.n_experts <= 0 or not cfg.batch_axes or cfg.seq_axes:
        return ""
    if cfg.n_experts % shd._axis_size(mesh, "model") == 0:
        return "ep"
    if cfg.d_ff % shd._axis_size(mesh, "model") == 0:
        return "ep_tp"
    return ""


def cell_config(arch: str, shape_name: str, mesh: Mesh,
                profile: str = "tp") -> ModelConfig:
    """The full config specialized for this cell: batch-axis constraints
    applied when the batch is shardable over DP, MoE dispatch mode, and
    optional sequence parallelism."""
    cfg = get_config(arch)
    B = SHAPES[shape_name].batch
    S = SHAPES[shape_name].seq
    dp = dp_axes(mesh)
    if profile in ("fsdp", "fsdp_seqp"):
        all_axes = tuple(mesh.axis_names)
        if profile == "fsdp" and B % shd._axis_size(mesh, all_axes) == 0:
            cfg = dataclasses.replace(cfg, batch_axes=all_axes)
        elif B % shd._axis_size(mesh, tuple(dp)) == 0:
            cfg = dataclasses.replace(cfg, batch_axes=tuple(dp))
        if profile == "fsdp_seqp" and SHAPES[shape_name].kind != "decode" \
                and S % shd._axis_size(mesh, "model") == 0:
            # context sharding over the model axis (§Perf iteration 3)
            cfg = dataclasses.replace(
                cfg, seq_axes=("model",),
                seq_axes_size=shd._axis_size(mesh, "model"))
    elif B % shd._axis_size(mesh, tuple(dp)) == 0:
        cfg = dataclasses.replace(cfg, batch_axes=tuple(dp))
    return dataclasses.replace(
        cfg, moe_ep=_moe_mode(cfg, mesh, SHAPES[shape_name].kind))


def lower_cell(arch: str, shape_name: str, mesh: Mesh,
               cfg: Optional[ModelConfig] = None, profile: str = "tp",
               grad_accum: int = 1):
    """Lower (no compile) the step function of one cell on ``mesh``."""
    cfg = cfg or cell_config(arch, shape_name, mesh, profile)
    specs = input_specs(arch, shape_name, mesh, cfg)
    with jax.set_mesh(mesh):
        if specs["kind"] == "train":
            optimizer = default_optimizer(cfg)
            state = abstract_train_state(cfg, optimizer, mesh, profile)
            step = make_train_step(cfg, optimizer, grad_accum=grad_accum)
            return jax.jit(step, donate_argnums=(0,)).lower(
                state, specs["batch"])
        params = abstract_params(cfg, mesh, profile)
        if specs["kind"] == "prefill":
            fn = make_prefill(cfg, specs["s_max"])
            args = (params, specs["tokens"])
            if "vision_embeds" in specs:
                args = args + (specs["vision_embeds"],)
            return jax.jit(fn).lower(*args)
        fn = make_decode_step(cfg)
        return jax.jit(fn, donate_argnums=(1,)).lower(
            params, specs["caches"], specs["token"], specs["pos"])
