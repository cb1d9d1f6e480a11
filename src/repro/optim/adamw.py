"""AdamW with fp32 master weights + bf16 model weights (mixed precision),
global-norm clipping, decoupled weight decay with a name-based mask, and
ZeRO-style sharding spec derivation.

State pytree:
    {"step": i32[], "mu": fp32 tree, "nu": fp32 tree, "master": fp32 tree}

The device-side elementwise update is pluggable: the Pallas
``fused_adamw`` kernel (kernels/fused_adamw) implements the same math for
TPU; ``repro.kernels.fused_adamw.ops.adamw_update_leaf`` is selected with
``use_kernel=True`` (or any compatible callable via ``update_fn=``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import OptimizerConfig
from repro.optim.schedules import lr_at


def _decay_masks(tree) -> Any:
    """Decay only >=2-D tensors (matmul weights / embeddings); skip norm
    scales, biases, per-head scalars — the classic AdamW rule."""
    return jax.tree.map(lambda a: a.ndim >= 2, tree)


def adamw_init(params) -> Dict[str, Any]:
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.zeros(a.shape, jnp.float32), t)
    return {
        "step": jnp.zeros((), jnp.int32),
        "mu": f32(params),
        "nu": f32(params),
        "master": jax.tree.map(lambda a: a.astype(jnp.float32), params),
    }


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in jax.tree.leaves(tree)) + 1e-30)


def adamw_update(grads, state, cfg: OptimizerConfig, *,
                 update_fn: Optional[Callable] = None,
                 use_kernel: bool = False,
                 grad_norm=None):
    """Returns (new_params_in_model_dtype_tree_of(master), new_state,
    metrics).  ``grads`` may be any float dtype; math is fp32.

    ``use_kernel=True`` selects the fused Pallas elementwise update
    (``repro.kernels.fused_adamw.ops.adamw_update_leaf``); ``update_fn``
    overrides it with any callable of the same signature.  ``grad_norm``
    supplies a precomputed global norm — callers running inside a
    ``shard_map`` region (the in-executor fused optimizer) pass the
    psum-reduced norm because ``global_norm`` over the local tree would
    miss the other pipeline stages' block gradients."""
    with jax.named_scope("optimizer"):
        if update_fn is None and use_kernel:
            from repro.kernels.fused_adamw.ops import adamw_update_leaf
            update_fn = adamw_update_leaf
        step = state["step"] + 1
        lr = lr_at(cfg, step)
        gnorm = global_norm(grads) if grad_norm is None else grad_norm
        clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
            if cfg.grad_clip > 0 else jnp.asarray(1.0)
        b1, b2, eps = cfg.beta1, cfg.beta2, cfg.eps
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        masks = _decay_masks(grads)

        def upd(g, mu, nu, w, decay_on):
            g = g.astype(jnp.float32) * clip
            if update_fn is not None:
                return update_fn(g, mu, nu, w, lr=lr, b1=b1, b2=b2, eps=eps,
                                 bc1=bc1, bc2=bc2,
                                 wd=cfg.weight_decay if decay_on else 0.0)
            mu = b1 * mu + (1 - b1) * g
            nu = b2 * nu + (1 - b2) * jnp.square(g)
            upd = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
            if decay_on:
                upd = upd + cfg.weight_decay * w
            w = w - lr * upd
            return mu, nu, w

        out = jax.tree.map(upd, grads, state["mu"], state["nu"],
                           state["master"], masks)
        mu = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x:
                          isinstance(x, tuple))
        nu = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x:
                          isinstance(x, tuple))
        master = jax.tree.map(lambda o: o[2], out, is_leaf=lambda x:
                              isinstance(x, tuple))
        new_state = {"step": step, "mu": mu, "nu": nu, "master": master}
        metrics = {"grad_norm": gnorm, "lr": lr}
        return master, new_state, metrics


def cast_like(tree_fp32, params_proto):
    return jax.tree.map(lambda m, p: m.astype(p.dtype), tree_fp32,
                        params_proto)


# ---------------------------------------------------------------------------
# ZeRO sharding-spec derivation
# ---------------------------------------------------------------------------

def zero_state_specs(param_logical_specs, zero_stage: int):
    """Derive optimizer-state logical specs from parameter logical specs.

    - stage >= 1: optimizer states (mu/nu/master) carry the fsdp axis
      (sharded over the data axis) regardless of whether the params do.
    - stage >= 3: callers should also shard the *params* with fsdp (the
      model specs here already include fsdp on weight matrices, so ZeRO-3
      corresponds to using them as-is).
    """
    def add_fsdp(spec):
        if spec is None:
            return spec
        spec = tuple(spec)
        if any(ax == "fsdp" or (isinstance(ax, tuple) and "fsdp" in ax)
               for ax in spec):
            return spec
        # attach fsdp to the first free (None) axis, else leave replicated
        out = list(spec)
        for i, ax in enumerate(out):
            if ax is None:
                out[i] = "fsdp"
                return tuple(out)
        return spec

    if zero_stage < 1:
        return param_logical_specs
    return jax.tree.map(add_fsdp, param_logical_specs,
                        is_leaf=lambda s: isinstance(s, tuple) or s is None)


def drop_fsdp(param_logical_specs):
    """Param specs for ZeRO-1/2 (params replicated over dp, states
    sharded): remove the fsdp axis from parameter specs."""
    def rm(spec):
        if spec is None:
            return spec
        out = []
        for ax in tuple(spec):
            if ax == "fsdp":
                out.append(None)
            elif isinstance(ax, tuple):
                out.append(tuple(a for a in ax if a != "fsdp") or None)
            else:
                out.append(ax)
        return tuple(out)
    return jax.tree.map(rm, param_logical_specs,
                        is_leaf=lambda s: isinstance(s, tuple) or s is None)
