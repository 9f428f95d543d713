"""FFN sites: the dispatch point for the paper's technique.

``FFNSpec.kind`` selects dense (vanilla FF), fff (fast feedforward — the
paper), or moe (noisy-top-k — the contender).  One init/forward interface so
transformer blocks are agnostic to the choice; aux losses (hardening entropy,
MoE balancing) flow out through the aux dict.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FFNSpec
from repro.core import api, ff, fff, moe

Params = dict


def make_fff_config(spec: FFNSpec, d_model: int, *, param_dtype, accum_dtype
                    ) -> fff.FFFConfig:
    return fff.FFFConfig(
        dim_in=d_model, dim_out=d_model, depth=spec.fff_depth,
        leaf_width=spec.fff_leaf_width, node_width=spec.fff_node_width,
        activation=spec.activation, trees=spec.fff_trees,
        hardening_scale=spec.hardening_scale, leaf_bias=False,
        st_training=spec.fff_st, master_leaf=spec.fff_master_leaf,
        master_width=spec.fff_master_width,
        param_dtype=param_dtype, accum_dtype=accum_dtype)


def make_moe_config(spec: FFNSpec, d_model: int, *, param_dtype, accum_dtype
                    ) -> moe.MoEConfig:
    return moe.MoEConfig(
        dim_in=d_model, dim_out=d_model, num_experts=spec.moe_experts,
        expert_width=spec.d_ff, top_k=spec.moe_top_k,
        activation="gelu" if spec.activation == "swiglu" else spec.activation,
        bias=False, param_dtype=param_dtype, accum_dtype=accum_dtype)


def make_ff_config(spec: FFNSpec, d_model: int, *, param_dtype, accum_dtype
                   ) -> ff.FFConfig:
    return ff.FFConfig(
        dim_in=d_model, dim_out=d_model, width=spec.d_ff,
        activation=spec.activation, bias=False,
        param_dtype=param_dtype, accum_dtype=accum_dtype)


def init(key: jax.Array, spec: FFNSpec, d_model: int, *, param_dtype,
         accum_dtype) -> Params:
    kw = dict(param_dtype=param_dtype, accum_dtype=accum_dtype)
    if spec.kind == "none":
        return {}
    if spec.kind == "dense":
        return ff.init(key, make_ff_config(spec, d_model, **kw))
    if spec.kind == "fff":
        return fff.init(key, make_fff_config(spec, d_model, **kw))
    if spec.kind == "moe":
        return moe.init(key, make_moe_config(spec, d_model, **kw))
    raise ValueError(f"unknown ffn kind {spec.kind!r}")


def forward(params: Params, spec: FFNSpec, d_model: int, x: jax.Array, *,
            param_dtype, accum_dtype, train: bool = True,
            rng: Optional[jax.Array] = None,
            valid: Optional[jax.Array] = None) -> tuple[jax.Array, dict]:
    """x (..., D) -> (..., D), aux {'hardening', 'moe_aux', 'balance'}
    (scalars).

    ``valid`` (broadcastable to x's leading shape) marks phantom tokens —
    pad columns of a chunked-prefill slab, free slots of a serving decode
    batch — that capacity-bounded FFF backends must keep out of
    grouped-dispatch capacity and routing telemetry (ExecutionSpec.valid)."""
    kw = dict(param_dtype=param_dtype, accum_dtype=accum_dtype)
    zero = jnp.zeros((), jnp.float32)
    if spec.kind == "none":
        return x, {"hardening": zero, "moe_aux": zero, "balance": zero}
    if spec.kind == "dense":
        return ff.forward(params, make_ff_config(spec, d_model, **kw), x), \
            {"hardening": zero, "moe_aux": zero, "balance": zero}
    if spec.kind == "fff":
        cfg = make_fff_config(spec, d_model, **kw)
        # one entry point; backend="auto" picks the execution strategy per
        # platform/site (and the launch layer can steer it via
        # api.overrides) — see core/api.py.  The scope names the site's ops
        # in the HLO metadata (the device trace's per-scope time)
        with jax.named_scope("fff_site"):
            y, out = api.apply(params, cfg, x, api.ExecutionSpec(
                mode="train" if train else "infer", rng=rng, valid=valid))
        if train:
            harden = spec.hardening_scale * fff.hardening_loss(out.node_probs)
            # load-balancing over soft leaf usage (DESIGN.md §14); the soft
            # node_probs exist in both the FORWARD_T and ST train paths
            balance = (spec.balance_scale
                       * fff.balance_loss(out.node_probs, cfg.depth)
                       if spec.balance_scale else zero)
        aux = {"hardening": harden.astype(jnp.float32) if train else zero,
               "moe_aux": zero,
               "balance": balance.astype(jnp.float32) if train else zero}
        if not train and api.routing_enabled():
            # serving telemetry rides the aux return (DESIGN.md §9): a side
            # list would capture scan-body tracers under scan_layers
            aux["routing"] = api.routing_stats_from(out, cfg, valid)
        return y, aux
    if spec.kind == "moe":
        cfg = make_moe_config(spec, d_model, **kw)
        if train:
            y, aux = moe.forward(params, cfg, x, rng=rng, train=True)
            return y, {"hardening": zero,
                       "moe_aux": aux["aux_loss"].astype(jnp.float32),
                       "balance": zero}
        y, _ = moe.forward_sparse(params, cfg, x)
        return y, {"hardening": zero, "moe_aux": zero, "balance": zero}
    raise ValueError(f"unknown ffn kind {spec.kind!r}")
