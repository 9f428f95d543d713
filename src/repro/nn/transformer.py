"""Transformer block + stack assembly.

A model is a repeated *period* of heterogeneous blocks (attn / mamba / mlstm /
slstm, each with an optional FFN site).  Parameters of each period position
are stacked over the ``n_periods`` axis so the whole stack lowers to one
``lax.scan`` — small HLO, fast multi-pod compiles, and a natural remat point.

Modes: ``train`` (no cache), ``prefill`` (build caches over a prefix),
``decode`` (one token against the caches).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import BlockSpec, ModelConfig
from repro.core import api
from repro.distributed import act
from repro.nn import attention, mamba, mlp, norms, xlstm

Params = dict
Cache = dict


def _routing_weighted(r: "api.RoutingStats | None"):
    """Pre-weight overflow by slot count so per-layer records sum correctly
    across the period scan (finalized back to a fraction below)."""
    if r is None:
        return None
    return api.RoutingStats(r.leaf_counts, r.overflow * r.slots, r.slots,
                            r.distinct)


def _routing_finalize(r: "api.RoutingStats | None"):
    if r is None:
        return None
    return api.RoutingStats(r.leaf_counts,
                            r.overflow / jnp.maximum(r.slots, 1.0), r.slots,
                            r.distinct)


# ---------------------------------------------------------------------------
# per-block
# ---------------------------------------------------------------------------

def make_attn_config(cfg: ModelConfig, spec: BlockSpec, *, causal: bool = True
                     ) -> attention.AttnConfig:
    return attention.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, bias=cfg.attn_bias,
        rope_theta=cfg.rope_theta, use_rope=(cfg.pos_emb == "rope"),
        causal=causal, sliding_window=spec.sliding_window, chunk=cfg.attn_chunk,
        param_dtype=cfg.param_dtype, accum_dtype=cfg.accum_dtype)


def make_mamba_config(cfg: ModelConfig) -> mamba.MambaConfig:
    return mamba.MambaConfig(
        d_model=cfg.d_model, d_state=cfg.mamba_d_state, d_conv=cfg.mamba_d_conv,
        expand=cfg.mamba_expand, param_dtype=cfg.param_dtype,
        accum_dtype=cfg.accum_dtype)


def make_xlstm_config(cfg: ModelConfig) -> xlstm.XLSTMConfig:
    return xlstm.XLSTMConfig(
        d_model=cfg.d_model, n_heads=cfg.lstm_heads,
        param_dtype=cfg.param_dtype, accum_dtype=cfg.accum_dtype)


def block_init(key: jax.Array, cfg: ModelConfig, spec: BlockSpec, *,
               causal: bool = True) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": norms.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)}
    if spec.mixer == "attn":
        p["mixer"] = attention.init(ks[0], make_attn_config(cfg, spec, causal=causal))
    elif spec.mixer == "mamba":
        p["mixer"] = mamba.init(ks[0], make_mamba_config(cfg))
    elif spec.mixer == "mlstm":
        p["mixer"] = xlstm.mlstm_init(ks[0], make_xlstm_config(cfg))
    elif spec.mixer == "slstm":
        p["mixer"] = xlstm.slstm_init(ks[0], make_xlstm_config(cfg))
    elif spec.mixer != "none":
        raise ValueError(f"unknown mixer {spec.mixer!r}")
    if spec.cross_attention:
        p["norm_x"] = norms.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
        p["cross"] = attention.init(ks[1], make_attn_config(cfg, spec, causal=False))
    if spec.ffn.kind != "none":
        p["norm2"] = norms.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
        p["ffn"] = mlp.init(ks[2], spec.ffn, cfg.d_model,
                            param_dtype=cfg.param_dtype, accum_dtype=cfg.accum_dtype)
    return p


def init_block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int,
                     max_len: int, enc_len: int = 0, dtype=None, *,
                     page_size: int = 0, num_pages: int = 0,
                     prealloc: bool = True) -> Cache:
    dtype = dtype or cfg.param_dtype
    c: Cache = {}
    if spec.mixer == "attn":
        c["kv"] = attention.init_cache(batch, max_len,
                                       make_attn_config(cfg, spec), dtype,
                                       page_size=page_size,
                                       num_pages=num_pages, prealloc=prealloc)
    elif spec.mixer == "mamba":
        c["mamba"] = mamba.init_state(batch, make_mamba_config(cfg), cfg.accum_dtype)
    elif spec.mixer == "mlstm":
        c["mlstm"] = xlstm.mlstm_init_state(batch, make_xlstm_config(cfg),
                                            cfg.accum_dtype)
    elif spec.mixer == "slstm":
        c["slstm"] = xlstm.slstm_init_state(batch, cfg.d_model, cfg.accum_dtype)
    if spec.cross_attention:
        K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        c["cross_k"] = jnp.zeros((batch, enc_len, K, hd), dtype)
        c["cross_v"] = jnp.zeros((batch, enc_len, K, hd), dtype)
    return c


def block_forward(params: Params, cfg: ModelConfig, spec: BlockSpec,
                  x: jax.Array, *, mode: str = "train",
                  cache: Optional[Cache] = None,
                  rng: Optional[jax.Array] = None,
                  enc_out: Optional[jax.Array] = None,
                  causal: bool = True,
                  chunk_valid: Optional[jax.Array] = None,
                  decode_mask: Optional[jax.Array] = None,
                  token_valid: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, Optional[Cache], dict]:
    """One block: pre-norm mixer + residual, [cross-attn], pre-norm FFN + residual.

    ``mode="chunk"`` (chunked prefill, DESIGN.md §9) consumes a (B, C) slab
    against each row's cache at its current length; ``chunk_valid`` (B,)
    gives each row's real token count.  ``decode_mask`` (B,) bool, decode
    mode only: rows where it is False do not write/advance their KV cache.
    Both are attention-mixer features — recurrent mixers have no
    length-masked state to protect (the serving engine rejects them).

    ``token_valid`` (bool, broadcastable to x's leading (B, S) shape) marks
    phantom tokens for the FFN dispatch: capacity-bounded FFF backends route
    them to the sentinel leaf so they never consume grouped capacity or
    pollute routing telemetry (DESIGN.md §9).  In chunk mode it is derived
    from ``chunk_valid`` when not given; it is deliberately separate from
    ``decode_mask`` so a caller can keep KV writes on for every row (the
    monolithic engine's fixed-shape contract) while still masking the FFF
    dispatch."""
    new_cache: Cache = {} if cache is not None else None
    h = norms.norm_apply(cfg.norm, params["norm1"], x)

    if mode == "chunk" and (spec.mixer != "attn" or spec.cross_attention):
        raise ValueError("chunked prefill requires plain attention mixers "
                         "(recurrent state folds pad garbage in; cross-attn "
                         "slabs are unsupported)")
    if spec.mixer == "attn":
        acfg = make_attn_config(cfg, spec, causal=causal)
        if mode in ("train", "eval"):      # eval: full attn, hard FFN routing
            y = attention.forward(params["mixer"], acfg, h)
        elif mode == "prefill":
            y, kv = attention.forward_prefill(params["mixer"], acfg, h, cache["kv"])
            new_cache["kv"] = kv
        elif mode == "chunk":
            y, kv = attention.forward_chunk(params["mixer"], acfg, h,
                                            cache["kv"], chunk_valid)
            new_cache["kv"] = kv
        else:
            y, kv = attention.forward_decode(params["mixer"], acfg, h,
                                             cache["kv"], decode_mask)
            new_cache["kv"] = kv
    elif spec.mixer == "mamba":
        mcfg = make_mamba_config(cfg)
        st = cache["mamba"] if cache is not None else None
        y, st2 = mamba.forward(params["mixer"], mcfg, h, st)
        if cache is not None:
            new_cache["mamba"] = st2
    elif spec.mixer == "mlstm":
        xcfg = make_xlstm_config(cfg)
        st = cache["mlstm"] if cache is not None else None
        y, st2 = xlstm.mlstm_block(params["mixer"], xcfg, h, st)
        if cache is not None:
            new_cache["mlstm"] = st2
    elif spec.mixer == "slstm":
        xcfg = make_xlstm_config(cfg)
        st = cache["slstm"] if cache is not None else None
        y, st2 = xlstm.slstm_block(params["mixer"], xcfg, h, st)
        if cache is not None:
            new_cache["slstm"] = st2
    else:
        y = jnp.zeros_like(h)
    x = x + y
    x = act.shard(x, act.ACT_BSD)

    if spec.cross_attention:
        acfg = make_attn_config(cfg, spec, causal=False)
        hx = norms.norm_apply(cfg.norm, params["norm_x"], x)
        if mode in ("train", "eval"):
            ek, ev = attention.cross_kv(params["cross"], acfg, enc_out)
        elif mode == "prefill":
            ek, ev = attention.cross_kv(params["cross"], acfg, enc_out)
            new_cache["cross_k"], new_cache["cross_v"] = ek, ev
        else:
            ek, ev = cache["cross_k"], cache["cross_v"]
            new_cache["cross_k"], new_cache["cross_v"] = ek, ev
        x = x + attention.forward_cross(params["cross"], acfg, hx, ek, ev)
        x = act.shard(x, act.ACT_BSD)

    aux = {"hardening": jnp.zeros((), jnp.float32),
           "moe_aux": jnp.zeros((), jnp.float32),
           "balance": jnp.zeros((), jnp.float32)}
    if spec.ffn.kind != "none":
        if token_valid is None and mode == "chunk" and chunk_valid is not None:
            token_valid = (jnp.arange(x.shape[1]) < chunk_valid[:, None])
        h2 = norms.norm_apply(cfg.norm, params["norm2"], x)
        y2, aux = mlp.forward(params["ffn"], spec.ffn, cfg.d_model, h2,
                              param_dtype=cfg.param_dtype,
                              accum_dtype=cfg.accum_dtype,
                              train=(mode == "train"), rng=rng,
                              valid=token_valid)
        x = x + y2
        x = act.shard(x, act.ACT_BSD)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# the stack: scan over periods
# ---------------------------------------------------------------------------

def stack_init(key: jax.Array, cfg: ModelConfig, *, causal: bool = True,
               period: tuple[BlockSpec, ...] | None = None,
               n_layers: int | None = None) -> list[Params]:
    """Returns a list (one entry per period position) of param trees whose
    leaves carry a leading ``n_periods`` axis."""
    period = period or cfg.period
    n_layers = n_layers or cfg.n_layers
    n_periods = n_layers // len(period)
    keys = jax.random.split(key, n_layers).reshape(n_periods, len(period),
                                                   -1)
    # one vmapped init per period position (layer i*len(period)+pos draws
    # keys[i, pos]): the same values as a per-layer loop, in a program
    # whose size does not grow with depth
    return [jax.vmap(lambda k, spec=spec: block_init(k, cfg, spec,
                                                     causal=causal))(
                keys[:, pos])
            for pos, spec in enumerate(period)]


def init_caches(cfg: ModelConfig, batch: int, max_len: int, *,
                period: tuple[BlockSpec, ...] | None = None,
                n_layers: int | None = None, enc_len: int = 0,
                dtype=None, page_size: int = 0, num_pages: int = 0,
                prealloc: bool = True) -> list[Cache]:
    """Stacked caches, mirroring stack_init's layout."""
    period = period or cfg.period
    n_layers = n_layers or cfg.n_layers
    n_periods = n_layers // len(period)
    out = []
    for spec in period:
        one = init_block_cache(cfg, spec, batch, max_len, enc_len, dtype,
                               page_size=page_size, num_pages=num_pages,
                               prealloc=prealloc)
        out.append(jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_periods,) + x.shape), one))
    return out


def stack_forward(params: list[Params], cfg: ModelConfig, x: jax.Array, *,
                  mode: str = "train", caches: Optional[list[Cache]] = None,
                  rng: Optional[jax.Array] = None,
                  enc_out: Optional[jax.Array] = None,
                  causal: bool = True,
                  period: tuple[BlockSpec, ...] | None = None,
                  chunk_valid: Optional[jax.Array] = None,
                  decode_mask: Optional[jax.Array] = None,
                  token_valid: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, Optional[list[Cache]], dict]:
    """Run the whole stack (scan over periods).  ``chunk_valid`` /
    ``decode_mask`` / ``token_valid`` ride through to every block (see
    ``block_forward``); they are loop-invariant, so the scan closes over
    them."""
    period = period or cfg.period
    n_periods = jax.tree_util.tree_leaves(params[0])[0].shape[0]
    use_rng = rng is not None
    if use_rng:
        flat = jax.random.split(rng, n_periods * len(period))
        rngs = flat.reshape(n_periods, len(period), *flat.shape[1:])
    else:
        rngs = jnp.zeros((n_periods, len(period)), jnp.uint32)

    def period_body(x, per_params, per_caches, per_rngs):
        new_caches = []
        aux_h = jnp.zeros((), jnp.float32)
        aux_m = jnp.zeros((), jnp.float32)
        aux_b = jnp.zeros((), jnp.float32)
        routing = []
        for pos, spec in enumerate(period):
            r = per_rngs[pos] if use_rng else None
            c = per_caches[pos] if per_caches is not None else None
            x, nc, aux = block_forward(
                per_params[pos], cfg, spec, x, mode=mode, cache=c, rng=r,
                enc_out=enc_out, causal=causal, chunk_valid=chunk_valid,
                decode_mask=decode_mask, token_valid=token_valid)
            new_caches.append(nc)
            aux_h = aux_h + aux["hardening"]
            aux_m = aux_m + aux["moe_aux"]
            aux_b = aux_b + aux["balance"]
            # per-position (not summed across positions): sites in one period
            # may have different leaf counts; summation happens across
            # *periods*, where position specs are identical
            routing.append(_routing_weighted(aux.get("routing")))
        return x, new_caches, (aux_h, aux_m, aux_b, tuple(routing))

    def finish_aux(aux_h, aux_m, aux_b, routing):
        aux = {"hardening": aux_h, "moe_aux": aux_m, "balance": aux_b}
        if any(r is not None for r in routing):
            aux["routing"] = tuple(_routing_finalize(r) for r in routing)
        return aux

    if cfg.scan_layers:
        def scan_body(carry, xs):
            x = carry
            per_params, per_caches, per_rngs = xs
            x, new_caches, aux = period_body(x, per_params, per_caches, per_rngs)
            if new_caches[0] is None:
                new_caches = [{} for _ in new_caches]
            return x, (new_caches, aux)

        body = scan_body
        if cfg.remat == "dots" and mode == "train":
            body = jax.checkpoint(
                scan_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        elif cfg.remat == "full" and mode == "train":
            body = jax.checkpoint(scan_body)
        xs = (params, caches, rngs)
        x, (new_caches, (aux_h, aux_m, aux_b, routing)) = jax.lax.scan(
            body, x, xs)
        routing = jax.tree_util.tree_map(lambda a: a.sum(0), routing)
        aux = finish_aux(aux_h.sum(), aux_m.sum(), aux_b.sum(), routing)
        return x, (new_caches if caches is not None else None), aux

    # unrolled path (smoke tests / tiny models)
    aux_h = jnp.zeros((), jnp.float32)
    aux_m = jnp.zeros((), jnp.float32)
    aux_b = jnp.zeros((), jnp.float32)
    routing_acc = None
    new_caches_acc = [[] for _ in period]
    for i in range(n_periods):
        per_params = [jax.tree_util.tree_map(lambda a: a[i], p) for p in params]
        per_caches = ([jax.tree_util.tree_map(lambda a: a[i], c) for c in caches]
                      if caches is not None else None)
        per_rngs = rngs[i]
        x, ncs, (h_, m_, b_, routing) = period_body(x, per_params, per_caches,
                                                    per_rngs)
        aux_h += h_
        aux_m += m_
        aux_b += b_
        routing_acc = (routing if routing_acc is None else
                       jax.tree_util.tree_map(jnp.add, routing_acc, routing))
        for pos, nc in enumerate(ncs):
            new_caches_acc[pos].append(nc)
    new_caches = None
    if caches is not None:
        new_caches = [jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ncs)
                      for ncs in new_caches_acc]
    return x, new_caches, finish_aux(aux_h, aux_m, aux_b, routing_acc)
