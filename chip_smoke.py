#!/usr/bin/env python3
"""Chip smoke test: serve internlm2-20b FFF at its published widths on TPU.

One chip (the default)
    The ``internlm2-20b`` FFF model at its published widths — d_model 6144,
    48/8 attention heads of 128, 16 SwiGLU leaves of 1024, vocab 92544,
    bf16 — cut to 4 layers (about 5.4 GB of weights),
    random weights from ``--seed``.  ``ContinuousBatchingEngine`` serves it
    with ``fff_backend="auto"``: 8 slots, 16 requests with prompts of
    128-1024 tokens and 32 generated tokens each, chunked prefill of 256
    tokens (so the compiled family is decode, a chunk slab per row bucket,
    admit).  Then every FFF kernel path the run used is compared, on
    the chip and at full width, against ``backend="reference"`` in float32
    on the same site weights and inputs: at the decode shape, on a 64-token
    prefill slab, and on a skewed 256-token slab at a low capacity factor,
    where the kernel's exact overflow repair must serve the tokens it
    dropped.

Four chips (``--four-chips``)
    The whole 48-layer model (about 40 GB of bf16 weights: it needs four
    chips), initialised already sharded over a (1, 4) mesh, served
    expert-parallel (``grouped_ep`` and its ``all_to_all``) through the same
    engine; per-device memory shows the weights and the KV pool spread over
    all four chips; then the sharded dispatch is compared against the
    reference backend, which does not use it: the same three site
    comparisons on the sharded weights, and every one of the 48 FFF sites
    on the activations the sharded model computes for a 64-token prompt.
    The next-token logits of the whole model run freely through each
    backend are printed beside them, unchecked, with a control that moves
    only the embeddings by one ulp: see ``compare_free_running``.

Earlier lines report the backends ``api.resolve_backend`` picks, the decode
step's ``pallas_call`` count, compile seconds, the engine's metrics report
and ``compiled_shapes()``.  The last line of standard output is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without
a TPU, or when any phase fails, the script exits non-zero and prints no
such line.

Usage:
    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # four chips, sharded path only
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")
#: depth of the one-chip model: the published 48 layers hold 40 GB of bf16
#: weights; 4 leave room on a 16 GB v5e for the KV pool and the float32
#: reference of the comparison
ONE_CHIP_LAYERS = 4

# -- tolerances of the kernel-vs-reference comparison -------------------------
#: Share of tokens whose routed leaves must agree.  The kernels route on
#: bf16 activations with f32 accumulation in MXU order; the float32
#: reference sums the same products in another order, so a node logit
#: within rounding of zero may take the other branch.  Outputs are
#: compared on the tokens whose leaves agree.
LEAF_AGREEMENT = 0.95
#: Max |kernel - reference| over max |reference|, per compared path.  The
#: kernels store the leaf hidden activation and the output in bf16 (each a
#: relative rounding of at most 2^-8) and accumulate in f32; over the
#: 1024-wide leaf the roundings mostly cancel, so 2e-2 leaves a 5x margin
#: over two worst-case roundings.
SITE_REL_TOL = 2e-2
#: Tokens per block of the float32 reference: its per-token weight gather
#: copies a (tokens, D, l) float32 slab of every leaf matrix (75 MB per
#: token at published widths), so it runs over a slab in blocks this small.
REF_BLOCK = 16
#: The repair comparison: a slab of tokens near one point (noise of this
#: relative size around it), so most route to one leaf, served at a
#: capacity factor low enough that the backend drops tokens and its exact
#: overflow repair must serve them.
SKEW_NOISE = 0.05
REPAIR_CF = 0.25


class SmokeFailure(RuntimeError):
    """A phase of the smoke found the system wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def smoke_config(n_layers: int):
    """internlm2-20b FFF at published widths and dtype, cut to n_layers."""
    from repro.configs import internlm2_20b
    return dataclasses.replace(internlm2_20b.FFF_CONFIG, n_layers=n_layers)


def fff_site(params, cfg, layer: int = 0):
    """(FFFConfig, params) of one FFF site of the layer stack."""
    import jax
    from repro.nn import mlp as mlp_lib
    scfg = mlp_lib.make_fff_config(cfg.period[0].ffn, cfg.d_model,
                                   param_dtype=cfg.param_dtype,
                                   accum_dtype=cfg.accum_dtype)
    return scfg, jax.tree_util.tree_map(lambda a: a[layer],
                                        params["stack"][0]["ffn"])


def make_requests(cfg, n: int, prompt_lens: tuple, gen: int, seed: int):
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n)
    lens[0] = prompt_lens[1]                 # the longest prompt is served
    return [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, int(L),
                                               dtype=np.int32),
                    max_new_tokens=gen) for i, L in enumerate(lens)]


def serve(cfg, params, *, slots: int, n_requests: int, prompt_lens: tuple,
          gen: int, chunk: int, seed: int, mesh=None, trace_ctx=None,
          expect: tuple | None = None) -> dict:
    """Serve a mixed-length workload through the engine (``fff_backend=
    "auto"``, chunked prefill) and check it: the backends the resolver
    picks for the decode and chunk shapes (``expect`` = the pair required),
    the decode step's ``pallas_call`` count, every request finished, every
    sampled logit row finite.  Returns what it measured."""
    import jax.numpy as jnp
    from repro.core import api
    from repro.kernels import common
    from repro.models import lm
    from repro.serving.engine import ContinuousBatchingEngine, EngineConfig

    ctx = trace_ctx or contextlib.nullcontext
    scfg, site = fff_site(params, cfg)
    with ctx():
        backends = (api.resolve_backend(site, scfg, "infer",
                                        x_shape=(slots, 1, cfg.d_model)),
                    api.resolve_backend(site, scfg, "infer",
                                        x_shape=(slots, chunk, cfg.d_model)))
    print(f"backend: decode {(slots, 1, cfg.d_model)} -> {backends[0]}, "
          f"chunk {(slots, chunk, cfg.d_model)} -> {backends[1]}")
    if expect is not None:
        check(backends == tuple(expect),
              f"expected backends {expect} on this platform, resolver "
              f"picked {backends}")

    engine = ContinuousBatchingEngine(
        params, cfg, EngineConfig(
            num_slots=slots, max_len=prompt_lens[1] + gen,
            max_prompt_len=prompt_lens[1], prefill_chunk=chunk,
            fff_backend="auto", seed=seed),
        trace_ctx=trace_ctx, mesh=mesh)
    with ctx():
        n_calls = common.count_pallas_calls(
            lambda p, t, c, o: lm.decode_step(p, cfg, t, c, o)[0], params,
            jnp.zeros((slots, 1), jnp.int32), engine.caches,
            jnp.zeros((slots,), jnp.int32))
    print(f"decode step: {n_calls} pallas_call dispatches for "
          f"{cfg.n_layers} FFF sites")
    if backends[0] == "pallas_decode":
        check(n_calls >= cfg.n_layers,
              f"decode step issues {n_calls} pallas_calls for "
              f"{cfg.n_layers} FFF sites: a kernel path was not taken")

    nonfinite = []
    sample = engine._sample

    def checked_sample(st, logits_row):
        if not np.isfinite(logits_row).all():
            nonfinite.append(st.request.rid)
        return sample(st, logits_row)

    engine._sample = checked_sample
    reqs = make_requests(cfg, n_requests, prompt_lens, gen, seed)
    t0 = time.perf_counter()
    results, metrics = engine.run(reqs)
    wall = time.perf_counter() - t0
    done = [r for r in results if r.n_generated == gen]
    print(f"served {len(done)}/{len(reqs)} requests "
          f"(prompts {min(len(r.prompt) for r in reqs)}-"
          f"{max(len(r.prompt) for r in reqs)} tokens, {gen} generated "
          f"each) in {wall:.3f} s wall, compiles included")
    print(metrics.report())
    print(f"compiled shapes: {engine.compiled_shapes()}")
    check(len(done) == len(reqs),
          f"{len(reqs) - len(done)} of {len(reqs)} requests did not finish")
    check(not nonfinite, f"non-finite logits for requests {nonfinite}")
    return {"backends": backends, "decode_pallas_calls": n_calls,
            "finished": len(done), "engine": engine}


def site_pair(scfg, backend: str, capacity_factor=None):
    """A jitted ``(site, x) -> (y, leaf_idx, overflow, y_ref, leaf_idx_ref)``:
    one FFF site through ``backend``, and through ``backend="reference"``
    in float32 (``default_matmul_precision("highest")``) on the same
    weights and bf16 inputs x (..., D).  The reference runs over the tokens
    in blocks of at most ``REF_BLOCK``; outputs come back flattened to
    (tokens, ...)."""
    import math

    import jax
    import jax.numpy as jnp
    from repro import utils
    from repro.core import api

    cfg32 = dataclasses.replace(scfg, param_dtype=jnp.float32,
                                accum_dtype=jnp.float32)
    spec = api.ExecutionSpec(backend=backend, capacity_factor=capacity_factor)
    ref_spec = api.ExecutionSpec(backend="reference")

    def run(site, x):
        y, out = api.apply(site, scfg, x, spec)
        n = math.prod(x.shape[:-1])
        blk = math.gcd(n, REF_BLOCK)
        site32 = utils.tree_cast(site, jnp.float32)
        with jax.default_matmul_precision("highest"):
            y_ref, out_ref = jax.lax.map(
                lambda xb: api.apply(site32, cfg32, xb, ref_spec),
                x.reshape(n // blk, blk, -1).astype(jnp.float32))
        return (y.reshape(n, -1), out.leaf_idx.reshape(n, -1),
                out.overflow_fraction, y_ref.reshape(n, -1),
                out_ref.leaf_idx.reshape(n, -1))

    return jax.jit(run)


def slab(shape: tuple, seed: int, dtype, skew: bool = False):
    """Random bf16 site inputs; ``skew`` puts every token within
    ``SKEW_NOISE`` of one point, so most route to one leaf."""
    import jax
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, shape)
    if skew:
        x = jax.random.normal(k2, shape[-1:]) + SKEW_NOISE * x
    return x.astype(dtype)


def overflow_share(backend: str, scfg, idx, overflow,
                   capacity_factor) -> float:
    """Share of routed slots past capacity, which the exact repair served:
    what ``grouped_ep`` reports, or for ``pallas`` (which reports 0, being
    exact) counted from leaf_gemm's capacity; 0 for uncapped paths."""
    if backend != "pallas" or idx.shape[0] <= 32:    # fused_fff: uncapped
        return float(overflow)
    from repro.core import api
    from repro.kernels.leaf_gemm import ops as gemm_ops
    cf = (api.DEFAULT_CAPACITY_INFER if capacity_factor is None
          else capacity_factor)
    cap = gemm_ops.group_capacity(idx.shape[0], scfg.num_leaves, cf)
    over = sum(np.maximum(np.bincount(col, minlength=scfg.num_leaves) - cap,
                          0).sum() for col in idx.T)
    return float(over / idx.size)


def site_errors(y, idx, y_ref, idx_ref) -> tuple:
    """(per-token leaf agreement mask, max relative error on the tokens whose
    leaves agree)."""
    agree = (idx == idx_ref).all(axis=1)
    rel = float(np.abs(y - y_ref)[agree].max() / np.abs(y_ref[agree]).max())
    return agree, rel


def compare_site(site, scfg, backend: str, x, capacity_factor=None,
                 trace_ctx=None, repair: bool = False) -> dict:
    """One FFF site through ``backend`` vs the float32 reference on inputs
    ``x``; checks leaf agreement and the output error against the module
    tolerances, and with ``repair`` that the backend's overflow repair
    really served tokens."""
    ctx = trace_ctx or contextlib.nullcontext
    with ctx():
        out = site_pair(scfg, backend, capacity_factor)(site, x)
    y, idx, ovf, y_ref, idx_ref = (np.asarray(a, np.float32) if i in (0, 3)
                                   else np.asarray(a)
                                   for i, a in enumerate(out))
    agree, rel = site_errors(y, idx, y_ref, idx_ref)
    served = overflow_share(backend, scfg, idx, ovf, capacity_factor)
    cf = ("" if capacity_factor is None
          else f", capacity factor {capacity_factor}")
    print(f"compare {backend} vs reference at {tuple(x.shape)}{cf}: leaves "
          f"agree on {agree.mean():.4f} of tokens (need >= {LEAF_AGREEMENT}),"
          f" max rel err {rel:.3e} (need <= {SITE_REL_TOL}), overflow "
          f"repaired {served:.4f} of slots, finite "
          f"{bool(np.isfinite(y).all())}")
    check(bool(np.isfinite(y).all()), f"{backend}: non-finite outputs")
    check(agree.mean() >= LEAF_AGREEMENT,
          f"{backend}: leaves agree on {agree.mean():.4f} of tokens")
    check(rel <= SITE_REL_TOL, f"{backend}: max rel err {rel:.3e}")
    if repair:
        check(served > 0, f"{backend}: the repair comparison overflowed "
                          f"nothing")
    return {"backend": backend, "shape": tuple(x.shape),
            "leaf_agreement": float(agree.mean()), "rel_err": rel,
            "repaired": served}


def compare_paths(site, scfg, backends: tuple, *, slots: int,
                  compare_tokens: int, repair_tokens: int, seed: int,
                  trace_ctx=None) -> list:
    """The site comparisons of a run whose resolver picked ``backends``
    (decode, chunk): the decode path at ``(slots, 1, D)`` and the chunk
    path on a ``compare_tokens`` prefill slab, at the run's capacity
    factor; then the chunk path on a skewed ``repair_tokens`` slab at
    ``REPAIR_CF``, where its exact overflow repair must fire."""
    D, dt = scfg.dim_in, scfg.param_dtype
    kw = dict(trace_ctx=trace_ctx)
    return [
        compare_site(site, scfg, backends[0], slab((slots, 1, D), seed + 1,
                                                   dt), **kw),
        compare_site(site, scfg, backends[1],
                     slab((1, compare_tokens, D), seed + 2, dt), **kw),
        compare_site(site, scfg, backends[1],
                     slab((1, repair_tokens, D), seed + 3, dt, skew=True),
                     capacity_factor=REPAIR_CF, repair=True, **kw)]


def serve_and_compare(cfg, params, *, slots: int = 8, n_requests: int = 16,
                      prompt_lens: tuple = (128, 1024), gen: int = 32,
                      chunk: int = 256, compare_tokens: int = 64,
                      repair_tokens: int = 256, seed: int = 0,
                      expect: tuple | None = None) -> dict:
    """The one-chip smoke: serve, then compare each FFF kernel path the run
    used against the float32 reference (``compare_paths``)."""
    run = serve(cfg, params, slots=slots, n_requests=n_requests,
                prompt_lens=prompt_lens, gen=gen, chunk=chunk, seed=seed,
                expect=expect)
    scfg, site = fff_site(params, cfg)
    run["compare"] = compare_paths(site, scfg, run["backends"], slots=slots,
                                   compare_tokens=compare_tokens,
                                   repair_tokens=repair_tokens, seed=seed)
    return run


def shard_bytes(tree, devices) -> list:
    """Bytes of ``tree``'s shards on each device."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    return [sum(s.data.nbytes for a in leaves for s in a.addressable_shards
                if s.device == d) for d in devices]


def bytes_in_use(devices) -> list:
    """``memory_stats()["bytes_in_use"]`` per device (0 where the backend
    keeps no statistics)."""
    return [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]


def gb(xs) -> list:
    return [round(x / 1e9, 3) for x in xs]


def serve_sharded_and_compare(cfg, *, model_parallel: int = 4,
                              slots: int = 8, n_requests: int = 8,
                              prompt_lens: tuple = (64, 256), gen: int = 8,
                              chunk: int = 128, compare_tokens: int = 64,
                              repair_tokens: int = 256, depth_tokens: int = 64,
                              seed: int = 0, expect: tuple | None = None
                              ) -> dict:
    """The four-chip smoke: sharded init, expert-parallel serving, spread
    check of the weights and the KV pool (their shards, and each device's
    ``memory_stats``), then the sharded dispatch against the reference
    backend, which does not use it: the site comparisons of
    ``compare_paths`` on the sharded weights, and every layer of the whole
    depth on the activations the model computes for a ``depth_tokens``
    prompt (``compare_depth``)."""
    import jax
    import jax.numpy as jnp
    from repro.launch import mesh as mesh_lib
    from repro.models import lm

    mesh, trace_ctx = mesh_lib.serving_context(model_parallel)
    devices = list(mesh.devices.flat)

    def check_spread(what, tree, before):
        own = shard_bytes(tree, devices)
        grew = [b - a for a, b in zip(before, bytes_in_use(devices))]
        total = sum(own)
        print(f"{what}: {total / 1e9:.3f} GB over mesh {dict(mesh.shape)}; "
              f"shards per device GB {gb(own)}; memory_stats bytes_in_use "
              f"grew per device GB {gb(grew)}")
        share = total / len(devices)
        check(all(0.8 * share <= v <= 1.25 * share for v in own),
              f"{what} is not spread over the devices: {own}")
        if any(bytes_in_use(devices)):
            check(all(g >= 0.8 * v for g, v in zip(grew, own)),
                  f"{what}: device memory grew {grew}, shards hold {own}")

    b0 = bytes_in_use(devices)
    params = jax.block_until_ready(lm.init_params(cfg, seed, mesh))
    check_spread("weights", params, b0)

    b1 = bytes_in_use(devices)
    run = serve(cfg, params, slots=slots, n_requests=n_requests,
                prompt_lens=prompt_lens, gen=gen, chunk=chunk, seed=seed,
                mesh=mesh, trace_ctx=trace_ctx, expect=expect)
    check_spread("kv pool", run["engine"].caches, b1)
    print(f"bytes in use per device after serving GB "
          f"{gb(bytes_in_use(devices))}")
    del run["engine"]                  # its pool is not needed any more

    scfg, site = fff_site(params, cfg)
    run["compare"] = compare_paths(site, scfg, run["backends"], slots=slots,
                                   compare_tokens=compare_tokens,
                                   repair_tokens=repair_tokens, seed=seed,
                                   trace_ctx=trace_ctx)
    prompt = jnp.asarray(make_requests(cfg, 1, (depth_tokens,) * 2, 1,
                                       seed + 4)[0].prompt)[None]
    run["depth"] = compare_depth(cfg, params, run["backends"][1], prompt,
                                 trace_ctx)
    compare_free_running(cfg, params, run["backends"][1], prompt, trace_ctx)
    return run


def parting_logits(nodes, x, idx, idx_ref, depth: int) -> list:
    """For each token whose two leaves differ: |node logit| (float64, on
    the host, from the site's ``node_*`` weights) at the node where the two
    paths part, and the median |node logit| of that node over all
    tokens."""
    w1 = np.asarray(nodes["node_w1"][0, :, :, 0], np.float64)     # (N, D)
    logits = ((np.asarray(x, np.float64) @ w1.T
               + np.asarray(nodes["node_b1"][0, :, 0], np.float64))
              * np.asarray(nodes["node_w2"][0, :, 0], np.float64)
              + np.asarray(nodes["node_b2"][0], np.float64))       # (T, N)
    out = []
    for t in np.nonzero(idx[:, 0] != idx_ref[:, 0])[0]:
        a, r = int(idx[t, 0]), int(idx_ref[t, 0])
        m = depth - (a ^ r).bit_length()          # first level that differs
        node = 2 ** m - 1 + (r >> (depth - m))
        out.append((float(abs(logits[t, node])),
                    float(np.median(np.abs(logits[:, node])))))
    return out


def compare_depth(cfg, params, backend: str, prompt, trace_ctx) -> dict:
    """Every FFF site of the whole depth on the activations the model
    computes for ``prompt``: the stack runs layer by layer in eval mode
    with each site through ``backend``, and each site's input also goes
    through the float32 reference.  Both paths see the same input at every
    layer, so a token that takes another leaf at one layer cannot carry
    the difference into the next.  Checks the leaf agreement over all
    (token, layer) slots and each layer's output error on the tokens whose
    leaves agree; prints the share of slots the backend's overflow repair
    served and, for each token that took another leaf, the |node logit|
    where the paths part beside that node's median."""
    import jax
    from repro.models import lm
    from repro.nn import norms, transformer

    spec = cfg.period[0]
    mixer_only = dataclasses.replace(
        spec, ffn=dataclasses.replace(spec.ffn, kind="none"))
    scfg, _ = fff_site(params, cfg)
    pair = site_pair(scfg, backend)

    def at(tree, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            tree)

    @jax.jit
    def mix(stack, i, x):
        """The block's attention half, then the FFN's pre-norm."""
        lp = at(stack, i)
        xm, _, _ = transformer.block_forward(lp, cfg, mixer_only, x,
                                             mode="eval")
        return xm, norms.norm_apply(cfg.norm, lp["norm2"], xm)

    site_at = jax.jit(lambda stack, i: at(stack, i)["ffn"])
    stack = params["stack"][0]
    slots = errs = 0
    worst, repaired, parted = 0.0, 0.0, []
    with trace_ctx():
        x = jax.jit(lambda p, t: lm._embed_inputs(p, cfg, {"tokens": t}))(
            params, prompt)
        for i in range(cfg.n_layers):
            xm, h = mix(stack, i, x)
            site = site_at(stack, i)
            y, idx, ovf, y_ref, idx_ref = pair(site, h)
            x = xm + y.reshape(xm.shape).astype(xm.dtype)
            y, y_ref = (np.asarray(a, np.float32) for a in (y, y_ref))
            idx, idx_ref = np.asarray(idx), np.asarray(idx_ref)
            agree, rel = site_errors(y, idx, y_ref, idx_ref)
            slots += agree.size
            errs += int((~agree).sum())
            repaired += float(ovf) * agree.size
            worst = max(worst, rel)
            check(bool(np.isfinite(y).all()), f"layer {i}: non-finite")
            check(rel <= SITE_REL_TOL, f"layer {i}: max rel err {rel:.3e}")
            if not agree.all():
                hf = np.asarray(h, np.float32).reshape(agree.size, -1)
                nodes = {k: v for k, v in site.items()
                         if k.startswith("node_")}
                parted += [(i,) + p for p in parting_logits(
                    jax.device_get(nodes), hf, idx, idx_ref, scfg.depth)]
    share = 1 - errs / slots
    print(f"compare {backend} vs reference at every FFF site, {cfg.n_layers} "
          f"layers on the model's own activations ({prompt.shape[1]}-token "
          f"prompt): leaves agree on {share:.4f} of {slots} (token, layer) "
          f"slots (need >= {LEAF_AGREEMENT}), worst layer max rel err "
          f"{worst:.3e} (need <= {SITE_REL_TOL}), overflow repaired "
          f"{repaired / slots:.4f} of slots")
    for layer, a, med in parted:
        print(f"  layer {layer}: paths part at |node logit| {a:.3e} "
              f"(median there {med:.3e})")
    check(share >= LEAF_AGREEMENT,
          f"leaves agree on {share:.4f} of (token, layer) slots")
    return {"leaf_agreement": share, "worst_rel_err": worst,
            "repaired": repaired / slots, "parted": parted}


def nudge_embeddings(params):
    """``params`` with every nonzero token-embedding entry moved one unit in
    the last place of its dtype (its bit pattern +-1, the direction taken
    from its lowest bit): a change of rounding size, at the input only."""
    import jax
    import jax.numpy as jnp

    def nudge(tok):
        ity = {2: jnp.int16, 4: jnp.int32}[tok.dtype.itemsize]
        bits = jax.lax.bitcast_convert_type(tok, ity)
        moved = jax.lax.bitcast_convert_type(bits + 1 - 2 * (bits & 1),
                                             tok.dtype)
        return jnp.where(tok == 0, tok, moved)

    embed = dict(params["embed"], tok=jax.jit(nudge)(params["embed"]["tok"]))
    return dict(params, embed=embed)


def compare_free_running(cfg, params, backend: str, prompt, trace_ctx
                         ) -> None:
    """Prints, without checking, the next-token logits of the whole model
    run freely through ``backend`` and through the reference (both in the
    model's dtype), and how many routed (token, layer) slots their leaf
    histograms disagree on.  Unlike ``compare_depth`` each run feeds its
    own activations forward, so rounding differences reach later layers
    and may send a token down another leaf there.  A control beside it:
    the reference against itself with the embeddings moved one unit in the
    last place (``nudge_embeddings``) — no sharded dispatch, only a
    rounding-sized change — shows how far rounding alone carries."""
    import jax
    import jax.numpy as jnp
    from repro.core import api
    from repro.models import lm

    T = prompt.shape[1]
    runs = {backend: (backend, params), "reference": ("reference", params),
            "nudged": ("reference", nudge_embeddings(params))}
    prefill, out = {}, {}
    for name, (b, p) in runs.items():
        fn = prefill.setdefault(b, jax.jit(
            lambda p, t, c: lm.prefill_padded(
                p, cfg, {"tokens": t}, c, jnp.full((1,), T, jnp.int32))))
        with trace_ctx(), api.overrides(backend=b, mode="infer"), \
                api.collect_routing():
            logits, _, stats = fn(p, prompt, lm.init_caches(cfg, 1, T + 1))
        out[name] = (np.asarray(logits, np.float32),
                     sum(np.asarray(s.leaf_counts, np.float64)
                         for s in stats))

    def drift(x, ref):
        (a, ha), (r, hr) = out[x], out[ref]
        rel = float(np.abs(a - r).max() / np.abs(r).max())
        return (f"max rel err {rel:.3e}, argmax {int(a.argmax())} vs "
                f"{int(r.argmax())}; leaf "
                f"histograms differ on {int(round(np.abs(ha - hr).sum() / 2))}"
                f" of {int(hr.sum())} routed slots")

    print(f"free-running next-token logits {backend} vs reference "
          f"({cfg.n_layers} layers, not checked): "
          f"{drift(backend, 'reference')}")
    print(f"control, reference with every embedding moved one ulp vs "
          f"reference ({cfg.n_layers} layers, not checked): "
          f"{drift('nudged', 'reference')}")


class CompileClock:
    """Sums the backend compile seconds JAX reports (cache hits compile
    nothing, so a warm compile cache shows up as fewer seconds)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded path (48 layers, "
                         "(1, 4) mesh, grouped_ep) and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repository's src/repro is not beside "
              f"{os.path.abspath(__file__)}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro import utils
    cache = utils.enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    n_dev = 4 if args.four_chips else 1
    if len(jax.devices()) < n_dev:
        print(f"chip_smoke: needs {n_dev} TPU chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    clock = CompileClock()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {cache}")

    cfg = smoke_config(48 if args.four_chips else ONE_CHIP_LAYERS)
    print(f"model: {cfg.arch_id} FFF, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} of "
          f"{cfg.resolved_head_dim}, FFF {2 ** cfg.period[0].ffn.fff_depth} x "
          f"{cfg.period[0].ffn.fff_leaf_width} {cfg.period[0].ffn.activation} "
          f"leaves, vocab {cfg.vocab_size}, "
          f"{jax.numpy.dtype(cfg.param_dtype).name}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            serve_sharded_and_compare(cfg, seed=args.seed,
                                      expect=("grouped_ep", "grouped_ep"))
        else:
            from repro.models import lm
            params = jax.block_until_ready(lm.init_params(cfg, args.seed))
            print(f"weights: {utils.tree_bytes(params) / 1e9:.3f} GB on "
                  f"{dev.device_kind}")
            serve_and_compare(cfg, params, seed=args.seed,
                              expect=("pallas_decode", "pallas"))
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(f"compile seconds: {clock.seconds:.3f} (backend compiles this "
          f"process); total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
