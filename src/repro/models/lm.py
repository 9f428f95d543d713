"""Causal LM wrapper: init / train loss / prefill / decode for every assigned
architecture, including encoder-decoder (whisper) and stub-frontend (vlm,
audio) variants.

The three entry points lowered by the dry-run:
  * ``train_step``  — loss + grads + optimizer update (shape: train_4k)
  * ``prefill``     — build KV/state caches over a prefix (prefill_32k)
  * ``decode_step`` — one new token against the caches (decode_32k, long_500k)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import act
from repro.nn import embeddings, norms, rope as rope_lib, transformer

Params = dict


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {
        "embed": embeddings.embed_init(ks[0], cfg.vocab_size, cfg.d_model,
                                       tie=cfg.tie_embeddings,
                                       param_dtype=cfg.param_dtype),
        "stack": transformer.stack_init(ks[1], cfg, causal=True),
        "final_norm": norms.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype),
    }
    if cfg.pos_emb == "learned":
        p["pos"] = embeddings.learned_pos_init(ks[2], cfg.max_seq_len,
                                               cfg.d_model, cfg.param_dtype)
    if cfg.frontend != "none" and cfg.encoder is None:
        p["frontend"] = embeddings.frontend_init(ks[3], cfg.frontend,
                                                 cfg.d_model, cfg.param_dtype)
    if cfg.encoder is not None:
        p["enc_frontend"] = embeddings.frontend_init(ks[3], cfg.frontend,
                                                     cfg.d_model, cfg.param_dtype)
        p["enc_stack"] = transformer.stack_init(
            ks[4], cfg, causal=False, period=cfg.encoder.period,
            n_layers=cfg.encoder.n_layers)
        p["enc_norm"] = norms.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
    return p


def init_params(cfg: ModelConfig, seed: int, mesh=None) -> Params:
    """``init`` as one jitted program — how every entry point builds its
    weights.  Under a serving mesh every parameter is born on its shard
    (``out_shardings`` from ``sharding.param_specs``), so a model too large
    for one chip never lands whole on the first one."""
    key = jax.random.PRNGKey(seed)
    out_shardings = None
    if mesh is not None:
        from repro.distributed import sharding
        shapes = jax.eval_shape(lambda k: init(k, cfg), key)
        out_shardings = sharding.param_shardings(shapes, mesh, fsdp=False)
    return jax.jit(lambda k: init(k, cfg), out_shardings=out_shardings)(key)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def encode(params: Params, cfg: ModelConfig, enc_embeds: jax.Array
           ) -> jax.Array:
    """Encoder over precomputed frame/patch embeddings (B, S_enc, D)."""
    x = embeddings.frontend(params["enc_frontend"], enc_embeds, cfg.accum_dtype)
    x = x + rope_lib.sinusoidal_embedding(x.shape[1], cfg.d_model).astype(x.dtype)
    x = act.shard(x, act.ACT_BSD)
    x, _, _ = transformer.stack_forward(
        params["enc_stack"], cfg, x, mode="train", causal=False,
        period=cfg.encoder.period)
    return norms.norm_apply(cfg.norm, params["enc_norm"], x)


def _embed_inputs(params: Params, cfg: ModelConfig, batch: dict,
                  pos_offset: int | jax.Array = 0) -> jax.Array:
    if cfg.frontend != "none" and cfg.encoder is None and "embeds" in batch:
        x = embeddings.frontend(params["frontend"], batch["embeds"],
                                cfg.accum_dtype)
    else:
        x = embeddings.embed(params["embed"], batch["tokens"], cfg.accum_dtype)
    if cfg.pos_emb == "learned":
        x = embeddings.learned_pos(params["pos"], x, pos_offset)
    elif cfg.pos_emb == "sinusoidal":
        x = x + rope_lib.sinusoidal_embedding(
            x.shape[1] + 0, cfg.d_model).astype(x.dtype)
    return act.shard(x, act.ACT_BSD)


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        x = norms.norm_apply(cfg.norm, params["final_norm"], x)
        lg = embeddings.logits(params["embed"], x)
        return act.shard(lg, act.LOGITS_BSV)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore_index: int = -1) -> tuple[jax.Array, jax.Array]:
    """Mean CE over valid positions; returns (loss, accuracy)."""
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(valid.sum(), 1)
    loss = -(ll * valid).sum() / denom
    acc = ((logits.argmax(-1) == labels) & valid).sum() / denom
    return loss, acc


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def loss_fn(params: Params, cfg: ModelConfig, batch: dict,
            rng: Optional[jax.Array] = None) -> tuple[jax.Array, dict]:
    """Training loss: CE + hardening (FFF) + load-balancing (FFF leaf usage,
    DESIGN.md §14) + balancing (MoE) aux terms."""
    enc_out = None
    if cfg.encoder is not None:
        enc_out = encode(params, cfg, batch["enc_embeds"])
    x = _embed_inputs(params, cfg, batch)
    x, _, aux = transformer.stack_forward(params["stack"], cfg, x,
                                          mode="train", rng=rng,
                                          enc_out=enc_out)
    logits = _head(params, cfg, x)
    ce, acc = cross_entropy(logits, batch["labels"])
    loss = ce + aux["hardening"] + aux["moe_aux"] + aux["balance"]
    metrics = {"loss": loss, "ce": ce, "accuracy": acc,
               "hardening": aux["hardening"], "moe_aux": aux["moe_aux"],
               "balance": aux["balance"]}
    return loss, metrics


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=None, *, page_size: int = 0, num_pages: int = 0,
                prealloc: bool = True) -> list[dict]:
    enc_len = cfg.encoder.seq_len if cfg.encoder is not None else 0
    return transformer.init_caches(cfg, batch, max_len, enc_len=enc_len,
                                   dtype=dtype, page_size=page_size,
                                   num_pages=num_pages, prealloc=prealloc)


def prefill(params: Params, cfg: ModelConfig, batch: dict,
            caches: list[dict]) -> tuple[jax.Array, list[dict]]:
    """Run the prefix, fill caches, return last-position logits (B, V)."""
    enc_out = None
    if cfg.encoder is not None:
        enc_out = encode(params, cfg, batch["enc_embeds"])
    x = _embed_inputs(params, cfg, batch)
    x, caches, _ = transformer.stack_forward(params["stack"], cfg, x,
                                             mode="prefill", caches=caches,
                                             enc_out=enc_out)
    logits = _head(params, cfg, x[:, -1:, :])
    return logits[:, 0], caches


def prefill_padded(params: Params, cfg: ModelConfig, batch: dict,
                   caches: list[dict], true_len: jax.Array
                   ) -> tuple[jax.Array, list[dict], Any]:
    """Prefill with RIGHT-PADDED prompts (the serving engine's fixed-shape
    contract, DESIGN.md §9).

    ``batch["tokens"]`` is (B, S_pad); ``true_len`` (B,) int32 gives each
    row's real prompt length.  Causal attention makes positions < true_len
    independent of the pad garbage to their right; the garbage K/V rows land
    in the cache but are masked out by setting each row's cache length to
    ``true_len`` (and are progressively overwritten by decode appends).
    Returns (logits at each row's last real token (B, V), caches, routing
    stats — None unless an ``api.collect_routing`` tap is active).

    Only valid for attention-mixer stacks: recurrent mixers (mamba/xlstm)
    fold pad tokens into their state.  Callers enforce that
    (``serving.engine`` checks the period at construction).
    """
    x = _embed_inputs(params, cfg, batch)
    x, caches, aux = transformer.stack_forward(params["stack"], cfg, x,
                                               mode="prefill", caches=caches)
    last = jnp.take_along_axis(
        x, (true_len - 1)[:, None, None].astype(jnp.int32), axis=1)  # (B,1,D)
    logits = _head(params, cfg, last)
    caches = set_cache_lengths(caches, true_len)
    return logits[:, 0], caches, aux.get("routing")


def decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                caches: list[dict], pos_offset: jax.Array | int = 0,
                *, write_mask: Optional[jax.Array] = None,
                token_valid: Optional[jax.Array] = None,
                with_stats: bool = False):
    """One serve step: token (B, 1) int32 -> logits (B, V), updated caches.

    ``pos_offset`` may be per-row (B,) for continuous batching (slots sit at
    different positions; only learned positional embeddings consume it — RoPE
    reads per-row positions off the KV cache lengths).  ``write_mask`` (B,)
    bool, optional: rows where it is False compute logits but neither write
    K/V nor advance their cache length — the engine decodes its full slot
    batch while some slots are mid-chunked-prefill (DESIGN.md §9).
    ``token_valid`` (B,) bool, optional: rows where it is False are phantom
    (free slots) — capacity-bounded FFF backends route them to the sentinel
    leaf so they never consume grouped-dispatch capacity or appear in
    routing telemetry; deliberately separate from ``write_mask`` so the
    fixed-shape KV-write contract is unaffected.  With ``with_stats=True``
    also returns the per-site routing-stats tuple from the
    ``api.collect_routing`` tap (None when no tap is active)."""
    x = _embed_inputs(params, cfg, {"tokens": token}, pos_offset=pos_offset)
    tv = token_valid[:, None] if token_valid is not None else None
    x, caches, aux = transformer.stack_forward(params["stack"], cfg, x,
                                               mode="decode", caches=caches,
                                               decode_mask=write_mask,
                                               token_valid=tv)
    logits = _head(params, cfg, x)
    if with_stats:
        return logits[:, 0], caches, aux.get("routing")
    return logits[:, 0], caches


# ---------------------------------------------------------------------------
# paged-cache surgery (continuous-batching serving, DESIGN.md §9/§11)
# ---------------------------------------------------------------------------

def set_cache_lengths(caches: list[dict], lengths: jax.Array) -> list[dict]:
    """Overwrite every attention cache's per-row filled length with
    ``lengths`` (B,) — the padded-prefill epilogue."""
    out = []
    for c in caches:
        c = dict(c)
        if "kv" in c:
            kv = c["kv"]
            c["kv"] = kv._replace(length=jnp.broadcast_to(
                lengths.astype(kv.length.dtype)[None], kv.length.shape))
        out.append(c)
    return out


def cache_admit(caches: list[dict], admit: jax.Array, tables: jax.Array,
                lengths: jax.Array, cow_src: jax.Array, cow_dst: jax.Array
                ) -> list[dict]:
    """Install admitted rows' page tables in ONE batched dispatch
    (DESIGN.md §11).

    ``admit`` (B,) bool marks rows being (re)admitted this step; their page
    tables are overwritten with ``tables`` (B, ppr) and their cache lengths
    with ``lengths`` (B,) — the shared-prefix boundary, so prefill resumes
    at the first novel token.  ``cow_src``/``cow_dst`` (B,) are page ids
    for the copy-on-write case (a prompt fully covered by shared pages must
    recompute its last token for first-token logits): the source page's K/V
    are copied into the row's private ``cow_dst`` page before the table
    swap.  Rows without a copy pass the ``num_pages`` sentinel as
    ``cow_dst`` (the scatter drops it).

    Eviction needs no dispatch at all: freeing pages is host-side refcount
    bookkeeping, and a freed row's stale device table is harmless because
    every decode/chunk write is masked to live rows."""
    out = []
    for c in caches:
        c = dict(c)
        kv = c["kv"]                       # leaves stacked (n_periods, ...)
        num_pages = kv.k.shape[1]
        src = jnp.minimum(cow_src, num_pages - 1)
        new_k = kv.k.at[:, cow_dst].set(kv.k[:, src], mode="drop")
        new_v = kv.v.at[:, cow_dst].set(kv.v[:, src], mode="drop")
        new_table = jnp.where(admit[None, :, None],
                              tables[None].astype(kv.table.dtype), kv.table)
        new_len = jnp.where(admit[None, :],
                            lengths[None].astype(kv.length.dtype), kv.length)
        c["kv"] = kv._replace(k=new_k, v=new_v, table=new_table,
                              length=new_len)
        out.append(c)
    return out


def cache_install(caches: list[dict], admit: jax.Array, tables: jax.Array,
                  lengths: jax.Array, pages: jax.Array,
                  k_rows: list[jax.Array], v_rows: list[jax.Array]
                  ) -> list[dict]:
    """Install a handed-off row — page CONTENTS plus table — in ONE batched
    dispatch (the cluster cache-handoff receive path, DESIGN.md §12).

    Same ``admit`` (B,) / ``tables`` (B, ppr) / ``lengths`` (B,) contract
    as ``cache_admit``, but the page K/V arrive over the wire instead of
    being computed here: ``pages`` (ppr,) int32 names the destination page
    ids in THIS pool (``num_pages`` sentinel for unused tail entries —
    their writes drop), and ``k_rows``/``v_rows`` align with ``caches``,
    each entry a ``(n_periods, ppr, page_size, K, hd)`` slab gathered from
    the SENDING worker's pool (``cluster/handoff.extract``; zero-padded
    past the shipped pages — fresh generation-room pages tolerate the
    overwrite, nothing reads past the installed length).

    One fixed compiled shape per engine config: the decode-worker analogue
    of the prefill side's ``admit`` dispatch."""
    out = []
    for c, kr, vr in zip(caches, k_rows, v_rows):
        c = dict(c)
        kv = c["kv"]                       # leaves stacked (n_periods, ...)
        new_k = kv.k.at[:, pages].set(kr.astype(kv.k.dtype), mode="drop")
        new_v = kv.v.at[:, pages].set(vr.astype(kv.v.dtype), mode="drop")
        new_table = jnp.where(admit[None, :, None],
                              tables[None].astype(kv.table.dtype), kv.table)
        new_len = jnp.where(admit[None, :],
                            lengths[None].astype(kv.length.dtype), kv.length)
        c["kv"] = kv._replace(k=new_k, v=new_v, table=new_table,
                              length=new_len)
        out.append(c)
    return out


def prefill_chunk(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  valid_len: jax.Array, caches: list[dict],
                  pos_offset: jax.Array, rows: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, list[dict], Any]:
    """Consume one chunk of prefill for many rows of a pooled cache at once
    (chunked prefill, DESIGN.md §9).

    ``tokens`` is a (R, C) slab — C = the engine's ``prefill_chunk`` — so
    every in-flight prefill advances in ONE dispatch.  ``rows`` (R,) int32
    names the cache row (slot) of each slab row; None means the slab covers
    every row of the caches in order (R = B).  A ``rows`` entry >= B is a
    padding row: it reads an unmapped table and length 0, writes nothing,
    and its length scatter drops — never repeat a real row's index there,
    or a stale length could overwrite the real one.  ``valid_len`` (R,)
    int32 in [0, C] is each slab row's real token count this chunk (0 = no
    prefill work; the row is in-distribution filler).  ``pos_offset`` (R,)
    is each row's absolute start position — the number of prompt tokens
    already consumed — and must equal the row's current attention-cache
    length (the caller tracks both; they advance in lockstep).

    Each row's valid tokens are appended to its cache at
    ``pos_offset[j]..`` and attend causally to the row's full history;
    pad positions and inactive rows write nothing (``chunk_into_cache``
    drops their scatter indices) and their outputs are garbage the caller
    ignores.  Rows are independent, so a row's result does not depend on
    which other rows share its slab (under an exact FFF dispatch).  Returns
    (logits (R, V) at each slab row's LAST VALID chunk position — the
    next-token logits for rows whose prompt completes this chunk — updated
    caches, routing stats per slab row).  Attention mixers only, like
    ``prefill_padded``."""
    if rows is not None:
        view = _rows_view(caches, rows)
        logits, view, aux = prefill_chunk(params, cfg, tokens, valid_len,
                                          view, pos_offset)
        return logits, _rows_merge(caches, view, rows), aux
    x = _embed_inputs(params, cfg, {"tokens": tokens}, pos_offset=pos_offset)
    x, caches, aux = transformer.stack_forward(
        params["stack"], cfg, x, mode="chunk", caches=caches,
        chunk_valid=valid_len)
    last_idx = jnp.clip(valid_len - 1, 0)[:, None, None].astype(jnp.int32)
    last = jnp.take_along_axis(x, last_idx, axis=1)               # (B, 1, D)
    logits = _head(params, cfg, last)
    return logits[:, 0], caches, aux.get("routing")


def _rows_view(caches: list[dict], rows: jax.Array) -> list[dict]:
    """The pooled caches as seen by the slab rows ``rows`` (R,): the page
    pools whole and shared, each layer's table and length gathered at
    ``rows`` (leaves stacked ``(n_periods, B, ...)``).  Rows >= B read an
    all-sentinel table and length 0."""
    out = []
    for c in caches:
        c = dict(c)
        kv = c["kv"]
        c["kv"] = kv._replace(
            table=kv.table.at[:, rows].get(mode="fill",
                                           fill_value=kv.k.shape[1]),
            length=kv.length.at[:, rows].get(mode="fill", fill_value=0))
        out.append(c)
    return out


def _rows_merge(caches: list[dict], view: list[dict], rows: jax.Array
                ) -> list[dict]:
    """Fold a ``_rows_view`` back: the view's page pools replace the
    caches', and its lengths scatter to ``rows`` (rows >= B drop)."""
    out = []
    for c, v in zip(caches, view):
        c = dict(c)
        kv, kv_rows = c["kv"], v["kv"]
        c["kv"] = kv._replace(
            k=kv_rows.k, v=kv_rows.v,
            length=kv.length.at[:, rows].set(kv_rows.length, mode="drop"))
        out.append(c)
    return out


def verify_chunk(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 valid_len: jax.Array, caches: list[dict],
                 pos_offset: jax.Array) -> tuple[jax.Array, list[dict], Any]:
    """Speculative-decoding verify step (DESIGN.md §10): the same fixed-shape
    (B, C) slab dispatch as ``prefill_chunk``, but returning the target
    model's logits at EVERY slab position — ``logits[b, j]`` is the target's
    next-token distribution after consuming ``tokens[b, :j+1]``, exactly
    what host-side rejection sampling needs to accept/reject a draft run
    ``tokens[b] = [pending, d_1 .. d_k]``.

    K/V for all C positions (the pending token plus every draft token) are
    appended optimistically; the caller rolls rejected suffixes back with
    ``set_cache_lengths`` — stale rows beyond the new length are masked by
    length and overwritten by later appends, the same mechanism as
    ``prefill_padded``.  Rows with ``valid_len == 0`` (free slots) write
    nothing, and the chunk-mode validity mask keeps their phantom tokens out
    of FFF grouped-dispatch capacity.  Attention mixers only."""
    x = _embed_inputs(params, cfg, {"tokens": tokens}, pos_offset=pos_offset)
    x, caches, aux = transformer.stack_forward(
        params["stack"], cfg, x, mode="chunk", caches=caches,
        chunk_valid=valid_len)
    logits = _head(params, cfg, x)                              # (B, C, V)
    return logits, caches, aux.get("routing")


def generate(params: Params, cfg: ModelConfig, prompt: jax.Array,
             steps: int, max_len: int, rng: Optional[jax.Array] = None,
             temperature: float = 0.0,
             eos_id: Optional[int] = None, caches=None) -> jax.Array:
    """Greedy/temperature sampling loop (host-driven example path).

    With ``eos_id`` set, rows that emit it stop: their subsequent tokens are
    pinned to ``eos_id`` (pad), and the loop exits once every row has
    finished — so the result may have fewer than ``steps`` generated columns.
    ``caches`` substitutes a caller-built cache set (e.g. a preallocated
    *paged* one from ``init_caches(..., page_size=N)``) for the default
    contiguous allocation; it must be fresh (zero lengths) and sized
    ``(B, max_len)``.
    """
    B = prompt.shape[0]
    if caches is None:
        caches = init_caches(cfg, B, max_len)
    logits, caches = prefill(params, cfg, {"tokens": prompt}, caches)
    out = [prompt]
    tok = logits.argmax(-1)[:, None].astype(jnp.int32)
    done = jnp.zeros((B,), bool)
    for i in range(steps):
        out.append(tok)
        if eos_id is not None:
            done = done | (tok[:, 0] == eos_id)
            if bool(done.all()):
                break
        logits, caches = decode_step(params, cfg, tok, caches,
                                     pos_offset=prompt.shape[1] + i)
        if temperature > 0.0 and rng is not None:
            rng, sub = jax.random.split(rng)
            tok = jax.random.categorical(sub, logits / temperature)[:, None]
            tok = tok.astype(jnp.int32)
        else:
            tok = logits.argmax(-1)[:, None].astype(jnp.int32)
        if eos_id is not None:
            tok = jnp.where(done[:, None], jnp.int32(eos_id), tok)
    return jnp.concatenate(out, axis=1)
