"""Speculative decoding for the continuous-batching engine (DESIGN.md §10).

The paper's FORWARD_I makes per-token FLOPs nearly free (log-depth leaf
path), so serving throughput is bounded by the one-token-per-step decode
loop — dispatch overhead plus one full weight pass per emitted token.
Speculative decoding (Leviathan et al., 2023) breaks that bound: a cheap
DRAFT model proposes ``k`` tokens autoregressively, the TARGET model scores
all ``k + 1`` positions in ONE slab dispatch, and host-side rejection
sampling keeps the longest prefix the target agrees with — the output
distribution is exactly the target's, for any draft.

Engine integration (``serving/engine.py``) keeps the fixed-shape contract:

* ``draft_rollout`` — the whole draft phase as one traced computation: a
  ``lax.scan`` of ``k + 1`` draft decode steps over the pooled draft caches
  (the extra step appends the last draft token's KV so an all-accepted
  round leaves the draft cache aligned).  It also applies both cache
  trees' length rollback from the PREVIOUS round's rejection — lengths are
  metadata, so the truncate rides along for free instead of costing its
  own dispatch.
* ``lm.verify_chunk`` — the chunk-slab machinery scores
  ``(num_slots, k + 1)`` at every position, writing draft KV
  optimistically.
* ``spec_round`` — rollout + verify fused into ONE dispatch per round
  (verify reads the drafts on device; only rejection needs the host), so a
  round costs a single dispatch overhead however many tokens it emits.
* ``rejection_sample`` — host-side accept/reject per row, exact.

The FFF-specific edge: the draft's leaf routing path is a free PRIOR on the
verify step's leaf occupancy.  The rollout aggregates per-slot draft leaf
histograms (``api.RoutingStats``) and the engine folds them into the
occupancy EWMA the ``leaf_aware`` / ``weighted_leaf_aware`` schedulers
read, so verify slabs are composed to minimize predicted grouped-dispatch
overflow before the target ever routes a token.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.models import lm

Params = dict


# ---------------------------------------------------------------------------
# draft-model construction
# ---------------------------------------------------------------------------

def self_draft_config(cfg, n_periods: int = 1):
    """The ``self:N`` draft config: the target architecture truncated to its
    first ``n_periods`` period repetitions (an early-exit draft).  Shares
    vocabulary, d_model and period structure with the target by
    construction, so sliced target params fit it directly."""
    if not (1 <= n_periods <= cfg.n_periods):
        raise ValueError(f"self-draft n_periods {n_periods} out of range "
                         f"[1, {cfg.n_periods}]")
    return dataclasses.replace(cfg, n_layers=n_periods * len(cfg.period))


def slice_draft_params(params: Params, cfg, n_periods: int = 1) -> Params:
    """Self-speculative draft parameters: the first ``n_periods`` entries of
    every stacked period axis, SHARING embed / positional / final-norm
    leaves with the target (no copies — the draft is a view of the target's
    own early layers).  An early-exit draft needs no training to correlate
    with the target, which is what makes acceptance non-trivial out of the
    box; a well-calibrated target makes it high."""
    if not (1 <= n_periods <= cfg.n_periods):
        raise ValueError(f"self-draft n_periods {n_periods} out of range "
                         f"[1, {cfg.n_periods}]")
    out = dict(params)
    out["stack"] = [jax.tree_util.tree_map(lambda a: a[:n_periods], p)
                    for p in params["stack"]]
    return out


def build_draft(spec: Optional[str], params: Params, cfg,
                seed: int = 0) -> Tuple[Params, object]:
    """Resolve a draft-model spec string into ``(draft_params, draft_cfg)``.

    * ``None`` / ``"self"`` / ``"self:N"`` — self-speculative: the target's
      own first N periods (default 1), params shared (see
      ``slice_draft_params``).
    * a registry arch id (``configs.registry.ARCH_IDS``) — an independent
      randomly-initialized draft in the *reduced* shape.  Near-zero
      acceptance untrained (correctness testing / a slot for real trained
      drafts), and its KV pool is still slot-indexed alongside the
      target's.  Must share the target's vocabulary.
    """
    spec = spec or "self"
    if spec == "self" or spec.startswith("self:"):
        n = int(spec.split(":", 1)[1]) if ":" in spec else 1
        return slice_draft_params(params, cfg, n), self_draft_config(cfg, n)
    from repro.configs.registry import get_config
    dcfg = get_config(spec, ffn="fff").reduced(
        d_model=cfg.d_model, n_heads=cfg.n_heads, vocab=cfg.vocab_size,
        seq=cfg.max_seq_len)
    if dcfg.vocab_size != cfg.vocab_size:   # pragma: no cover - reduced() sets it
        raise ValueError(f"draft {spec!r}: vocab {dcfg.vocab_size} != "
                         f"target vocab {cfg.vocab_size}")
    if any(b.mixer != "attn" for b in dcfg.period):
        raise ValueError(f"draft {spec!r}: the engine's pooled-cache "
                         f"contract needs attention mixers in the draft too")
    return lm.init(jax.random.PRNGKey(seed), dcfg), dcfg


# ---------------------------------------------------------------------------
# the fused draft phase (one dispatch per spec round)
# ---------------------------------------------------------------------------

def _agg_stats(stats):
    """Collapse scan-stacked per-site RoutingStats (leading k+1 step axis)
    into one per-site aggregate: summed leaf counts / slots, slot-weighted
    overflow, distinct leaves per step (the mean over the steps)."""
    if stats is None:
        return None
    out = []
    for s in stats:
        if s is None:
            out.append(None)
            continue
        slots = s.slots.sum()
        out.append(api.RoutingStats(
            leaf_counts=s.leaf_counts.sum(0),
            overflow=(s.overflow * s.slots).sum() / jnp.maximum(slots, 1.0),
            slots=slots, distinct=s.distinct.mean(0)))
    return tuple(out)


def draft_rollout(draft_params: Params, dcfg, tok0: jax.Array,
                  target_caches: list, draft_caches: list,
                  target_len: jax.Array, draft_len: jax.Array,
                  pos0: jax.Array, write_masks: jax.Array,
                  live: jax.Array, temps: jax.Array, key: jax.Array,
                  draft_backend: Optional[str] = None):
    """The whole draft phase in one traced computation (jitted by the
    engine; fixed shapes — compiles once).

    Steps, in order:
    1. Roll BOTH cache trees back to the host-tracked lengths
       (``set_cache_lengths`` — the previous verify appended ``k + 1``
       positions optimistically; rejected suffixes die here, one round
       late, without a dedicated truncate dispatch).
    2. ``lax.scan`` ``k + 1`` draft decode steps: step ``j`` feeds the
       current token at per-row position ``pos0 + j``, appends its KV to
       the draft cache (per-step ``write_masks[j]`` guards the ``max_len``
       edge), and samples the next draft token — on-device gumbel-argmax
       for ``temps > 0`` rows, argmax otherwise, so the proposal
       distribution is exactly ``softmax(q_logits / temp)`` and host-side
       rejection can use the returned logits verbatim.

    Args:
        tok0:        (S, 1) int32 — each live row's pending token.
        target_len:  (S,) int32 — authoritative target cache lengths.
        draft_len:   (S,) int32 — authoritative draft cache lengths.
        pos0:        (S,) int32 — absolute position of ``tok0``.
        write_masks: (k+1, S) bool — per-step KV-append guards.
        live:        (S,) bool — FFF validity mask (free slots are routed
                     to the sentinel leaf, DESIGN.md §9).
        temps:       (S,) float32 — per-row sampling temperature.
        key:         PRNG key for on-device draft sampling.
        draft_backend: optional FFF backend name steered (``use_backend``)
                     around the scanned draft steps only — the engine
                     passes ``"pallas_decode"`` so the rollout's seq-len-1
                     decode steps trace onto the fused megakernel
                     (DESIGN.md §13) while the verify slab keeps its own
                     resolution.  None = no steer.

    Returns ``(drafts (k, S), q_logits (k+1, S, V), target_caches,
    draft_caches, stats)`` — ``drafts[j]`` was sampled from
    ``softmax(q_logits[j] / temps)``; ``stats`` is the step-aggregated
    per-site RoutingStats tuple (the scheduler's verify-occupancy prior).
    """
    target_caches = lm.set_cache_lengths(target_caches, target_len)
    draft_caches = lm.set_cache_lengths(draft_caches, draft_len)
    k_plus_1 = write_masks.shape[0]
    t_safe = jnp.maximum(temps, 1e-6)[:, None]

    def step(carry, xs):
        tok, caches = carry
        j, wm, sub = xs
        logits, caches, stats = lm.decode_step(
            draft_params, dcfg, tok, caches, pos_offset=pos0 + j,
            write_mask=wm, token_valid=live, with_stats=True)
        greedy = logits.argmax(-1)
        g = jax.random.gumbel(sub, logits.shape, dtype=jnp.float32)
        sampled = (logits.astype(jnp.float32) / t_safe + g).argmax(-1)
        nxt = jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)
        return (nxt[:, None], caches), (nxt, logits, stats)

    xs = (jnp.arange(k_plus_1), write_masks,
          jax.random.split(key, k_plus_1))
    steer = (api.overrides(backend=draft_backend, mode="infer")
             if draft_backend is not None else contextlib.nullcontext())
    with steer:      # trace-time: applies to the scanned step body only
        (_, draft_caches), (sampled, q_logits, stats) = jax.lax.scan(
            step, (tok0, draft_caches), xs)
    # the last step exists only to append d_k's KV; its sample is unused
    return (sampled[:-1], q_logits, target_caches, draft_caches,
            _agg_stats(stats))


def spec_round(params: Params, cfg, draft_params: Params, dcfg,
               tok0: jax.Array, caches: list, draft_caches: list,
               target_len: jax.Array, draft_len: jax.Array,
               pos0: jax.Array, write_masks: jax.Array, verify_len: jax.Array,
               live: jax.Array, temps: jax.Array, key: jax.Array,
               verify_cf: Optional[float] = None,
               draft_backend: Optional[str] = None):
    """One whole speculative round in a single traced computation: the draft
    rollout followed immediately by the target's batched verify over the
    ``(num_slots, k + 1)`` slab ``[pending, d_1 .. d_k]``.

    The verify consumes the drafts ON DEVICE (host rejection only needs the
    resulting logits), so fusing it into the rollout's jit costs nothing and
    halves the per-round dispatch overhead — the term that decides whether
    speculation wins at all in the small-model regime the paper's log-depth
    FORWARD_I creates (see benchmarks/serving_spec.py).

    ``verify_len`` (S,) int32 in [0, k + 1]: tokens of the slab the target
    actually scores/appends per row (0 = free slot; rows near the cache edge
    clip, mirroring ``write_masks``).  ``verify_cf``: capacity factor for
    the verify dispatch only (``api.overrides(capacity_factor=...)``, which
    nests inside and wins over the engine's own override) — the engine
    passes the decode capacity factor scaled by ``k + 1`` so each verify
    token sees the per-leaf capacity it would have seen in plain decode
    (None = backend default, for capacity-free backends).  Returns
    ``(drafts (k, S), q_logits (k+1, S, V), p_logits (S, k+1, V), caches,
    draft_caches, draft_stats, verify_stats)``.
    """
    ctx = (api.overrides(capacity_factor=verify_cf) if verify_cf is not None
           else contextlib.nullcontext())
    with ctx:
        # the rollout runs at the scaled capacity too: draft dispatch
        # capacity only shapes the PROPOSAL distribution (rejection keeps
        # exactness for any draft), so capacity drops there are pure
        # acceptance loss — one early drop rejects the whole suffix
        drafts, q_logits, caches, draft_caches, dstats = draft_rollout(
            draft_params, dcfg, tok0, caches, draft_caches, target_len,
            draft_len, pos0, write_masks, live, temps, key,
            draft_backend=draft_backend)
        vtoks = jnp.concatenate([tok0, drafts.T], axis=1)  # (S, k+1)
        p_logits, caches, vstats = lm.verify_chunk(
            params, cfg, vtoks, verify_len, caches, pos0)
    return drafts, q_logits, p_logits, caches, draft_caches, dstats, vstats


def chunk_both(params: Params, cfg, draft_params: Params, dcfg,
               tokens: jax.Array, valid_len: jax.Array, caches: list,
               draft_caches: list, pos_offset: jax.Array,
               rows: Optional[jax.Array] = None):
    """Chunked prefill with speculation on: one slab dispatch advances every
    in-flight prefill through BOTH cache trees, the slab's ``rows`` the same
    slots of each (``lm.prefill_chunk``)."""
    logits, caches, stats = lm.prefill_chunk(
        params, cfg, tokens, valid_len, caches, pos_offset, rows)
    _, draft_caches, dstats = lm.prefill_chunk(
        draft_params, dcfg, tokens, valid_len, draft_caches, pos_offset,
        rows)
    return logits, caches, draft_caches, stats, dstats


# ---------------------------------------------------------------------------
# host-side rejection sampling (exact target distribution)
# ---------------------------------------------------------------------------

def _softmax64(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, np.float64)
    z = z - z.max()
    e = np.exp(z)
    return e / e.sum()


def rejection_sample(p_logits: np.ndarray, q_logits: np.ndarray,
                     drafts: np.ndarray, temperature: float,
                     rng: Optional[np.random.Generator] = None
                     ) -> Tuple[List[int], int]:
    """Speculative rejection sampling for one row (Leviathan et al., 2023).

    Args:
        p_logits: (m+1, V) target logits — row ``j`` is the target's
                  next-token distribution after consuming the pending token
                  plus drafts ``d_1 .. d_j``.
        q_logits: (m, V) draft logits — row ``j`` is the distribution
                  ``d_{j+1}`` was sampled from.
        drafts:   (m,) proposed tokens ``d_1 .. d_m``.
        temperature: the request's sampling temperature (<= 0 = greedy).
        rng:      host RNG for the stochastic path (unused when greedy).

    Returns ``(emitted, n_accepted)``: ``emitted`` is the accepted prefix
    plus exactly one more token — the corrected sample from
    ``norm(max(p - q, 0))`` on first rejection, or the bonus token from the
    target's ``m+1``-th distribution when every draft is accepted.  The
    sequence of emitted tokens is distributed EXACTLY as if each had been
    sampled from the target one at a time; under greedy both reduce to the
    target argmax chain, token for token.
    """
    m = len(drafts)
    emitted: List[int] = []
    if temperature <= 0.0:
        for j in range(m):
            t = int(p_logits[j].argmax())
            if t != int(drafts[j]):
                return emitted + [t], j
            emitted.append(t)
        return emitted + [int(p_logits[m].argmax())], m
    for j in range(m):
        p = _softmax64(p_logits[j] / temperature)
        q = _softmax64(q_logits[j] / temperature)
        d = int(drafts[j])
        if rng.random() < min(1.0, p[d] / max(q[d], 1e-300)):
            emitted.append(d)
            continue
        r = np.maximum(p - q, 0.0)
        s = r.sum()
        if s <= 0.0:          # numerically p <= q everywhere: p itself
            r, s = p, p.sum()
        return emitted + [int(rng.choice(r.size, p=r / s))], j
    p = _softmax64(p_logits[m] / temperature)
    return emitted + [int(rng.choice(p.size, p=p / p.sum()))], m
