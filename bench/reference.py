"""The plain reference of the benchmark's model family, in float32.

A decoder-only transformer as the configuration files describe it:
token embedding; per layer a pre-RMSNorm grouped-query attention with
rotary positions (rotate-half pairing, theta from the file) and a
pre-RMSNorm FFF site; a final RMSNorm and an untied LM head.  The FFF site
follows FORWARD_I of Belcak & Wattenhofer (2023): each tree descends from
its root, going right where the node's hyperplane logit is >= 0, and the
token's output is the sum over trees of its one leaf's SwiGLU MLP.

Nothing here imports the program.  It reads the benchmark's own weights
(``weights.py``) by name, runs one sequence at a time, layer by layer, with
every matrix product at ``Precision.HIGHEST``, and evaluates every leaf on
every row under a mask (the plainest form of "one leaf per token").

``quant="fp8"`` is the control: every operand of every matrix product is
rounded to float8 e4m3 (one scale per tensor) before the product, the step
below the bf16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
ROW_BLOCK = 128
FP8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, heads, hd), pos (S,)"""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(x, st, layer, length, *, cfg, quant):
    """Block ``layer`` of the stack ``st`` over a padded sequence x (S, D);
    positions >= length are padding that no real position attends."""
    H, K, hd, eps, theta = cfg
    lp = {"norm1": st["norm1"]["scale"][layer],
          "norm2": st["norm2"]["scale"][layer],
          **{k: v[layer] for k, v in st["mixer"].items()},
          **{k: v[layer] for k, v in st["ffn"].items()}}
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    S = x.shape[0]
    G = H // K
    pos = jnp.arange(S)
    h = _rms(x, lp["norm1"], eps)
    q = _rope(_mm("sd,dhk->shk", h, f32(lp["wq"]), quant), pos, theta)
    k = _rope(_mm("sd,dhk->shk", h, f32(lp["wk"]), quant), pos, theta)
    v = _mm("sd,dhk->shk", h, f32(lp["wv"]), quant)

    def attend(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, Q_BLOCK, 0)
        qg = qb.reshape(Q_BLOCK, K, G, hd)
        s = _mm("qkgd,skd->kgqs", qg, k, quant) / np.sqrt(hd)
        qpos = qb_start + jnp.arange(Q_BLOCK)
        ok = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < length)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _mm("kgqs,skd->qkgd", p, v, quant)
        return o.reshape(Q_BLOCK, H, hd)

    o = jax.lax.map(attend, jnp.arange(0, S, Q_BLOCK)).reshape(S, H, hd)
    x = x + _mm("shk,hkd->sd", o, f32(lp["wo"]), quant)

    h = _rms(x, lp["norm2"], eps)
    T, N = lp["node_w1"].shape[:2]
    L = lp["leaf_wg"].shape[1]
    depth = int(np.log2(L))
    y = jnp.zeros_like(x)
    margin = jnp.full((S,), jnp.inf)
    for t in range(T):
        idx = jnp.zeros((S,), jnp.int32)
        for m in range(depth):
            node = 2 ** m - 1 + idx
            w1 = f32(lp["node_w1"][t, :, :, 0])[node]            # (S, D)
            if quant == "fp8":
                hq, w1 = _fp8(h), _fp8(w1)
            else:
                hq = h
            logit = ((jnp.sum(hq * w1, -1) + f32(lp["node_b1"][t, :, 0])[node])
                     * f32(lp["node_w2"][t, :, 0])[node]
                     + f32(lp["node_b2"][t])[node])
            idx = 2 * idx + (logit >= 0).astype(jnp.int32)
            margin = jnp.minimum(margin, jnp.abs(logit))

        def leaf(acc, l, t=t, idx=idx):
            g = _mm("sd,dh->sh", h, f32(lp["leaf_wg"][t, l]), quant)
            u = _mm("sd,dh->sh", h, f32(lp["leaf_wu"][t, l]), quant)
            out = _mm("sh,hd->sd", jax.nn.silu(g) * u,
                      f32(lp["leaf_wd"][t, l]), quant)
            return acc + jnp.where((idx == l)[:, None], out, 0.0), None

        y, _ = jax.lax.scan(leaf, y, jnp.arange(L))
    return x + y, margin


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, scale, head, *, eps, quant):
    return _mm("sd,dv->sv", _rms(x, scale, eps), head.astype(jnp.float32),
               quant)


def padded_len(max_len: int) -> int:
    return -(-int(max_len) // Q_BLOCK) * Q_BLOCK


def logits_at(params: dict, config: dict, tokens: np.ndarray,
              rows: np.ndarray, s_pad: int, quant: str = "",
              margins: bool = False):
    """Next-token logits (len(rows), V) of the sequence ``tokens`` at the
    positions ``rows``; the sequence is padded to ``s_pad`` so that every
    call of one cell runs the same compiled programs.  With ``margins``,
    also each row's routing margin: the smallest |node logit| on its paths
    through every tree of every layer (how near it came to another leaf)."""
    n = len(tokens)
    if n > s_pad:
        raise ValueError(f"sequence of {n} tokens exceeds {s_pad}")
    cfg = (config["n_heads"], config["n_kv_heads"], config["head_dim"],
           float(config["norm_eps"]), float(config["rope_theta"]))
    ids = np.zeros((s_pad,), np.int32)
    ids[:n] = tokens
    x = jnp.take(params["embed"]["tok"], jnp.asarray(ids), axis=0
                 ).astype(jnp.float32)
    st = params["stack"][0]
    margin = jnp.full((s_pad,), jnp.inf)
    for layer in range(config["n_layers"]):
        x, layer_margin = _layer(x, st, jnp.int32(layer), jnp.int32(n),
                                 cfg=cfg, quant=quant)
        margin = jnp.minimum(margin, layer_margin)
    # rows padded to a multiple of ROW_BLOCK: few head shapes per cell
    m = len(rows)
    idx = np.zeros((-(-m // ROW_BLOCK) * ROW_BLOCK,), np.int32)
    idx[:m] = rows
    sel = jnp.take(x, jnp.asarray(idx), axis=0)
    logits = np.asarray(_head(sel, params["final_norm"]["scale"],
                              params["embed"]["head"], eps=cfg[3],
                              quant=quant))[:m]
    if margins:
        return logits, np.asarray(margin)[np.asarray(rows)]
    return logits
