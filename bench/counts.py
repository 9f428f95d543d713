"""Operations and bytes from shapes: the model step and the FFF kernels.

All counts are of the work the algorithm needs, whatever implements it: a
multiply-add is 2 operations, weights are read once per call in the type
they are served in (bf16, 2 bytes), and padded rows, filler rows and free
slots count nothing.

Distinct leaves.  A kernel that serves n tokens of one FFF site needs each
tree's node weights and each distinct (tree, leaf) that its tokens routed
to.  The engine returns no per-call routing through a public surface, so the
byte counts take one leaf per tree per call: the fewest any call with a
valid token can read.  That can only under-count bytes, so a kernel's
roofline share is a floor and can never pass 100% by this count.  A
per-step distinct-leaf counter in the program would make it exact.
"""
from __future__ import annotations

BF16 = 2


def _dims(config: dict):
    f = config["ffn"]
    return (config["d_model"], config["n_heads"], config["n_kv_heads"],
            config["head_dim"], config["vocab_size"], config["n_layers"],
            f["trees"], f["depth"], f["leaf_width"])


def fff_token_flops(config: dict) -> float:
    """One token through one FFF site: per tree ``depth`` node dots and one
    SwiGLU leaf (three D x l products)."""
    D, _, _, _, _, _, T, depth, l = _dims(config)
    return T * (2 * D * depth + 2 * 3 * D * l)


def model_flops(config: dict, tokens: int, ctx: int, emitted: int) -> float:
    """Forward operations of the whole model for ``tokens`` token rows
    (prefill and decode) whose attention spans ``ctx`` positions in all,
    plus the LM head for the ``emitted`` tokens."""
    D, H, K, hd, V, n, _, _, _ = _dims(config)
    proj = 2 * D * (H + 2 * K) * hd + 2 * H * hd * D
    per_token = n * (proj + fff_token_flops(config))
    return tokens * per_token + n * 4 * H * hd * ctx + emitted * 2 * D * V


def leaf_bytes(config: dict) -> int:
    D, _, _, _, _, _, _, _, l = _dims(config)
    return 3 * D * l * BF16


def fused_decode_call(config: dict, n: int) -> tuple[float, float]:
    """(operations, bytes) of one fused-decode call over n valid tokens:
    routing and leaf MLP of every tree; node weights, one leaf per tree,
    and the tokens' activations in and out."""
    D, _, _, _, _, _, T, depth, _ = _dims(config)
    nodes = T * (2 ** depth - 1) * D * BF16
    return (n * fff_token_flops(config),
            nodes + T * leaf_bytes(config) + n * 2 * D * BF16)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
