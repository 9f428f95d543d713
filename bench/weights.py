"""Weights from the seed, made by the benchmark itself.

One jitted call draws every parameter on the device, in the type it is
served in, in the parameter layout the serving engine takes.  The reference
(``reference.py``) reads the same arrays by name, so neither side uses
anything the program made.  ``check_layout`` compares the layout with the
program's own ``lm.init`` shapes (shapes only, never values) so that a
change of the program's layout fails at set-up instead of serving garbage.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def shapes(config: dict) -> dict:
    """name path -> (shape, init std or "ones"/"zeros")."""
    D, n = config["d_model"], config["n_layers"]
    H, K, hd = config["n_heads"], config["n_kv_heads"], config["head_dim"]
    V = config["vocab_size"]
    f = config["ffn"]
    T, depth, l = f["trees"], f["depth"], f["leaf_width"]
    N, L = 2 ** depth - 1, 2 ** depth
    sd = 1.0 / math.sqrt(D)
    return {
        ("embed", "tok"): ((V, D), sd),
        ("embed", "head"): ((D, V), sd),
        ("final_norm", "scale"): ((D,), "ones"),
        ("stack", "norm1", "scale"): ((n, D), "ones"),
        ("stack", "mixer", "wq"): ((n, D, H, hd), sd),
        ("stack", "mixer", "wk"): ((n, D, K, hd), sd),
        ("stack", "mixer", "wv"): ((n, D, K, hd), sd),
        ("stack", "mixer", "wo"): ((n, H, hd, D), 1.0 / math.sqrt(H * hd)),
        ("stack", "norm2", "scale"): ((n, D), "ones"),
        ("stack", "ffn", "node_w1"): ((n, T, N, D, 1), sd),
        ("stack", "ffn", "node_b1"): ((n, T, N, 1), "zeros"),
        ("stack", "ffn", "node_w2"): ((n, T, N, 1), 1.0),
        ("stack", "ffn", "node_b2"): ((n, T, N), "zeros"),
        ("stack", "ffn", "leaf_wg"): ((n, T, L, D, l), sd),
        ("stack", "ffn", "leaf_wu"): ((n, T, L, D, l), sd),
        ("stack", "ffn", "leaf_wd"): ((n, T, L, l, D), 1.0 / math.sqrt(l)),
    }


def _nest(flat: dict) -> dict:
    """Flat paths -> the engine's tree: ``stack`` is a one-entry list (one
    block per period) whose leaves carry the layer axis first."""
    out: dict = {}
    for path, v in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    out["stack"] = [out["stack"]]
    return out


def make(config: dict, seed: int, mesh=None) -> dict:
    """All parameters, drawn in one program: on the default device, or
    under a serving ``mesh`` each born on its shard as the program places
    it (``sharding.param_shardings``, as ``lm.init_params`` does), so that
    no device ever holds the whole model.  The values depend on the seed
    alone (threefry is partitionable), not on where they are placed."""
    dtype = jnp.dtype(config["dtype"])
    table = shapes(config)

    def draw(key):
        flat = {}
        for i, (path, (shape, how)) in enumerate(sorted(table.items())):
            if how == "ones":
                flat[path] = jnp.ones(shape, dtype)
            elif how == "zeros":
                flat[path] = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(key, i)
                flat[path] = (jax.random.truncated_normal(
                    k, -2.0, 2.0, shape, jnp.float32) * how).astype(dtype)
        return _nest(flat)

    key = key_from_seed(seed, stream=1)
    if mesh is None:
        return jax.jit(draw)(key)
    from repro.distributed import sharding
    placed = sharding.param_shardings(jax.eval_shape(draw, key), mesh,
                                      fsdp=False)
    return jax.jit(draw, out_shardings=placed)(key)


def check_layout(params: dict, program_cfg) -> None:
    """Raise if the program's ``lm.init`` would build another tree, other
    shapes or other types than ``params`` holds."""
    from repro.models import lm
    want = jax.eval_shape(lambda k: lm.init(k, program_cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got):
        raise ValueError(f"parameter tree differs from the program's: "
                         f"{jax.tree_util.tree_structure(want)} vs "
                         f"{jax.tree_util.tree_structure(got)}")
    bad = [(jax.tree_util.keystr(p), w, g) for (p, w), g in zip(
        jax.tree_util.tree_leaves_with_path(want),
        jax.tree_util.tree_leaves(got))
        if (w.shape, w.dtype) != (g.shape, g.dtype)]
    if bad:
        raise ValueError(f"parameter shapes differ from the program's: "
                         f"{bad[:4]}")
