"""A cell at a size a CPU test can hold, run through the chip run's code.

The program's own reduced config (``ModelConfig.reduced``) in bf16, and the
configuration file the benchmark would hold for it; the traffic is the
closed or open loop of the real mixes at toy lengths.
"""
import dataclasses

import jax.numpy as jnp

from bench import spec


def program_cfg(trees: int = 1):
    from repro.configs import registry
    arch = "internlm2-20b" if trees == 1 else "olmoe-1b-7b"
    cfg = registry.get_config(arch, ffn="fff").reduced(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, vocab=256, seq=96)
    return dataclasses.replace(cfg, param_dtype=jnp.bfloat16,
                               accum_dtype=jnp.bfloat16)


def config(cfg) -> dict:
    ffn = cfg.period[0].ffn
    return {"name": "tiny", "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "norm_eps": 1e-6,
            "ffn": {"kind": "fff", "trees": ffn.fff_trees,
                    "depth": ffn.fff_depth, "leaf_width": ffn.fff_leaf_width,
                    "activation": ffn.activation},
            "dtype": "bfloat16"}


def mix(loop: str = "closed") -> dict:
    m = {"prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                           "min": 8, "max": 64},
         "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
         "engine": {"num_slots": 4, "prefill_chunk": 16, "max_len": 96,
                    "max_prompt_len": 64, "fff_backend": "auto"},
         "drain_s": 30, "check_requests": 3}
    if loop == "closed":
        m.update(loop="closed", clients=6, warm_completions=3)
    else:
        m.update(loop="open", rate_per_s=4.0)
    return m


def cell(trees: int = 1, loop: str = "closed", limits=None):
    cfg = program_cfg(trees)
    return spec.Cell(name="tiny", config=config(cfg), traffic=mix(loop),
                     chips=1, end_to_end=(),
                     per_layer=(), limits=limits or {}), cfg


PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
