"""What a run is asked to do, read from the data files by name.

``BENCHMARK.json`` (at the checkout's root) names the cells; each cell names
a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  Per-layer metrics are readers in
``bench/metrics/<metric>.py`` and correctness limits live in
``bench/limits/<cell>.json``.  Adding a cell, configuration, mix or metric
adds files and entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A data file is missing or says something the harness cannot run."""


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<mix>.json
    chips: int
    end_to_end: tuple       # metric entries of BENCHMARK.json
    per_layer: tuple
    limits: dict            # bench/limits/<cell>.json ({} = none set yet)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(cells)}")
    w = cells[name]
    here = os.path.join(root, "bench")
    config = _load(os.path.join(here, "configs", w["config"] + ".json"))
    traffic = _load(os.path.join(here, "traffic", w["traffic"] + ".json"))
    limits_path = os.path.join(here, "limits", name + ".json")
    limits = _load(limits_path) if os.path.exists(limits_path) else {}
    return Cell(
        name=name, config=config, traffic=traffic, chips=int(w["chips"]),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        limits=limits)


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = _load(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (have {sorted(table['devices'])})")
    return dict(table["devices"][device_kind], source=table["source"])


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the repo's
    FFF variant of ``program_arch`` with the file's depth, checked number by
    number against the file so that the file holds what is run."""
    from repro.configs import registry
    base = registry.get_config(config["program_arch"], ffn="fff")
    cfg = dataclasses.replace(base, n_layers=int(config["n_layers"]))
    ffn = cfg.period[0].ffn
    have = {
        "d_model": cfg.d_model, "n_layers": cfg.n_layers,
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "ffn": {"kind": ffn.kind, "trees": ffn.fff_trees,
                "depth": ffn.fff_depth, "leaf_width": ffn.fff_leaf_width,
                "activation": ffn.activation},
        "dtype": _dtype_name(cfg.param_dtype),
    }
    want = {k: config[k] for k in have}
    if have != want or len(cfg.period) != 1 or cfg.period[0].mixer != "attn" \
            or ffn.fff_master_leaf or ffn.fff_node_width != 1 \
            or cfg.norm != "rmsnorm" or cfg.pos_emb != "rope" \
            or cfg.tie_embeddings or cfg.attn_bias \
            or _dtype_name(cfg.accum_dtype) != config["dtype"]:
        raise SpecError(f"the program's {config['program_arch']} FFF config "
                        f"no longer matches {config['name']}.json: program "
                        f"{have}, file {want}")
    return cfg


def _dtype_name(dt) -> str:
    import numpy as np
    return np.dtype(dt).name
