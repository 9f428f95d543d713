"""BENCHMARK.json and the data files it names hang together: every cell's
configuration and mix exist, every metric has its reader, and each
configuration file holds what the program runs."""
import json
import os
import re

import pytest

from bench import spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
    DOC = json.load(f)
CELLS = [w["name"] for w in DOC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_units():
    metrics = DOC["end_to_end"] + DOC["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + \
        [c["name"] for c in DOC["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
               for m in metrics)
    assert {m["better"] for m in metrics} <= {"lower", "higher"}
    assert any(m["name"] == "setup_s" for m in DOC["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_with_readers(cell):
    c = spec.load_cell(cell)
    assert c.chips == 1
    for m in c.end_to_end + c.per_layer:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    moved = {m["moves"] for m in c.per_layer}
    assert moved <= {m["name"] for m in c.end_to_end}
    assert c.per_layer and len(c.end_to_end) >= 2


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda e: e["name"])
def test_config_file_is_what_the_program_runs(entry):
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"]
    cfg = spec.program_config(config)
    assert cfg.n_layers == config["n_layers"] < config["published"]["n_layers"]


def test_program_config_refuses_a_changed_width():
    with open(os.path.join(BENCH, "configs", "internlm2-20b-fff-4l.json")) as f:
        config = json.load(f)
    config["d_model"] = 1024
    with pytest.raises(spec.SpecError):
        spec.program_config(config)


def test_peaks_table_refuses_unknown_devices():
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.load_peaks("cpu")
