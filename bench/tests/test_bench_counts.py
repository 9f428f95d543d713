"""Operation and byte counts at the configurations' shapes: internlm2's
one tree from its file, and the OLMoE forest's widths (8 trees of depth 3,
1024-wide leaves at d_model 2048)."""
import json
import os

import pytest

from bench import counts

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


INTERN = "internlm2-20b-fff-4l"
OLMOE = "olmoe-forest"
FOREST = {"d_model": 2048, "n_heads": 16, "n_kv_heads": 16, "head_dim": 128,
          "vocab_size": 50304, "n_layers": 6,
          "ffn": {"kind": "fff", "trees": 8, "depth": 3, "leaf_width": 1024,
                  "activation": "swiglu"}}


def config(name):
    if name == OLMOE:
        return FOREST
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,per_token", [
    # one tree: 4 node dots + 3 products of 6144 x 1024
    (INTERN, 2 * 6144 * 4 + 6 * 6144 * 1024),
    # eight trees: 3 node dots + 3 products of 2048 x 1024 each
    (OLMOE, 8 * (2 * 2048 * 3 + 6 * 2048 * 1024)),
])
def test_fff_token_flops(name, per_token):
    assert counts.fff_token_flops(config(name)) == per_token


@pytest.mark.parametrize("name,leaf", [(INTERN, 3 * 6144 * 1024 * 2),
                                       (OLMOE, 3 * 2048 * 1024 * 2)])
def test_leaf_bytes(name, leaf):
    assert counts.leaf_bytes(config(name)) == leaf


@pytest.mark.parametrize("name", [INTERN, OLMOE])
@pytest.mark.parametrize("n", [1, 32])
def test_fused_decode_reads_one_leaf_per_tree(name, n):
    c = config(name)
    f = c["ffn"]
    flops, nbytes = counts.fused_decode_call(c, n)
    nodes = f["trees"] * (2 ** f["depth"] - 1) * c["d_model"] * 2
    acts = n * 2 * c["d_model"] * 2
    # the distinct-leaf floor: one leaf per tree whatever n is, so a kernel
    # that reads each distinct leaf once can never read above 100%
    assert nbytes == nodes + f["trees"] * counts.leaf_bytes(c) + acts
    assert flops == n * counts.fff_token_flops(c)


def test_model_flops_adds_up():
    c = config(INTERN)
    D, H, K, hd, V, n = 6144, 48, 8, 128, 92544, 4
    proj = 2 * D * (H + 2 * K) * hd + 2 * H * hd * D
    one = counts.model_flops(c, tokens=1, ctx=10, emitted=1)
    assert one == n * (proj + counts.fff_token_flops(c)) \
        + n * 4 * H * hd * 10 + 2 * D * V
    assert counts.model_flops(c, 0, 0, 0) == 0


def test_least_seconds_takes_the_binding_roof():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.least_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert counts.least_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)
