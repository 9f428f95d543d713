"""The run's serving mesh: a cell of several chips served over a (1, n)
mesh of its devices, and a one-chip cell served exactly as before.

The four-device cases run in a subprocess on four host devices
(``--xla_force_host_platform_device_count=4``), since a process fixes its
device count when JAX starts: the tiny cell through ``run.serve`` with
the weights born sharded, every FFF site through ``grouped_ep``, then the
comparison that decides ``correct`` on the sharded weights and its float8
control; and once more with the exchange between the chips left out
(every ``all_to_all`` hands back its own shard's buffer), which the same
comparison must find.  The one-device cases run here.
"""
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from bench import driver, weights
from bench import run as bench_run
from bench.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 33 + 303
# the toy model's limit and its readings: test_bench_correct.py
LIMIT = {"mean_logit_gap": 0.07}
#: sha256 over every leaf's bytes, in tree order, of the tiny config's
#: weights from SEED, as the one-device ``weights.make`` drew them before
#: it took a mesh
ONE_DEVICE_SHA256 = \
    "e6aa07e643c38ea1ff625667baaf1dc123833f91979ce0af814670c495c3069d"

SERVE_ON_FOUR = textwrap.dedent("""
    import json
    import jax
    import numpy as np
    from bench import correct, weights
    from bench import run as bench_run
    from bench.tests import tiny
    from repro.core import api

    seed, limits, fault = {seed}, {limits}, {fault!r}
    if fault == "no_exchange":
        jax.lax.all_to_all = lambda x, *a, **kw: x
    cell, cfg = tiny.cell(1, "closed", limits)
    # every infer backend the engine's programs trace, with the sequence
    # length it was given: decode (1) and the chunk slab (prefill_chunk)
    traced = []
    for (mode, name), fn in list(api._REGISTRY.items()):
        if mode == "infer":
            def rec(params, c, x, spec, fn=fn, name=name):
                traced.append([name, int(x.shape[-2]) if x.ndim > 2 else 0])
                return fn(params, c, x, spec)
            api._REGISTRY[(mode, name)] = rec
    devices = jax.devices()
    run, params, window = bench_run.serve(cell, cfg, tiny.PEAKS, seed, 1.5,
                                          False, devices)
    sharded = [a for a in jax.tree_util.tree_leaves(params)
               if not a.sharding.is_fully_replicated]
    held = [sum(s.data.nbytes for a in sharded for s in a.addressable_shards
                if s.device == d) for d in devices]
    same = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        params, weights.make(cell.config, seed)))
    args = (params, cell.config, window, seed,
            cell.traffic["check_requests"], cell.traffic["engine"]["max_len"],
            cell.limits)
    v = correct.check(*args)
    c = correct.check(*args, control=True) if not fault else v
    print(json.dumps({{
        "devices": len(devices), "chips": run.chips, "held": held,
        "mesh": dict(run.mesh.shape), "same_values": same,
        "traced": traced, "compiles_in_window": window.compiles_in_window,
        "shapes_same": window.shapes_before == window.shapes_after,
        "peak": run.memory_peak_bytes,
        "ok": v.ok, "gap": v.checks["mean_logit_gap"]["value"], "n": v.n,
        "control_ok": c.ok, "control_gap": c.got["mean_logit_gap"]}}))
""")


def serve_on_four(fault: str = "") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    code = SERVE_ON_FOUR.format(seed=SEED, limits=LIMIT, fault=fault)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def four():
    return serve_on_four()


def test_four_devices_serve_over_a_one_by_four_mesh(four):
    assert four["devices"] == four["chips"] == 4
    assert four["mesh"] == {"data": 1, "model": 4}


def test_weights_are_born_sharded_and_spread_evenly(four):
    held = four["held"]
    share = sum(held) / len(held)
    assert all(0.8 * share <= h <= 1.25 * share for h in held), held
    # where they are placed changes nothing of their values
    assert four["same_values"]


def test_every_fff_site_takes_grouped_ep_in_decode_and_prefill(four):
    names = {name for name, _ in four["traced"]}
    lengths = {n for _, n in four["traced"]}
    assert names == {"grouped_ep"}, four["traced"]
    chunk = tiny.mix()["engine"]["prefill_chunk"]
    assert {1, chunk} <= lengths, four["traced"]


def test_window_compiles_nothing(four):
    assert four["compiles_in_window"] == 0
    assert four["shapes_same"]


def test_sharded_run_matches_the_reference_and_its_control_fails(four):
    assert four["n"] > 0 and four["ok"], four
    assert four["gap"] <= LIMIT["mean_logit_gap"] < four["control_gap"]
    assert not four["control_ok"]


def test_exchange_left_out_fails_correct():
    got = serve_on_four("no_exchange")
    assert got["n"] > 0 and not got["ok"], got
    assert got["gap"] > LIMIT["mean_logit_gap"]


def _sha256(tree) -> str:
    h = hashlib.sha256()
    for a in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def test_one_device_weights_are_drawn_as_before():
    cell, _ = tiny.cell(1)
    assert _sha256(weights.make(cell.config, SEED)) == ONE_DEVICE_SHA256


def test_one_device_engine_gets_no_mesh(monkeypatch):
    from repro.serving import engine as engine_lib
    seen = {}

    class Recording(engine_lib.ContinuousBatchingEngine):
        def __init__(self, *a, **kw):
            seen.update(kw)
            super().__init__(*a, **kw)

    monkeypatch.setattr(engine_lib, "ContinuousBatchingEngine", Recording)
    cell, cfg = tiny.cell(1)
    devices = jax.devices()[:1]
    mesh, trace_ctx = bench_run.serving_mesh(devices)
    assert (mesh, trace_ctx) == (None, None)
    params = weights.make(cell.config, SEED)
    driver.build_engine(params, cfg, cell.traffic, SEED, mesh, trace_ctx)
    assert seen == {"mesh": None, "trace_ctx": None}
