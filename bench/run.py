"""The chip benchmark of the FFF serving stack: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and its configuration and traffic mix
from ``bench/configs`` and ``bench/traffic``; makes the weights and the
requests from ``--seed``; builds the serving engine (on a cell of several
chips under the program's serving mesh over them, the weights born sharded)
and warms up every shape the window uses (set-up); serves the traffic for
``--seconds`` (the window); drains; reads the peak memory of the fullest
device; frees the engine; then checks the served tokens against the float32
reference (``correct.py``).  With ``--trace 0`` the metrics are the cell's
end-to-end ones, with ``--trace 1`` its per-layer ones, read from a
profiler trace of the window (no longer than the mix's ``trace_seconds``
where it sets one).  The last line of standard output is one
JSON object; the numbers compared for ``correct`` close standard error.

Needs an accelerator: with none, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import spec  # noqa: E402


def log(*a) -> None:
    print(*a, flush=True)


def read_metric(name: str, run) -> float | None:
    path = os.path.join(spec.BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise spec.SpecError(f"no reader bench/metrics/{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


def start_jax(n: int):
    """Turn on the persistent compile cache (the program's own directory
    rule: ``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` in the
    checkout) for every program, then return the accelerator devices, or
    None with the reason on stderr."""
    from repro import utils
    utils.enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    if len(devs) < n:
        print(f"bench: the cell needs {n} accelerator chip(s); JAX found "
              f"{[d.platform + ':' + d.device_kind for d in jax.devices()]}",
              file=sys.stderr)
        return None
    return devs[:n]


def serving_mesh(devices) -> tuple:
    """``(mesh, trace_ctx)`` the engine serves under: ``(None, None)`` on
    one chip; on n chips the program's serving layout
    (``launch/mesh.serving_context(n)``: a ``("data", "model")`` mesh of
    shape (1, n) whose activation rules every jitted call traces under).
    That layout takes every visible device, so it has to be the cell's."""
    if len(devices) == 1:
        return None, None
    from repro.launch import mesh as mesh_lib
    mesh, trace_ctx = mesh_lib.serving_context(len(devices))
    if dict(mesh.shape) != {"data": 1, "model": len(devices)} or \
            set(mesh.devices.flat) != set(devices):
        raise RuntimeError(f"the serving mesh {dict(mesh.shape)} is not a "
                           f"(1, {len(devices)}) mesh of the cell's chips")
    return mesh, trace_ctx


def window_seconds(mix: dict, seconds: float, traced: bool) -> float:
    """The window's length: ``--seconds``, but in a traced run no more than
    the mix's ``trace_seconds`` where it sets one, for a cell whose trace
    of a whole window takes longer to write and read than a run may last.
    The per-layer metrics then read that shorter window throughout."""
    if traced and "trace_seconds" in mix:
        return min(seconds, float(mix["trace_seconds"]))
    return seconds


def memory_peaks(devices) -> list:
    """``peak_bytes_in_use`` of each device (0 where none is kept)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def serve(cell, program_cfg, peaks: dict, seed: int, seconds: float,
          traced: bool, devices) -> tuple:
    """Set-up, window, drain.  Returns (run record, params, window)."""
    import jax
    from bench import driver, traffic, trace, weights

    config, mix = cell.config, cell.traffic
    mesh, trace_ctx = serving_mesh(devices)
    params = jax.block_until_ready(weights.make(config, seed, mesh))
    weights.check_layout(params, program_cfg)
    engine = driver.build_engine(params, program_cfg, mix, seed, mesh,
                                 trace_ctx)
    counter = driver.CompileCounter()
    driver.warm_up(engine, mix, config["vocab_size"])
    items = traffic.generate(mix, seed, config["vocab_size"], seconds,
                             n_closed=driver.closed_loop_budget(mix, seconds))
    capture = trace.Capture() if traced else None
    marks = {}

    def on_open():
        marks["setup_s"] = time.monotonic() - T_START
        if capture:
            capture.start()

    def on_close():
        if capture:
            marks["xplane"] = capture.stop()

    annotate = (jax.profiler.TraceAnnotation if traced
                else lambda name: contextlib.nullcontext())
    window = driver.run_window(engine, mix, items, seconds, counter,
                               annotate, on_open=on_open, on_close=on_close)
    log(f"window: {len(window.steps)} steps, compilations inside the window "
        f"{window.compiles_in_window}, compiled shapes "
        f"{window.shapes_before} -> {window.shapes_after}")
    before = sum(s.arrival < window.t_open for s in window.requests.values())
    log(f"ramp: {before} requests sent in the "
        f"{window.t_open - window.t_first:.3f} s before the window opened")
    if window.late_s:
        late = sorted(window.late_s)
        log(f"generator lateness: median {late[len(late) // 2] * 1e3:.3f} ms, "
            f"max {late[-1] * 1e3:.3f} ms over {len(late)} arrivals")
    per_device = memory_peaks(devices)
    peak = max(per_device)
    log(f"device memory: {devices[0].memory_stats() or {}}")
    log(f"peak bytes in use per device: {per_device}")
    del engine
    gc.collect()
    reduction = table = None
    if capture:
        table = trace.load(marks["xplane"])
        capture.close()
        reduction = trace.reduce(
            table, trace.window_on_profile_clock(table, "bench.window"))
    run = types.SimpleNamespace(
        config=config, mix=mix, window=window, trace=reduction, peaks=peaks,
        chips=len(devices), mesh=mesh, setup_s=marks["setup_s"],
        seconds=seconds, memory_peak_bytes=peak, trace_table=table)
    return run, params, window


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    devices = start_jax(cell.chips)
    if devices is None:
        return 3

    from bench import correct
    seconds = window_seconds(cell.traffic, args.seconds, bool(args.trace))
    run, params, window = serve(
        cell, spec.program_config(cell.config),
        spec.load_peaks(devices[0].device_kind), args.seed, seconds,
        bool(args.trace), devices)
    verdict = correct.check(
        params, cell.config, window, args.seed,
        int(cell.traffic["check_requests"]),
        int(cell.traffic["engine"]["max_len"]), cell.limits)
    log(f"correct: {verdict.ok}; {verdict.n} served tokens against the "
        f"float32 reference read {verdict.got}")

    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    arrived = [s for s in window.requests.values()
               if window.t_open <= s.arrival < window.t_close]
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": verdict.ok, "attempted": len(arrived),
              "failed": sum(s.first_token is None for s in arrived),
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["checks"] = verdict.checks
    for name, c in verdict.checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
