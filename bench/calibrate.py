"""Readings for a cell's correctness limit: the program and its control.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,...  [--control-seeds 1,2,3] [--out FILE]

For each seed, one process runs set-up, the window at the cell's own load
and the drain exactly as ``run.py`` does, then decides ``correct`` through
``correct.check`` as a run does (the program's reading).  For each control
seed the same call also decides the control's ``correct``: the reference
computed in float8 put in the program's place, read at each position of the
same prompts and served tokens (``correct.check(control=True)``).  Beside
each verdict it prints the look behind the widest gaps: the reference's own
routing margin (the smallest |node logit| on the position's paths) where
the program's gap is over 1, and overall.  The limit in
``bench/limits/<cell>.json`` is set between the largest program reading and
the smallest control reading (PERF.md gives both).  Benchmark runs never
run this.
"""
from __future__ import annotations

import gc
import json
import os
import sys

import numpy as np

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from bench import run as bench_run  # noqa: E402
from bench import correct, spec  # noqa: E402


def readings(cell, program_cfg, peaks, seed: int, seconds: float, devices,
             control: bool) -> dict:
    run, params, window = bench_run.serve(cell, program_cfg, peaks, seed,
                                          seconds, False, devices)
    v = correct.check(params, cell.config, window, seed,
                      int(cell.traffic["check_requests"]),
                      int(cell.traffic["engine"]["max_len"]), cell.limits,
                      control=control)
    del params, run
    gc.collect()
    ok, _, got = correct.verdict(v.program, cell.limits)
    wide = v.program > 1.0
    margin = v.margin
    out = {"seed": seed, "served_tokens": v.n, "program_correct": ok,
           "program": got,
           "wide_gaps": int(wide.sum()),
           "margin_median_at_wide_gaps": (float(np.median(margin[wide]))
                                          if wide.any() else None),
           "margin_median": float(np.median(margin)),
           "share_margin_below_0.05": float((margin < 0.05).mean()),
           "share_margin_below_0.05_at_wide_gaps": (
               float((margin[wide] < 0.05).mean()) if wide.any() else None)}
    if control:
        out["control_correct"] = v.ok
        out["control"] = v.got
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = bench_run.start_jax(cell.chips)
    if devices is None:
        return 3
    program_cfg = spec.program_config(cell.config)
    peaks = spec.load_peaks(devices[0].device_kind)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        line = json.dumps(readings(cell, program_cfg, peaks, seed,
                                   args.seconds, devices, seed in controls))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
