"""Schema gate for ``experiments/BENCH_*.json`` benchmark artifacts (the CI
``bench-smoke`` job; start of the perf trajectory ISSUE 5 names).

Each artifact self-identifies via its ``bench`` key; this checker asserts
the per-bench required top-level keys and — for benches that embed engine
runs — the ``EngineMetrics.as_dict()`` core fields inside every run record,
so a refactor that silently drops a dashboarded field fails CI instead of
producing hollow artifacts.

Usage:
  PYTHONPATH=src python -m benchmarks.check_schema experiments/BENCH_*.json
"""
from __future__ import annotations

import json
import sys

# the EngineMetrics.as_dict() core every embedded run must carry
# (docs/serving.md documents the schema field-by-field)
METRICS_KEYS = {
    "n_requests", "n_tokens", "elapsed_s", "n_steps", "throughput_tok_s",
    "ttft_ms", "per_token_ms", "e2e_ms", "decode_step_ms",
    "decode_interval_ms", "overflow_fraction_mean", "overflow_decode_mean",
    "hint_mismatches", "tenants",
    # paged KV cache / prefix sharing (DESIGN.md §11)
    "prefill_tokens", "prefix_hit_tokens", "cow_copies", "pages_in_use",
    "pages_free",
}
SUMMARY_KEYS = {"n", "mean_ms", "p50_ms", "p90_ms", "p99_ms", "max_ms"}

# bench name -> (required top-level keys, key holding the run list/map of
#                EngineMetrics.as_dict() runs)
SCHEMAS = {
    # serving_load additionally carries the capacity<1.0 overflow-policy
    # sections (DESIGN.md §14): policy throughput gate, balanced-training
    # overflow gate, and the approximate-repair error bound
    "serving_load": ({"bench", "quick", "slots", "classes", "policy_compare",
                      "balance_compare", "repair_error", "runs"}, "runs"),
    "serving_chunked": ({"bench", "quick", "slots", "chunk",
                         "decode_interval_p99_drop", "stall_bound_tokens",
                         "runs"}, "runs"),
    "serving_qos": ({"bench", "quick", "slots", "classes", "fairness",
                     "profile_convergence", "overflow_decode", "runs"}, "runs"),
    "serving_spec": ({"bench", "quick", "slots", "depth", "gen", "spec_k",
                      "classes", "speedup", "speedup_gate", "speedup_ok",
                      "overflow_ok", "runs"}, "runs"),
    "serving_paged": ({"bench", "quick", "slots", "page_size", "shared_len",
                       "gen", "prefill_ratio", "prefill_gate", "prefill_ok",
                       "ttft_ok", "parity_checked", "compile_ok",
                       "compiled_shapes", "runs"}, "runs"),
    "serving_cluster": ({"bench", "quick", "topology", "page_size", "gen",
                         "speedup", "speedup_gate", "speedup_ok", "kill_ok",
                         "lost_requests", "parity_checked", "worker_restarts",
                         "replayed_requests", "duplicate_results", "scale_ok",
                         "scale_events", "compile_ok", "compiled_shapes",
                         "runs"}, "runs"),
}


def check_artifact(path: str) -> list:
    """Return a list of problem strings (empty = artifact passes)."""
    problems = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    bench = doc.get("bench")
    if bench not in SCHEMAS:
        return [f"{path}: unknown/missing bench id {bench!r} "
                f"(known: {sorted(SCHEMAS)})"]
    required, runs_key = SCHEMAS[bench]
    missing = required - set(doc)
    if missing:
        problems.append(f"{path}: missing top-level keys {sorted(missing)}")
    runs = doc.get(runs_key, [])
    records = list(runs.values()) if isinstance(runs, dict) else list(runs)
    if not records:
        problems.append(f"{path}: empty {runs_key!r}")
    for i, rec in enumerate(records):
        gone = METRICS_KEYS - set(rec)
        if gone:
            problems.append(f"{path}: run[{i}] missing metric keys "
                            f"{sorted(gone)}")
            continue
        for k in ("ttft_ms", "decode_step_ms"):
            if set(rec[k]) != SUMMARY_KEYS:
                problems.append(f"{path}: run[{i}].{k} is not a latency "
                                f"summary (has {sorted(rec[k])})")
    return problems


def main(argv=None) -> int:
    paths = list(sys.argv[1:] if argv is None else argv)
    if not paths:
        print("usage: python -m benchmarks.check_schema BENCH_*.json",
              file=sys.stderr)
        return 2
    problems = []
    for p in paths:
        problems += check_artifact(p)
    for msg in problems:
        print(f"SCHEMA: {msg}", file=sys.stderr)
    if not problems:
        print(f"schema ok: {len(paths)} artifact(s) "
              f"({', '.join(paths)})")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
