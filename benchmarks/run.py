"""Benchmark harness: one module per paper table/figure, each printing
``name,us_per_call,derived`` CSV rows.

  table1 -> paper Table 1 (FFF vs FF across widths/leaf sizes, M_A/G_A/speedup)
  fig2   -> paper Figure 2 (equal inference size comparison)
  table2 -> paper Table 2 (FFF vs MoE vs FF + epochs-to-train)
  fig34  -> paper Figures 3-4 (mechanism latency scaling, BERT dims)
  table3 -> paper Table 3 (ViT with FFF layers)
  ep_dispatch -> grouped_ep dispatch-locality curve: tokens/s, per-shard
                 capacity and bytes moved vs model-shard count, plus the
                 overflow-policy traffic gate (master_leaf repair bytes == 0,
                 exact_dense pays a real round) (DESIGN.md §5, §14)
  serving -> continuous-batching engine under Poisson load, fcfs vs
             leaf_aware admission: throughput / TTFT / per-token latency /
             overflow_fraction; plus the capacity<1.0 overflow-policy
             sections — master_leaf-vs-exact_dense decode tok/s gate,
             balanced-vs-unbalanced training overflow gate, approximate-
             repair error bound (DESIGN.md §9, §14; writes
             BENCH_serving_load.json)
  serving_chunked -> chunked vs monolithic prefill under long-prompt
             arrivals: decode-interval p99 / throughput / TTFT
             (DESIGN.md §9; writes BENCH_serving_chunked.json)
  serving_qos -> multi-tenant weighted-fair admission + online routing
             profiles on a skewed two-tenant workload: fairness vs
             weights, profile convergence, overflow vs no-hint fcfs
             (DESIGN.md §9; writes BENCH_serving_qos.json)
  serving_spec -> speculative decoding vs plain decode on a drafter-
             consistent deep target: tokens/s speedup gate, acceptance,
             verify-slab overflow vs baseline
             (DESIGN.md §10; writes BENCH_serving_spec.json)
  serving_paged -> paged KV cache + cross-request prefix sharing vs the
             contiguous cache on a shared-system-prompt workload:
             prefill-token ratio gate, TTFT, exact parity, compile contract
             (DESIGN.md §11; writes BENCH_serving_paged.json)
  serving_cluster -> disaggregated prefill/decode cluster vs one colocated
             engine at equal total slots: throughput gate, worker-kill
             replay with exact parity, elastic scale-up/down, per-role
             compile contract (DESIGN.md §12; writes
             BENCH_serving_cluster.json)

``python -m benchmarks.run`` runs the quick profile (CPU-sized, ~minutes);
``python -m benchmarks.run --full`` runs the paper-scale grids.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grids (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table1,fig2,table2,fig34,"
                         "table3,ep_dispatch,serving,"
                         "serving_chunked,serving_qos,serving_spec,"
                         "serving_paged,serving_cluster")
    args = ap.parse_args()

    from repro import utils
    utils.enable_compile_cache()
    from benchmarks import (ep_dispatch, fig2, fig34, serving_chunked,
                            serving_cluster, serving_load, serving_paged,
                            serving_qos, serving_spec, table1, table2,
                            table3)
    suites = {
        "table1": table1.main,
        "fig2": fig2.main,
        "table2": table2.main,
        "fig34": fig34.main,
        "table3": table3.main,
        "ep_dispatch": ep_dispatch.main,
        "serving": serving_load.main,
        "serving_chunked": serving_chunked.main,
        "serving_qos": serving_qos.main,
        "serving_spec": serving_spec.main,
        "serving_paged": serving_paged.main,
        "serving_cluster": serving_cluster.main,
    }
    selected = (args.only.split(",") if args.only else list(suites))
    failures = []
    for name in selected:
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            suites[name](quick=not args.full)
        except Exception as e:                       # noqa: BLE001
            traceback.print_exc()
            failures.append((name, e))
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILURES: {[n for n, _ in failures]}")
        sys.exit(1)


if __name__ == "__main__":
    main()
