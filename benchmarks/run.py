"""Benchmark harness entry: one function per paper table/figure.

Prints ``name,value,derived`` CSV. Asserts inside each benchmark double as
integration tests of the reproduction's claims (routing beats random, the
skew-difficulty correlation holds, token-cost blowup matches, ...).

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller KG / fewer queries")
    args = ap.parse_args()

    from benchmarks import kernel_bench, kgqa_experiment, paper_figures as F

    rows: list[tuple] = []
    t0 = time.monotonic()

    # -- static cost-model benchmarks (paper Fig 2 / Table 4) ---------------
    rows += F.fig2a_token_cost()
    rows += F.fig2b_scale_tradeoff()

    # -- KGQA pipeline (paper Figs 3-9, Table 3) ----------------------------
    n_q = 300 if args.quick else 600
    n_e = 8000 if args.quick else 12000
    steps = 300 if args.quick else 600
    for dataset in (["cwq"] if args.quick else ["cwq", "webqsp"]):
        _, _, _, records = kgqa_experiment.build_experiment(
            dataset, n_queries=n_q, n_entities=n_e, train_steps=steps)
        rows.append((f"{dataset}/n_records", len(records), "queries evaluated"))
        rows += F.fig3_skew_examples(records)
        rows += F.fig4_skew_vs_difficulty(records)
        rows += F.table3_baselines(records, dataset)
        rows += F.fig56_routing(records, dataset, "qwen7b", "qwen72b")
        rows += F.fig56_routing(records, dataset, "llama8b", "llama70b")
        rows += F.fig56_routing(records, dataset, "qwen7b", "llama70b",
                                strict_parity=False)  # Fig 8
        if dataset == "cwq":
            rows += F.fig7_multi_tier(records)
            rows += F.fig9_cumulative_p(records)

    # -- kernels --------------------------------------------------------------
    rows += kernel_bench.run_all()

    # -- routing-policy frontier (gates asserted inside; full bench with
    # tracked JSON: python -m benchmarks.policy_frontier_bench) -------------
    from benchmarks import policy_frontier_bench
    rows += policy_frontier_bench.csv_rows(quick=args.quick)

    # -- serving-stack smokes (each bench's gates assert inside; the full
    # sweeps with tracked JSON remain the standalone entries) ---------------
    from benchmarks import (fabric_sync_bench, load_sim_bench,
                            roofline_report, sharded_dispatch_bench)
    rows += load_sim_bench.csv_rows(quick=True)
    rows += fabric_sync_bench.csv_rows(quick=True)
    rows += sharded_dispatch_bench.csv_rows(quick=True)
    rows += roofline_report.csv_rows(quick=args.quick)

    rows.append(("total_wall_s", time.monotonic() - t0, ""))
    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val},{derived}")


if __name__ == "__main__":
    main()
