"""Load-simulation benchmark: admission control on vs. off, same trace.

Replays a canonical seeded workload trace (bursty arrivals + score-skew
drift + a large-tier replica failure; see
``repro.serving.loadgen.workload.CANONICAL_TRACES``) through the tuned
canonical serving setup twice — once with the admission controller
(cost-budget feedback + SLO-aware tier-spill) and once without (exactly
today's routing) — and reports the SLO-attainment / $-per-query /
quality-proxy trade the controller buys.

Acceptance gates (asserted on every run, smoke included):

* baseline reproduces pre-admission behavior bit-for-bit: zero spills
  and executed tier mix == dispatcher decisions;
* admission keeps realized $/query inside the configured budget (and the
  expensive-tier executed share inside the share that budget implies);
* admission IMPROVES simulated SLO attainment over the baseline.

Full runs (default trace, no --smoke) also write structured JSON to
``BENCH_load_sim.json`` at the repo root — the load-serving trajectory
tracked across PRs (``--json`` overrides the path, ``--json ''``
disables writing; smoke runs don't touch the tracked file unless asked).

  PYTHONPATH=src python -m benchmarks.load_sim_bench [--smoke] [--trace NAME]
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import time

from repro.serving.loadgen import canonical_load_runner, canonical_trace

DEFAULT_TRACE = "bursty_drift_saturation"
SMOKE_TRACE = "smoke"
BUDGET_TOL = 1.05       # realized $/query may exceed budget by <= 5%
SHARE_TOL = 0.02        # executed share vs the budget-implied ceiling
DEFAULT_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_load_sim.json"


def _sanitize(x):
    """nan/inf -> None so the tracked JSON stays strictly parseable."""
    if isinstance(x, float):
        return x if math.isfinite(x) else None
    if isinstance(x, dict):
        return {k: _sanitize(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_sanitize(v) for v in x]
    return x


def run_pair(trace_name: str, record_every: int) -> dict:
    trace = canonical_trace(trace_name)
    out = {}
    for label, with_admission in (("baseline", False), ("admission", True)):
        runner = canonical_load_runner(with_admission, trace,
                                       record_every=record_every)
        t0 = time.perf_counter()
        report = runner.run(trace)
        wall = time.perf_counter() - t0
        out[label] = {"wall_s": wall, "report": report,
                      "runner": runner}
        s = report.summary
        print(f"{label:9s}: slo_attainment={s['slo_attainment']:.4f}  "
              f"$/query={s['cost_per_query']:.6f}  "
              f"quality={s['quality_proxy']:.2f}  "
              f"top_share={s['expensive_share_executed']:.3f}  "
              f"spilled={s['n_spilled']}  "
              f"recals={s['n_recalibrations']}  wall={wall:.1f}s")
    return out


def check_gates(pair: dict) -> dict:
    base = pair["baseline"]["report"].summary
    adm = pair["admission"]["report"].summary
    runner = pair["admission"]["runner"]
    spec = runner.session.spec
    budget = spec.admission.cost_budget_per_query

    # -- baseline is bit-for-bit today's routing ------------------------------
    assert base["n_spilled"] == 0, \
        f"baseline spilled {base['n_spilled']} requests with admission off"
    decisions = {str(t): c for t, c in
                 runner_decisions(pair["baseline"]["runner"]).items()}
    assert decisions == base["tier_counts_executed"], (
        f"baseline executed mix {base['tier_counts_executed']} diverged "
        f"from dispatcher decisions {decisions}")

    # -- budget invariant ------------------------------------------------------
    assert adm["cost_per_query"] <= budget * BUDGET_TOL, (
        f"admission run spent ${adm['cost_per_query']:.6f}/query against a "
        f"${budget:.6f} budget (tolerance x{BUDGET_TOL})")
    cost_model = spec.cost_model()
    models = spec.models()
    c_low, c_top = (cost_model.request_cost(models[0]),
                    cost_model.request_cost(models[-1]))
    implied_share = (budget * BUDGET_TOL - c_low) / (c_top - c_low)
    assert adm["expensive_share_executed"] <= implied_share + SHARE_TOL, (
        f"executed expensive share {adm['expensive_share_executed']:.3f} "
        f"exceeds the budget-implied ceiling {implied_share:.3f}")

    # -- SLO invariant ---------------------------------------------------------
    assert adm["slo_attainment"] > base["slo_attainment"], (
        f"admission did not improve SLO attainment: "
        f"{adm['slo_attainment']:.4f} vs baseline "
        f"{base['slo_attainment']:.4f}")

    gates = {
        "budget": budget,
        "budget_tol": BUDGET_TOL,
        "implied_top_share_ceiling": implied_share + SHARE_TOL,
        "slo_attainment_delta": (adm["slo_attainment"]
                                 - base["slo_attainment"]),
        "cost_per_query_delta": (adm["cost_per_query"]
                                 - base["cost_per_query"]),
        "quality_proxy_delta": (adm["quality_proxy"]
                                - base["quality_proxy"]),
        "passed": True,
    }
    print(f"gates PASSED: slo +{gates['slo_attainment_delta']:.4f}, "
          f"cost {gates['cost_per_query_delta']:+.6f} $/query "
          f"(budget ${budget:.6f}), quality "
          f"{gates['quality_proxy_delta']:+.2f}")
    return gates


def runner_decisions(runner) -> dict:
    return {int(t): int(c)
            for t, c in runner.session.stats.tier_counts.items()}


def csv_rows(quick: bool = True) -> list[tuple]:
    """``benchmarks.run`` harness entry: the admission-on/off pair on
    the smoke trace (full canonical trace when ``quick=False``), gates
    asserted inside."""
    pair = run_pair(SMOKE_TRACE if quick else DEFAULT_TRACE,
                    record_every=5)
    gates = check_gates(pair)
    rows: list[tuple] = []
    for label in ("baseline", "admission"):
        s = pair[label]["report"].summary
        tag = f"load_sim/{label}"
        rows.append((f"{tag}/slo_attainment", round(s["slo_attainment"], 4),
                     "completed within SLO / arrivals"))
        rows.append((f"{tag}/cost_per_query",
                     round(s["cost_per_query"], 8),
                     "$ over the executed tier mix"))
        rows.append((f"{tag}/n_spilled", s["n_spilled"],
                     "admission tier-spill demotions"))
    rows.append(("load_sim/slo_attainment_delta",
                 round(gates["slo_attainment_delta"], 4),
                 "admission - baseline (gated > 0)"))
    return rows


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="short CI trace (same gates, ~4x faster)")
    ap.add_argument("--trace", default=None,
                    help="canonical trace name (overrides --smoke choice)")
    ap.add_argument("--json", default=None,
                    help="structured-output path ('' disables; default: "
                    "repo-root BENCH_load_sim.json for full default runs)")
    ap.add_argument("--record-every", type=int, default=5,
                    help="telemetry-row thinning for the JSON trajectory")
    args = ap.parse_args()

    trace_name = args.trace or (SMOKE_TRACE if args.smoke else DEFAULT_TRACE)
    print(f"trace: {trace_name}")
    pair = run_pair(trace_name, record_every=args.record_every)
    gates = check_gates(pair)

    if args.json is not None:
        json_path = pathlib.Path(args.json) if args.json else None
    elif trace_name == DEFAULT_TRACE:
        json_path = DEFAULT_JSON     # full default run: track it
    else:
        json_path = None
    if json_path is not None:
        payload = _sanitize({
            "bench": "load_sim",
            "trace": pair["baseline"]["report"].trace,
            "gates": gates,
            "baseline": {
                "wall_s": pair["baseline"]["wall_s"],
                "summary": pair["baseline"]["report"].summary,
                "trajectory": pair["baseline"]["report"].steps,
            },
            "admission": {
                "wall_s": pair["admission"]["wall_s"],
                "summary": pair["admission"]["report"].summary,
                "trajectory": pair["admission"]["report"].steps,
            },
        })
        json_path.write_text(json.dumps(payload, indent=1, sort_keys=True)
                             + "\n")
        print(f"wrote {json_path}")


if __name__ == "__main__":
    main()
