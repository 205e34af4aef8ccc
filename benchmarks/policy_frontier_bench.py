"""Routing-policy frontier benchmark: every registered policy on the
same canonical drift trace, on one cost/quality plane.

Replays the canonical drift workload (seeded arrival + score-skew drift;
``repro.serving.loadgen.workload.CANONICAL_TRACES``) through ROUTE-ONLY
sessions — one per registered routing policy — and places each on the
($/query, quality-proxy) frontier. No replica pools: this bench isolates
the DECISION economics (which tier, which depth, which mode, at what
prompt price) from queueing effects, which ``load_sim_bench`` covers.

Hardness model (the part a share-weighted proxy cannot express): a
seeded latent ``needs_big`` bit per query — true for the hardest ~15%
by fused difficulty, with 5% label noise so skew correlates with but
does not determine hardness — plus a near-noiseless engine self-score
observing it (3% miss / 3% false-alarm). Quality per query is
hardness-aware, per paper Fig 4's reading: EASY queries score the top
model's paper CWQ F1 at ANY tier (both models answer them equally
well — extra escalation buys nothing), while a ``needs_big`` query
scores top-tier F1 only if it FINISHES on the top tier and a flat
collapse penalty otherwise. The same rule prices every policy.

Why cascade can dominate the single threshold here: the threshold
policy must buy the top tier for a fixed SHARE of traffic (30% at the
canonical calibration) chosen blind to hardness, so it both overpays
(easy queries above the cut) and still misses the hard queries below
it. The cascade escalates on calibrated difficulty OR the self-score,
so it buys the expensive tier for roughly P(needs_big) of traffic —
below the ~27.5% cost-crossover at paper pricing — while catching the
hard queries the threshold's skew cut misses.

Acceptance gates (asserted on every run, smoke included):

* cascade is STRICTLY cheaper per query than the single-threshold
  baseline at EQUAL-OR-BETTER hardness-aware quality;
* cascade's realized escalation rate stays below the analytic cost
  crossover for the paper's price pair;
* adaptive_depth prices below the full-depth threshold baseline (it
  routes identical tiers on strictly shorter prompts).

Full runs (default trace, no --smoke) write structured JSON to
``BENCH_policy_frontier.json`` at the repo root — the policy-frontier
trajectory tracked across PRs (``--json`` overrides, ``--json ''``
disables; smoke runs don't touch the tracked file unless asked).

  PYTHONPATH=src python -m benchmarks.policy_frontier_bench [--smoke]
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import time

import numpy as np

from repro.core.cost import PAPER_QUALITY

DEFAULT_TRACE = "bursty_drift_saturation"
SMOKE_TRACE = "smoke"
DEFAULT_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_policy_frontier.json"

# Hardness model: hardest ~12% by difficulty are latently hard, with 4%
# label noise; the engine self-score observes the latent bit at 97%.
HARD_QUANTILE = 0.88
LABEL_FLIP = 0.04
SELF_SCORE_ERR = 0.03
MISS_PENALTY = 15.0      # F1 points a hard query loses below the top tier
NO_RAG_PENALTY = 3.0     # F1 points for answering without any context
WARMUP_FRAC = 0.3        # calibration warmup share of the trace
HARDNESS_SEED = 20250808


def _sanitize(x):
    """nan/inf -> None so the tracked JSON stays strictly parseable."""
    if isinstance(x, float):
        return x if math.isfinite(x) else None
    if isinstance(x, dict):
        return {k: _sanitize(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_sanitize(v) for v in x]
    return x


def trace_batches(trace_name: str) -> list[np.ndarray]:
    from repro.serving.loadgen import canonical_trace, generate
    trace = canonical_trace(trace_name)
    return [w.scores for w in generate(trace) if w.n_arrivals], trace


def probe_difficulty(batches: list[np.ndarray], top_k: int) -> np.ndarray:
    """Per-request fused difficulty under a static probe session (no
    calibration — difficulty is threshold-independent)."""
    from repro.api import RouteSpec, build
    session = build(RouteSpec(metric="entropy", thresholds=(6.0,),
                              top_k=top_k,
                              tier_names=("qwen7b", "qwen72b")))
    return np.concatenate([np.asarray(session.route(b).difficulty)
                           for b in batches])


def hardness_model(difficulty: np.ndarray, seed: int = HARDNESS_SEED):
    """(needs_big, self_scores): the latent hard bit + its noisy engine
    observation, both seeded so every policy prices the same queries."""
    rng = np.random.default_rng(seed)
    cut = np.quantile(difficulty, HARD_QUANTILE)
    needs_big = difficulty > cut
    flip = rng.random(needs_big.size) < LABEL_FLIP
    needs_big = needs_big ^ flip
    observed = needs_big ^ (rng.random(needs_big.size) < SELF_SCORE_ERR)
    self_scores = np.where(observed,
                           rng.uniform(0.70, 1.00, needs_big.size),
                           rng.uniform(0.00, 0.30, needs_big.size))
    return needs_big, self_scores.astype(np.float32)


def policy_sessions(top_k: int) -> dict:
    """{name: (RouteSpec, uses_self_scores)} — the contenders."""
    from repro.api import (AdaptiveDepthPolicySpec, CalibrationSpec,
                           CascadePolicySpec, ModeSelectPolicySpec,
                           RouteSpec)
    cal = CalibrationSpec(policy="streaming", target_shares=(0.7, 0.3),
                          window=512, min_samples=64, tolerance=0.08,
                          cooldown=128)
    two = dict(metric="entropy", thresholds=(6.0,), top_k=top_k,
               tier_names=("qwen7b", "qwen72b"), calibration=cal)
    opts = tuple(sorted({max(1, top_k // 4), max(2, top_k // 2), top_k}))
    return {
        "threshold": (RouteSpec(**two), False),
        "cascade": (RouteSpec(**two, policy=CascadePolicySpec(
            escalation_cutoffs=(6.5,),
            # lax difficulty cut (hardest 5% escalate unconditionally);
            # the self-score catches the hard queries below it
            escalation_quantiles=(0.95,),
            self_score_cutoff=0.5)), True),
        "adaptive_depth": (RouteSpec(**two, policy=AdaptiveDepthPolicySpec(
            depth_options=opts,
            depth_cutoffs=tuple(5.0 + 1.5 * i
                                for i in range(len(opts) - 1)),
            depth_quantiles=tuple((i + 1) / len(opts)
                                  for i in range(len(opts) - 1)))), False),
        "mode_select": (RouteSpec(
            metric="entropy", thresholds=(5.0, 6.5), top_k=top_k,
            tier_names=("qwen7b", "qwen14b", "qwen72b"),
            calibration=CalibrationSpec(
                policy="streaming", target_shares=(0.4, 0.35, 0.25),
                window=512, min_samples=64, tolerance=0.08, cooldown=128),
            policy=ModeSelectPolicySpec(
                modes=("no_rag", "kg_rag", "kg_rag"))), False),
    }


def run_policy(name: str, spec, uses_self_scores: bool,
               batches: list[np.ndarray], needs_big: np.ndarray,
               self_scores: np.ndarray) -> dict:
    """Warmup (calibration + policy refit) then measure cost/quality."""
    from repro.api import build
    session = build(spec)
    models = spec.models()
    cost_model = spec.cost_model()
    tier_cost = np.asarray([cost_model.request_cost(m)
                            if m in cost_model.cost_per_mtok else 0.0
                            for m in models])
    f1 = PAPER_QUALITY["cwq"]
    tier_f1 = np.asarray([float(f1[m]["f1"]) if m in f1 else 40.0
                          for m in models])
    top = len(models) - 1
    modes = getattr(spec.policy, "modes", None)

    n_warm = max(1, int(WARMUP_FRAC * len(batches)))
    cost_total, qual_total, n_meas, n_missed_hard = 0.0, 0.0, 0, 0
    t0, i0 = time.perf_counter(), 0
    for bi, scores in enumerate(batches):
        n = scores.shape[0]
        ss = self_scores[i0:i0 + n] if uses_self_scores else None
        res = session.route(scores, self_scores=ss)
        if bi == n_warm - 1:
            # end of warmup: force one policy refit from the calibrator
            # window so data-dependent cutoffs enter measurement fitted
            session.dispatcher.apply_config(session.dispatcher.router)
        elif bi >= n_warm:
            tiers = np.asarray(res.tiers)
            cost = (np.asarray(res.request_cost)
                    if res.request_cost is not None else tier_cost[tiers])
            nb = needs_big[i0:i0 + n]
            # hardness-aware proxy: easy queries score top-tier F1 at
            # any tier; hard queries collapse unless finished on top
            q = np.full(n, tier_f1[top])
            if modes is not None:
                q = q - NO_RAG_PENALTY * (
                    np.asarray(modes)[tiers] == "no_rag")
            missed = nb & (tiers < top)
            q[missed] = tier_f1[0] - MISS_PENALTY
            cost_total += float(cost.sum())
            qual_total += float(q.sum())
            n_meas += n
            n_missed_hard += int(missed.sum())
        i0 += n
    out = {
        "policy": name,
        "cost_per_query": cost_total / max(n_meas, 1),
        "quality_proxy": qual_total / max(n_meas, 1),
        "n_measured": n_meas,
        "hard_miss_rate": n_missed_hard / max(n_meas, 1),
        "wall_s": time.perf_counter() - t0,
        "telemetry": session.policy.telemetry(),
    }
    print(f"{name:15s} $/query={out['cost_per_query']:.6f}  "
          f"quality={out['quality_proxy']:.2f}  "
          f"hard_miss={out['hard_miss_rate']:.4f}  "
          f"wall={out['wall_s']:.1f}s")
    return out


def escalation_crossovers(spec, base_cost: float) -> tuple[float, float]:
    """Cascade-vs-threshold cost crossovers for the 2-tier paper price
    pair: cascade (always pay tier-0, pay tier-1 on escalation) is
    cheaper iff its escalation rate e satisfies c0 + e*c1 < baseline
    $/query. Returns (analytic, realized): analytic assumes the
    canonical 70/30 split exactly; realized uses the baseline's actual
    measured $/query (the calibrator chases 30% but drifts between
    swaps), which is the number cost dominance is literally gated on."""
    cm = spec.cost_model()
    c0, c1 = (cm.request_cost(m) for m in spec.models())
    return (0.3 * c1 - 0.3 * c0) / c1, (base_cost - c0) / c1


def check_gates(rows: dict, specs: dict) -> dict:
    base, casc = rows["threshold"], rows["cascade"]
    analytic, realized = escalation_crossovers(specs["threshold"][0],
                                               base["cost_per_query"])
    esc_rate = casc["telemetry"]["escalation_rate"]

    assert casc["cost_per_query"] < base["cost_per_query"], (
        f"cascade (${casc['cost_per_query']:.6f}/query) is not strictly "
        f"cheaper than the threshold baseline "
        f"(${base['cost_per_query']:.6f}/query)")
    assert casc["quality_proxy"] >= base["quality_proxy"], (
        f"cascade quality {casc['quality_proxy']:.2f} fell below the "
        f"threshold baseline {base['quality_proxy']:.2f} — dominance "
        f"requires equal-or-better quality at lower cost")
    assert esc_rate < realized, (
        f"cascade escalation rate {esc_rate:.4f} is not below the "
        f"realized cost crossover {realized:.4f}")
    assert rows["adaptive_depth"]["cost_per_query"] \
        < base["cost_per_query"], (
        "adaptive_depth did not price below the full-depth baseline")
    for r in rows.values():
        assert r["cost_per_query"] > 0, f"{r['policy']} priced at zero"

    gates = {
        "cascade_cost_delta": (casc["cost_per_query"]
                               - base["cost_per_query"]),
        "cascade_quality_delta": (casc["quality_proxy"]
                                  - base["quality_proxy"]),
        "escalation_rate": esc_rate,
        "escalation_crossover_analytic": analytic,
        "escalation_crossover_realized": realized,
        "passed": True,
    }
    print(f"gates PASSED: cascade {gates['cascade_cost_delta']:+.6f} "
          f"$/query, quality {gates['cascade_quality_delta']:+.2f}, "
          f"escalation {esc_rate:.4f} < crossover {realized:.4f} "
          f"(analytic {analytic:.4f})")
    return gates


def run_frontier(trace_name: str) -> tuple[dict, dict, dict]:
    """(rows, gates, meta): the full bench minus I/O — shared by
    ``main`` and the ``benchmarks.run`` harness registration."""
    batches, trace = trace_batches(trace_name)
    difficulty = probe_difficulty(batches, trace.top_k)
    needs_big, self_scores = hardness_model(difficulty)
    print(f"{difficulty.size} queries, "
          f"P(needs_big)={needs_big.mean():.4f}")
    specs = policy_sessions(trace.top_k)
    rows = {name: run_policy(name, spec, uses_ss, batches,
                             needs_big, self_scores)
            for name, (spec, uses_ss) in specs.items()}
    gates = check_gates(rows, specs)
    meta = {"trace": trace.to_dict(),
            "p_needs_big": float(needs_big.mean())}
    return rows, gates, meta


def csv_rows(quick: bool = False) -> list[tuple]:
    """``benchmarks.run`` harness entry: one CSV row per policy on the
    canonical drift trace (gates asserted inside)."""
    rows, gates, _ = run_frontier(SMOKE_TRACE if quick else DEFAULT_TRACE)
    out = []
    for name, r in rows.items():
        out.append((f"policy_frontier/{name}/cost_per_query",
                    round(r["cost_per_query"], 8), "$ at paper pricing"))
        out.append((f"policy_frontier/{name}/quality_proxy",
                    round(r["quality_proxy"], 3), "hardness-aware F1"))
    out.append(("policy_frontier/cascade_cost_delta",
                round(gates["cascade_cost_delta"], 8),
                "cascade - threshold, $/query (gated < 0)"))
    return out


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="short CI trace (same gates, much faster)")
    ap.add_argument("--trace", default=None,
                    help="canonical trace name (overrides --smoke choice)")
    ap.add_argument("--json", default=None,
                    help="structured-output path ('' disables; default: "
                    "repo-root BENCH_policy_frontier.json for full "
                    "default runs)")
    args = ap.parse_args()

    trace_name = args.trace or (SMOKE_TRACE if args.smoke else DEFAULT_TRACE)
    print(f"trace: {trace_name}")
    rows, gates, meta = run_frontier(trace_name)

    if args.json is not None:
        json_path = pathlib.Path(args.json) if args.json else None
    elif trace_name == DEFAULT_TRACE:
        json_path = DEFAULT_JSON     # full default run: track it
    else:
        json_path = None
    if json_path is not None:
        payload = _sanitize({
            "bench": "policy_frontier",
            "trace": meta["trace"],
            "hardness": {"hard_quantile": HARD_QUANTILE,
                         "label_flip": LABEL_FLIP,
                         "self_score_err": SELF_SCORE_ERR,
                         "miss_penalty": MISS_PENALTY,
                         "no_rag_penalty": NO_RAG_PENALTY,
                         "p_needs_big": meta["p_needs_big"],
                         "seed": HARDNESS_SEED},
            "frontier": list(rows.values()),
            "gates": gates,
        })
        json_path.write_text(json.dumps(payload, indent=1, sort_keys=True)
                             + "\n")
        print(f"wrote {json_path}")


if __name__ == "__main__":
    main()
