"""Production serving driver: SkewRoute-fronted multi-tier LM fleet.

  PYTHONPATH=src python -m repro.launch.serve --requests 40 [--budget 0.4]

Runs the paper's deployment shape end to end on small-config tiers:
retrieval scoring -> declarative `repro.api.RouteSpec` -> one
`SkewRouteSession` (fused skew metrics, calibrated threshold routing,
drift-aware streaming recalibration, per-tier micro-batch queues) ->
engines generating real tokens, with cost/latency telemetry. The policy
is pure data: the driver prints the spec JSON a replica would need to
run the identical router.
"""

from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--budget", type=float, default=0.4,
                    help="target large-tier call ratio")
    ap.add_argument("--metric", default="gini",
                    choices=["area", "cumulative", "entropy", "gini"])
    args = ap.parse_args()

    from repro.api import CalibrationSpec, RouteSpec, build
    from repro.core import calibrate_threshold
    from repro.models.layers import LMConfig
    from repro.retrieval import scorer as sc
    from repro.retrieval import synthetic
    from repro.serving.engine import EngineBank, make_engine

    print("== retrieval stack ==")
    data = synthetic.make_dataset("cwq", n_queries=args.requests + 100,
                                  n_entities=4000)
    cfg = sc.ScorerConfig(lr=2e-3)
    params = sc.train_scorer(data, cfg, n_steps=150)

    calib, calib_nv = [], []
    for q in data.queries[: 100]:
        _, probs = sc.retrieve(params, data.kg, data.entity_emb,
                               data.relation_emb, q, cfg)
        calib.append(np.pad(probs[:100], (0, max(0, 100 - len(probs)))))
        calib_nv.append(min(len(probs), 100))
    calib_nv = np.asarray(calib_nv, np.int32)
    # ragged retrieval: calibrate on the same masked metrics dispatch uses
    calib_mask = np.arange(100)[None, :] < calib_nv[:, None]
    theta = calibrate_threshold(jnp.asarray(np.stack(calib)), args.budget,
                                args.metric, mask=jnp.asarray(calib_mask))

    # the WHOLE policy, declaratively — ship spec.to_json() to replicas
    spec = RouteSpec(
        metric=args.metric, thresholds=(theta,),
        tier_names=("qwen7b", "qwen72b"),
        backend="auto", micro_batch=8,
        calibration=CalibrationSpec(
            policy="streaming",
            target_shares=(1.0 - args.budget, args.budget),
            window=1024, min_samples=64))
    print(f"{args.metric} threshold {theta:.4f} for {args.budget:.0%} budget")
    print(f"policy: {spec.to_json()}")

    print("== tier engines ==")
    bank = EngineBank({
        0: make_engine(LMConfig(name="small-tier", n_layers=2, d_model=64,
                                n_heads=4, n_kv_heads=2, head_dim=16,
                                d_ff=128, vocab=512, dtype=jnp.float32)),
        1: make_engine(LMConfig(name="large-tier", n_layers=4, d_model=128,
                                n_heads=8, n_kv_heads=4, head_dim=16,
                                d_ff=256, vocab=512, dtype=jnp.float32)),
    }, max_new=8)
    session = build(spec, runners=bank)

    t0 = time.monotonic()
    batch_scores, batch_nv, batch_prompts = [], [], []
    for q in data.queries[100: 100 + args.requests]:
        _, probs = sc.retrieve(params, data.kg, data.entity_emb,
                               data.relation_emb, q, cfg)
        batch_scores.append(np.pad(probs[:100], (0, max(0, 100 - len(probs)))))
        batch_nv.append(min(len(probs), 100))  # ragged: pad is NOT data
        batch_prompts.append(
            np.abs(np.frombuffer(q.query_emb.tobytes(), np.uint8)[:16])
            .astype(np.int32) % 512)
        if len(batch_scores) == 16:  # request-batch granularity of dispatch
            session.submit(np.stack(batch_scores), batch_prompts,
                           n_valid=np.asarray(batch_nv, np.int32))
            batch_scores, batch_nv, batch_prompts = [], [], []
    if batch_scores:
        session.submit(np.stack(batch_scores), batch_prompts,
                       n_valid=np.asarray(batch_nv, np.int32))
    session.flush()
    wall = time.monotonic() - t0

    generated = sum(b.result.generated_tokens for b in session.executed)
    s = session.stats
    from repro.core.cost import CostModel
    cm = CostModel()
    all_large = cm.request_cost("qwen72b") * s.n_requests
    n_micro = session.telemetry()["pipeline"]["n_microbatches"]
    print(f"\nserved {s.n_requests} requests / {generated} tokens in "
          f"{wall:.1f}s over {n_micro} micro-batches; "
          f"tier mix {s.tier_counts} (large ratio {s.large_call_ratio:.2f}); "
          f"{s.n_recalibrations} drift recalibrations")
    print(f"est. cost ${s.total_cost:.4f} vs all-large ${all_large:.4f} "
          f"({100 * (1 - s.total_cost / all_large):.0f}% saved)")
    # hand-off artifact: this session's live state, as a policy/state
    # envelope (the state half alone is what replica sync ships)
    snap = session.snapshot()
    state = snap["state"]
    cal_state = state["calibrator"] or {"window": {"buffer": []}}
    print(f"snapshot envelope v{snap['envelope_version']}: "
          f"thresholds={state['thresholds']}, "
          f"{len(cal_state['window']['buffer'])} window samples — "
          f"restorable via SkewRouteSession.from_snapshot")


if __name__ == "__main__":
    main()
