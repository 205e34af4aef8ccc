"""Routing fast-path benchmark: per-request XLA oracle vs the `repro.api`
difficulty backends — now including the END-TO-END retrieve-to-decision
path.

The paper's pitch is that routing costs ~0.001x of a learned router; this
bench pins the serving-side realization. Two sections:

metric path (scores already retrieved)
  oracle/per-request : the seed serving path — one `skewness.difficulty`
                       jit call + threshold compare PER REQUEST.
  backend/batched    : the `repro.api` difficulty backend (``--backend
                       auto`` = the batch-size crossover: single-program
                       XLA oracle below ``crossover_batch``, fused Pallas
                       kernels above; interpret mode off-TPU) — ONE
                       device program for the whole batch.

end-to-end (candidate features in, tier decisions out)
  staged/per-request : the pre-fusion flow per request — XLA scoring,
                       scores back to host, numpy top-k, re-enter the
                       device for skew metrics, threshold compare.
  fused/batched      : `route_retrieved` — scoring -> top-k -> skew ->
                       decision as ONE jitted program, scores never
                       leave HBM.

Sweeps B in {1, 64, 1024} x K in {50, 100, 200} for the metric path and
B in {1, 16, 64} (N=256 candidates, K=100) end-to-end (``--smoke``: a
30-second subset) and prints ``name,value,derived`` CSV rows like
benchmarks/run.py. ``--out`` appends the rows to a CSV; full
default-config runs also write structured JSON to
``BENCH_routing_fastpath.json`` at the repo root — the perf trajectory
tracked across PRs (``--json`` overrides the path; smoke / non-default
sweeps don't touch the tracked file unless asked).

Acceptance gates (asserted when the full grid runs with the default
``auto`` backend):

* PER CELL, both sections: speedup >= 1.0 at EVERY (B, K) — the batched
  path must never lose to per-request dispatch, including B=1 (the
  regression this gate exists to catch; cells are annotated with the
  interpret mode they measured).
* headline: batched dispatch >= 5x the per-request oracle at
  B=1024, K=100.
* observability: a session with metrics + tracing ENABLED must route
  within ``OBS_OVERHEAD_MAX_RATIO`` (5%) of an obs-less session at the
  headline shape — the plane's batch-granular design is a perf contract,
  not an aspiration.

  PYTHONPATH=src python -m benchmarks.routing_fastpath_bench [--smoke]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import make_backend, resolve_backend_name
from repro.core import skewness
from repro.core.router import (RouterConfig, route_from_difficulty,
                               route_retrieved_staged)
from repro.retrieval.scorer import ScorerConfig, init_scorer, kernel_weights

FULL_GRID = {"B": (1, 64, 1024), "K": (50, 100, 200)}
SMOKE_GRID = {"B": (1, 64), "K": (50,)}
E2E_FULL = {"B": (1, 16, 64), "N": 256, "K": 100}
E2E_SMOKE = {"B": (1, 16), "N": 128, "K": 50}
GATE_SHAPE = (1024, 100)  # B, K of the headline acceptance assertion
GATE_SPEEDUP = 5.0
PER_CELL_SPEEDUP = 1.0    # every cell, both sections: never lose to
                          # per-request dispatch (the B=1 regression gate)
# Observability gate: a session with metrics + tracing ENABLED must
# dispatch within this factor of an obs-less session at the headline
# shape — the plane is batch-granular by design, so turning it on may
# not tax the fast path.
OBS_OVERHEAD_MAX_RATIO = 1.05
DEFAULT_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_routing_fastpath.json"


def _desc_scores(rng, b, k) -> np.ndarray:
    return np.sort(rng.uniform(0.01, 1, (b, k)).astype(np.float32),
                   axis=1)[:, ::-1].copy()


def _time_best_pair(fn_a, fn_b, iters: int) -> tuple[float, float]:
    """Best-of timing with the two sides INTERLEAVED (a, b, a, b, ...).
    Timing one side fully and then the other lets seconds-scale load
    drift land entirely on one side and flip a per-cell gate; alternating
    exposes both sides to the same noise windows while best-of still
    picks each side's quietest slot."""
    best_a = best_b = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn_a()
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def _cell_iters(b: int, iters: int) -> int:
    """Small batches time in microseconds — take more best-of samples so
    the per-cell >= 1.0 gate measures the path, not scheduler noise."""
    return iters if b >= 64 else max(iters, 30)


def _picked_path(backend, b: int) -> str:
    return backend.pick(b).name if hasattr(backend, "pick") else backend.name


def bench_shape(b: int, k: int, config: RouterConfig, backend,
                iters: int = 3, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    scores = _desc_scores(rng, b, k)
    thresholds = jnp.asarray(config.thresholds)

    # -- per-request oracle path (seed dispatch loop) ------------------------
    rows = [jnp.asarray(scores[i][None]) for i in range(b)]

    def per_request():
        out = []
        for row in rows:
            diff = skewness.difficulty(row, metric=config.metric,
                                       p=config.cumulative_p)
            out.append(route_from_difficulty(diff, thresholds))
        jax.block_until_ready(out)
        return out

    # -- batched backend path ------------------------------------------------
    batch = jnp.asarray(scores)

    def batched():
        res = backend.route_batch(batch, config)
        jax.block_until_ready(res.tiers)
        return res

    oracle_tiers = np.concatenate([np.asarray(t) for t in per_request()])
    kernel_tiers = np.asarray(batched().tiers)  # also warms both jits
    if not np.array_equal(oracle_tiers, kernel_tiers):
        raise AssertionError(f"path disagreement at B={b} K={k}")

    it = _cell_iters(b, iters)
    t_oracle, t_kernel = _time_best_pair(per_request, batched, it)
    return {
        "B": b, "K": k,
        "oracle_s": t_oracle, "kernel_s": t_kernel,
        "oracle_qps": b / t_oracle, "kernel_qps": b / t_kernel,
        "speedup": t_oracle / t_kernel,
        "path": _picked_path(backend, b),
        "interpret": bool(getattr(backend, "effective_interpret",
                                  lambda: jax.default_backend() != "tpu")()),
    }


def bench_e2e_shape(b: int, n: int, k: int, config: RouterConfig, backend,
                    params, d_triple: int, d_query: int,
                    iters: int = 3, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    feats = rng.normal(size=(b, n, d_triple)).astype(np.float32) * 0.3
    qemb = rng.normal(size=(b, d_query)).astype(np.float32)
    weights = kernel_weights(params)
    thresholds = jnp.asarray(config.thresholds)

    # -- staged per-request path (the pre-fusion production flow) ------------
    from repro.kernels.triple_score.ref import triple_score_ref
    score_one = jax.jit(lambda f, q: triple_score_ref(f, q[None], *weights)[0])

    def staged():
        tiers = []
        for i in range(b):
            logits = np.asarray(score_one(feats[i], qemb[i]))   # host hop
            order = np.argsort(-logits)[:k]                     # host top-k
            probs = 1.0 / (1.0 + np.exp(-logits[order]))
            diff = skewness.difficulty(jnp.asarray(probs[None]),  # re-enter
                                       metric=config.metric,
                                       p=config.cumulative_p)
            tiers.append(route_from_difficulty(diff, thresholds))
        jax.block_until_ready(tiers)
        return np.concatenate([np.asarray(t) for t in tiers])

    # -- fused device program ------------------------------------------------
    jf, jq = jnp.asarray(feats), jnp.asarray(qemb)

    def fused():
        res = backend.route_retrieved(jf, jq, params, config)
        jax.block_until_ready(res.tiers)
        return res

    staged_tiers = staged()
    fused_tiers = np.asarray(fused().tiers)  # warms the jits
    if not np.array_equal(staged_tiers, fused_tiers):
        raise AssertionError(f"end-to-end path disagreement at B={b} N={n}")

    it = _cell_iters(b, iters)
    t_staged, t_fused = _time_best_pair(staged, fused, it)
    return {
        "B": b, "N": n, "K": k,
        "staged_s": t_staged, "fused_s": t_fused,
        "staged_qps": b / t_staged, "fused_qps": b / t_fused,
        "speedup": t_staged / t_fused,
        "path": _picked_path(backend, b),
        "interpret": bool(getattr(backend, "effective_interpret",
                                  lambda: jax.default_backend() != "tpu")()),
    }


def bench_obs_overhead(b: int, k: int, metric: str = "entropy",
                       iters: int = 3, seed: int = 0) -> dict:
    """Full ``session.route`` with the observability plane enabled vs the
    NULL_OBS default, interleaved best-of — the events/instruments are
    batch-granular, so enabling them must stay within
    ``OBS_OVERHEAD_MAX_RATIO`` of disabled at the headline shape."""
    from repro.api import RouteSpec, build
    from repro.obs import Observability
    rng = np.random.default_rng(seed)
    scores = _desc_scores(rng, b, k)
    spec = RouteSpec(metric=metric, thresholds=(5.0,), top_k=k,
                     tier_names=("qwen7b", "qwen72b"))
    s_off = build(spec)
    s_on = build(spec, obs=Observability())

    def off():
        return s_off.route(scores)

    def on():
        return s_on.route(scores)

    if not np.array_equal(np.asarray(off().tiers),
                          np.asarray(on().tiers)):   # also warms both jits
        raise AssertionError(f"obs-on routing diverged at B={b} K={k}")
    # A 5% gate on a ~6ms call needs a deeper best-of than the speedup
    # cells (which clear by 10-70x): sub-gate noise would flake it.
    it = max(_cell_iters(b, iters), 15)
    t_off, t_on = _time_best_pair(off, on, it)
    ratio = t_on / t_off
    return {
        "B": b, "K": k,
        "obs_off_s": t_off, "obs_on_s": t_on,
        "ratio": round(ratio, 4),
        "max_ratio": OBS_OVERHEAD_MAX_RATIO,
        "n_events": len(s_on.obs.tracer),
        "passed": ratio <= OBS_OVERHEAD_MAX_RATIO,
    }


def run(grid: dict, iters: int = 3, metric: str = "entropy",
        backend_name: str = "auto") -> tuple[list[tuple], dict]:
    """Metric-path sweep. Returns (csv_rows, results keyed by (B, K))."""
    config = RouterConfig(metric=metric, thresholds=(5.0,))
    backend = make_backend(backend_name)
    rows: list[tuple] = []
    results: dict = {}
    for k in grid["K"]:
        for b in grid["B"]:
            r = bench_shape(b, k, config, backend, iters=iters)
            results[(b, k)] = r
            tag = f"fastpath/B{b}_K{k}"
            rows.append((f"{tag}/oracle_qps", round(r["oracle_qps"], 1),
                         "per-request XLA oracle dispatch"))
            rows.append((f"{tag}/kernel_qps", round(r["kernel_qps"], 1),
                         f"fused batched dispatch ({backend.name} backend, "
                         f"{r['path']} path)"))
            rows.append((f"{tag}/speedup", round(r["speedup"], 2),
                         "kernel_qps / oracle_qps"))
    return rows, results


def run_e2e(e2e: dict, iters: int = 3, metric: str = "entropy",
            backend_name: str = "auto",
            seed: int = 0) -> tuple[list[tuple], dict]:
    """End-to-end sweep (retrieval scoring -> decision)."""
    n, k = e2e["N"], e2e["K"]
    config = RouterConfig(metric=metric, thresholds=(5.0,), top_k=k)
    backend = make_backend(backend_name)
    cfg = ScorerConfig()
    params = init_scorer(jax.random.key(seed), cfg)
    rows: list[tuple] = []
    results: dict = {}
    for b in e2e["B"]:
        r = bench_e2e_shape(b, n, k, config, backend, params,
                            cfg.d_triple, cfg.d_query, iters=iters)
        results[(b, k)] = r
        tag = f"fastpath_e2e/B{b}_N{n}_K{k}"
        rows.append((f"{tag}/staged_qps", round(r["staged_qps"], 1),
                     "per-request staged host path (score/top-k/skew)"))
        rows.append((f"{tag}/fused_qps", round(r["fused_qps"], 1),
                     f"one-program retrieve-to-decision ({backend.name} "
                     f"backend, {r['path']} path)"))
        rows.append((f"{tag}/speedup", round(r["speedup"], 2),
                     "fused_qps / staged_qps"))
    return rows, results


def _per_cell_gate(results: dict, section: str) -> list[dict]:
    """Every measured cell must clear PER_CELL_SPEEDUP — a regression in
    ANY cell (the seed silently recorded B=1 losses) fails the bench
    instead of just being written to JSON."""
    cells = []
    for r in results.values():
        cells.append({
            "section": section,
            "B": r["B"], "K": r["K"],
            "speedup": round(r["speedup"], 2),
            "required_speedup": PER_CELL_SPEEDUP,
            "interpret": r["interpret"],
            "path": r["path"],
            "passed": r["speedup"] >= PER_CELL_SPEEDUP,
        })
    return cells


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI (no acceptance gates)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--metric", default="entropy",
                    choices=["area", "cumulative", "entropy", "gini"])
    ap.add_argument("--backend", default="auto",
                    help="repro.api difficulty backend for the batched "
                         "path (auto | fused | pallas | oracle | "
                         "registered name)")
    ap.add_argument("--skip-e2e", action="store_true",
                    help="metric-path section only")
    ap.add_argument("--out", default=None,
                    help="append CSV rows to this file (perf trajectory)")
    ap.add_argument("--json", default=None,
                    help="write structured results JSON here ('' disables); "
                         "defaults to BENCH_routing_fastpath.json at the "
                         "repo root for full default-config runs only, so "
                         "smoke / non-default sweeps never clobber the "
                         "tracked perf trajectory")
    args = ap.parse_args()

    json_path = args.json
    if json_path is None:
        trajectory_run = (not args.smoke and args.metric == "entropy"
                          and args.backend == "auto" and not args.skip_e2e
                          and args.iters == ap.get_default("iters"))
        json_path = str(DEFAULT_JSON) if trajectory_run else ""

    grid = SMOKE_GRID if args.smoke else FULL_GRID
    e2e_grid = E2E_SMOKE if args.smoke else E2E_FULL
    t0 = time.monotonic()
    rows, results = run(grid, iters=args.iters, metric=args.metric,
                        backend_name=args.backend)
    e2e_results: dict = {}
    if not args.skip_e2e:
        e2e_rows, e2e_results = run_e2e(e2e_grid, iters=args.iters,
                                        metric=args.metric,
                                        backend_name=args.backend)
        rows.extend(e2e_rows)
    obs_overhead = None
    if not args.smoke:
        gb, gk = GATE_SHAPE
        obs_overhead = bench_obs_overhead(gb, gk, metric=args.metric,
                                          iters=args.iters)
        tag = f"fastpath_obs/B{gb}_K{gk}"
        rows.append((f"{tag}/ratio", obs_overhead["ratio"],
                     "obs-enabled session.route / obs-off (gate <= "
                     f"{OBS_OVERHEAD_MAX_RATIO})"))
        rows.append((f"{tag}/obs_on_qps",
                     round(gb / obs_overhead["obs_on_s"], 1),
                     "full route() with metrics+tracing enabled"))
    wall = time.monotonic() - t0
    rows.append(("fastpath/wall_s", round(wall, 1), "total bench wall time"))

    for name, value, derived in rows:
        print(f"{name},{value},{derived}")

    if args.out:
        with open(args.out, "a") as f:
            for name, value, derived in rows:
                f.write(f"{name},{value},{derived}\n")

    gate = None
    if GATE_SHAPE in results:
        speedup = results[GATE_SHAPE]["speedup"]
        gate = {"shape": list(GATE_SHAPE),
                "required_speedup": GATE_SPEEDUP,
                "speedup": round(speedup, 2),
                "passed": speedup >= GATE_SPEEDUP}

    # per-cell gate: only meaningful (and only asserted) for the full grid
    # under the crossover-aware default backend
    cells = None
    if not args.smoke and args.backend == "auto":
        cells = (_per_cell_gate(results, "metric_path")
                 + _per_cell_gate(e2e_results, "end_to_end"))

    if json_path:
        backend = make_backend(args.backend)
        payload = {
            "bench": "routing_fastpath",
            "metric": args.metric,
            "backend": {
                "requested": args.backend,
                "resolved": resolve_backend_name(args.backend),
                "crossover_batch": getattr(backend, "crossover_batch", None),
                "interpret": bool(getattr(
                    backend, "effective_interpret",
                    lambda: jax.default_backend() != "tpu")()),
                "jax_backend": jax.default_backend(),
            },
            "grid": {"B": list(grid["B"]), "K": list(grid["K"])},
            "results": [results[(b, k)]
                        for k in grid["K"] for b in grid["B"]],
            "end_to_end": {
                "grid": {"B": list(e2e_grid["B"]), "N": e2e_grid["N"],
                         "K": e2e_grid["K"]},
                "results": [e2e_results[key] for key in sorted(e2e_results)],
            } if e2e_results else None,
            "gate": gate,
            "per_cell_gate": cells,
            "obs_overhead": obs_overhead,
            "smoke": args.smoke,
            "iters": args.iters,
            "wall_s": round(wall, 1),
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {json_path}")

    if cells is not None:
        losing = [c for c in cells if not c["passed"]]
        assert not losing, (
            f"batched dispatch lost to per-request dispatch at "
            f"{[(c['section'], c['B'], c['K']) for c in losing]} "
            f"(per-cell acceptance: >= {PER_CELL_SPEEDUP}x; the auto "
            f"crossover exists precisely so B=1 never regresses)")
        print(f"ACCEPT: all {len(cells)} cells >= {PER_CELL_SPEEDUP}x "
              f"per-request dispatch (both sections)")
    if gate is not None:
        assert gate["passed"], (
            f"batched kernel dispatch only {gate['speedup']:.1f}x the "
            f"per-request oracle at B={GATE_SHAPE[0]} K={GATE_SHAPE[1]} "
            f"(acceptance: >= {GATE_SPEEDUP}x)")
        print(f"ACCEPT: batched fast path {gate['speedup']:.1f}x "
              f"per-request oracle at B={GATE_SHAPE[0]}, K={GATE_SHAPE[1]}")
    if obs_overhead is not None:
        assert obs_overhead["passed"], (
            f"observability-enabled dispatch is "
            f"{obs_overhead['ratio']:.3f}x the obs-off session at "
            f"B={GATE_SHAPE[0]} K={GATE_SHAPE[1]} (acceptance: <= "
            f"{OBS_OVERHEAD_MAX_RATIO}x — the plane must stay "
            f"batch-granular on the hot path)")
        print(f"ACCEPT: metrics+tracing overhead {obs_overhead['ratio']:.3f}x"
              f" (<= {OBS_OVERHEAD_MAX_RATIO}x) at "
              f"B={GATE_SHAPE[0]}, K={GATE_SHAPE[1]}")


if __name__ == "__main__":
    main()
