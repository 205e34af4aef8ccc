"""Replica-fabric convergence benchmark: sync on vs. off, same fleet,
same biased traffic.

Replays a canonical drift trace through TWO identical fleets of routing
replicas. A sticky load balancer is simulated by sorting each step's
arrivals by their top retrieval score and handing each replica a
contiguous slice — replica 0 sees the easiest traffic, the last replica
the hardest, exactly the per-replica bias that makes independent
streaming calibration walk the fleet apart. One fleet exchanges
calibrator deltas through :class:`repro.serving.ReplicaFabric` every
``--sync-every`` steps; the other runs the identical sessions with no
exchange. Mid-run a cold replica joins BOTH fleets (bootstrapped from
replica 0's snapshot state-half in each, so the comparison isolates
ongoing sync, not initial state) and takes over a slice of traffic.

Convergence is measured on a fixed HOLDOUT batch drawn from the whole
trace's score distribution: a replica's "expensive-tier share" is the
fraction of holdout rows its current thresholds would send to the top
tier — i.e. how the replica would route *global* traffic, which is the
quantity per-slice calibration silently distorts.

Acceptance gates (asserted on every run, smoke included):

* the sync-enabled fleet ends with every replica's expensive-tier
  holdout share within ``SPREAD_GATE`` (2 percentage points) of every
  other's — including the mid-run cold joiner;
* the sync-disabled fleet ends measurably diverged: spread above
  ``SPREAD_GATE`` and above the sync fleet's;
* the cold replica converges (within ``SPREAD_GATE`` of the fleet mean)
  in at most ``COLD_ROUND_BOUND`` sync rounds after joining;
* sync bandwidth: the int8 delta compression beats raw f32 on the wire.

Full runs (default trace, no --smoke) also write structured JSON to
``BENCH_fabric_sync.json`` at the repo root — the fleet-consistency
trajectory tracked across PRs (``--json`` overrides the path, ``--json
''`` disables writing).

  PYTHONPATH=src python -m benchmarks.fabric_sync_bench [--smoke]
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import time

import numpy as np

from repro.api import CalibrationSpec, RouteSpec, build, make_backend
from repro.core.router import RouterConfig
from repro.serving import ReplicaFabric
from repro.serving.loadgen import canonical_trace, generate

DEFAULT_TRACE = "bursty_drift_saturation"
SMOKE_TRACE = "smoke"
N_REPLICAS = 3          # before the cold join
SYNC_EVERY = 10         # steps between fabric rounds
JOIN_AT_FRAC = 0.6      # cold replica joins at this fraction of the trace
SPREAD_GATE = 0.02      # max - min expensive-tier holdout share, 2 pp
COLD_ROUND_BOUND = 3    # sync rounds the cold joiner gets to converge in
HOLDOUT_ROWS = 2048
DEFAULT_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_fabric_sync.json"


def fleet_spec(trace) -> RouteSpec:
    """One policy for the whole fleet: entropy routing at the trace's
    retrieval depth, 70/30 streaming calibration."""
    return RouteSpec(
        metric="entropy", thresholds=(0.8 * math.log2(trace.top_k),),
        top_k=trace.top_k, tier_names=("qwen7b", "qwen72b"),
        calibration=CalibrationSpec(policy="streaming",
                                    target_shares=(0.7, 0.3), window=512,
                                    min_samples=64, tolerance=0.08,
                                    cooldown=128))


def holdout_difficulty(trace, spec) -> np.ndarray:
    """Difficulty of a fixed global-traffic sample: every score row the
    trace emits, subsampled to HOLDOUT_ROWS with a fixed rng. Difficulty
    is threshold-independent, so this is computed exactly once."""
    rows = [step.scores for step in generate(trace) if step.n_arrivals]
    all_rows = np.concatenate(rows)
    rng = np.random.default_rng(0)
    pick = rng.choice(len(all_rows), min(HOLDOUT_ROWS, len(all_rows)),
                      replace=False)
    cfg = RouterConfig(metric=spec.metric, thresholds=spec.thresholds,
                       top_k=spec.top_k)
    res = make_backend("auto").route_batch(all_rows[pick], cfg)
    return np.asarray(res.difficulty)


def expensive_share(difficulty: np.ndarray, thresholds) -> float:
    """Fraction of the holdout a replica's thresholds send to the top
    tier (tier = #thresholds strictly below difficulty)."""
    return float(np.mean(difficulty > thresholds[-1]))


def slice_batches(scores: np.ndarray, n_slices: int) -> list[np.ndarray]:
    """The sticky load balancer: rows sorted easiest-first (spikiest top
    score) and split contiguously — slice i is replica i's biased view."""
    order = np.argsort(-scores[:, 0], kind="stable")
    return np.array_split(scores[order], n_slices)


def run_fleets(trace, spec, sync_every: int) -> dict:
    names = [f"r{i}" for i in range(N_REPLICAS)]
    fab = ReplicaFabric()
    for n in names:
        fab.add_replica(n, build(spec))
    nosync = {n: build(spec) for n in names}
    diff = holdout_difficulty(trace, spec)
    join_at = int(JOIN_AT_FRAC * trace.steps)

    shares = lambda sessions: {n: expensive_share(diff, s.thresholds)
                               for n, s in sessions.items()}
    trajectory: list[dict] = []
    cold_rounds_to_converge = None
    rounds_after_join = 0

    for step in generate(trace):
        if step.step == join_at:
            fab.add_replica("cold", build(spec), bootstrap_from="r0")
            cold = build(spec)
            cold.restore_state(json.loads(json.dumps(
                nosync["r0"].snapshot()["state"])))
            nosync["cold"] = cold
            names = names + ["cold"]
        if step.n_arrivals:
            for name, chunk in zip(names,
                                   slice_batches(step.scores, len(names))):
                if chunk.shape[0]:
                    fab.sessions[name].route(chunk)
                    nosync[name].route(chunk.copy())
        if step.step % sync_every == sync_every - 1 or \
                step.step == trace.steps - 1:
            fab.sync_round()
            sy, no = shares(fab.sessions), shares(nosync)
            trajectory.append({
                "step": step.step,
                "sync_shares": sy, "nosync_shares": no,
                "sync_spread": max(sy.values()) - min(sy.values()),
                "nosync_spread": max(no.values()) - min(no.values()),
            })
            if "cold" in sy:
                rounds_after_join += 1
                fleet_mean = np.mean([v for n, v in sy.items()
                                      if n != "cold"])
                if cold_rounds_to_converge is None and \
                        abs(sy["cold"] - fleet_mean) <= SPREAD_GATE:
                    cold_rounds_to_converge = rounds_after_join

    return {"fabric": fab, "nosync": nosync, "trajectory": trajectory,
            "cold_rounds_to_converge": cold_rounds_to_converge,
            "join_at": join_at}


def check_gates(out: dict) -> dict:
    final = out["trajectory"][-1]
    sync_spread = final["sync_spread"]
    nosync_spread = final["nosync_spread"]
    tel = out["fabric"].telemetry()

    assert sync_spread <= SPREAD_GATE, (
        f"sync fleet ended with expensive-share spread {sync_spread:.4f} "
        f"> {SPREAD_GATE} across replicas {final['sync_shares']}")
    assert nosync_spread > SPREAD_GATE, (
        f"sync-disabled fleet did not diverge: spread "
        f"{nosync_spread:.4f} <= {SPREAD_GATE} — the biased slices are "
        f"not biased enough to demonstrate anything")
    assert nosync_spread > sync_spread, (
        f"sync fleet ({sync_spread:.4f}) is no tighter than unsynced "
        f"({nosync_spread:.4f})")
    assert out["cold_rounds_to_converge"] is not None \
        and out["cold_rounds_to_converge"] <= COLD_ROUND_BOUND, (
        f"cold replica took {out['cold_rounds_to_converge']} sync rounds "
        f"to reach the fleet mean (bound: {COLD_ROUND_BOUND})")
    assert tel["bytes_sent"] < tel["bytes_sent_raw"], (
        f"delta compression lost to raw f32: {tel['bytes_sent']} vs "
        f"{tel['bytes_sent_raw']} bytes")

    gates = {
        "spread_gate": SPREAD_GATE,
        "sync_spread_final": sync_spread,
        "nosync_spread_final": nosync_spread,
        "cold_rounds_to_converge": out["cold_rounds_to_converge"],
        "cold_round_bound": COLD_ROUND_BOUND,
        "bytes_sent": tel["bytes_sent"],
        "bytes_sent_raw": tel["bytes_sent_raw"],
        "compression_ratio": tel["bytes_sent_raw"]
        / max(tel["bytes_sent"], 1),
        "passed": True,
    }
    print(f"gates PASSED: sync spread {sync_spread:.4f} vs unsynced "
          f"{nosync_spread:.4f} (gate {SPREAD_GATE}); cold converged in "
          f"{out['cold_rounds_to_converge']} round(s); wire "
          f"{tel['bytes_sent']}B vs {tel['bytes_sent_raw']}B raw "
          f"({gates['compression_ratio']:.2f}x)")
    return gates


def csv_rows(quick: bool = True) -> list[tuple]:
    """``benchmarks.run`` harness entry: fleet convergence on the smoke
    trace (full canonical trace when ``quick=False``), gates asserted
    inside."""
    trace = canonical_trace(SMOKE_TRACE if quick else DEFAULT_TRACE)
    out = run_fleets(trace, fleet_spec(trace), SYNC_EVERY)
    gates = check_gates(out)
    return [
        ("fabric_sync/sync_spread", round(gates["sync_spread_final"], 4),
         f"expensive-share spread, synced fleet (gate <= {SPREAD_GATE})"),
        ("fabric_sync/nosync_spread",
         round(gates["nosync_spread_final"], 4),
         "same fleet, no exchange (gated > sync_spread)"),
        ("fabric_sync/cold_rounds_to_converge",
         gates["cold_rounds_to_converge"],
         f"mid-run joiner (bound {COLD_ROUND_BOUND})"),
        ("fabric_sync/compression_ratio",
         round(gates["compression_ratio"], 2),
         "raw f32 wire bytes / int8 delta bytes"),
    ]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="short CI trace (same gates, ~4x faster)")
    ap.add_argument("--trace", default=None,
                    help="canonical trace name (overrides --smoke choice)")
    ap.add_argument("--sync-every", type=int, default=SYNC_EVERY,
                    help="steps between fabric sync rounds")
    ap.add_argument("--json", default=None,
                    help="structured-output path ('' disables; default: "
                    "repo-root BENCH_fabric_sync.json for full default "
                    "runs)")
    args = ap.parse_args()

    trace_name = args.trace or (SMOKE_TRACE if args.smoke else DEFAULT_TRACE)
    trace = canonical_trace(trace_name)
    spec = fleet_spec(trace)
    print(f"trace: {trace_name}  replicas: {N_REPLICAS}+1 cold @ step "
          f"{int(JOIN_AT_FRAC * trace.steps)}  sync every "
          f"{args.sync_every} steps")
    t0 = time.perf_counter()
    out = run_fleets(trace, spec, args.sync_every)
    wall = time.perf_counter() - t0
    final = out["trajectory"][-1]
    for name in sorted(final["sync_shares"]):
        print(f"  {name:5s}: synced top-tier share "
              f"{final['sync_shares'][name]:.3f}  unsynced "
              f"{final['nosync_shares'][name]:.3f}")
    gates = check_gates(out)
    print(f"wall={wall:.1f}s")

    if args.json is not None:
        json_path = pathlib.Path(args.json) if args.json else None
    elif trace_name == DEFAULT_TRACE and args.sync_every == SYNC_EVERY:
        json_path = DEFAULT_JSON     # full default run: track it
    else:
        json_path = None
    if json_path is not None:
        payload = {
            "bench": "fabric_sync",
            "trace": trace.to_dict(),
            "spec": spec.to_dict(),
            "n_replicas": N_REPLICAS,
            "join_at": out["join_at"],
            "sync_every": args.sync_every,
            "gates": gates,
            "wall_s": wall,
            "final": final,
            "trajectory": out["trajectory"],
            "fabric_telemetry": out["fabric"].telemetry(),
        }
        json_path.write_text(json.dumps(payload, indent=1, sort_keys=True)
                             + "\n")
        print(f"wrote {json_path}")


if __name__ == "__main__":
    main()
