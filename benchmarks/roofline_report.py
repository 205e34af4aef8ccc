"""Render the EXPERIMENTS.md §Dry-run / §Roofline tables from the dry-run
result JSONs, or roofline the serving-side routing program.

  PYTHONPATH=src python -m benchmarks.roofline_report [--mesh single]
  PYTHONPATH=src python -m benchmarks.roofline_report --routing

``--routing`` compiles the fused retrieve-to-decision program
(`repro.core.router.route_retrieved`: Pallas/XLA triple scoring ->
device top-k -> skew metrics -> threshold decision, ONE jitted
computation) at canonical serving shapes and rooflines it from
``cost_analysis()`` + the loop-aware HLO re-derivation — the same
pipeline the dry-run records go through — so the decision program's
bottleneck (memory, at these shapes: the [B, N, Dt] feature read
dwarfs the MLP FLOPs) is tracked with the same constants as the
training cells.
"""

from __future__ import annotations

import argparse
import json
import pathlib

RESULTS = pathlib.Path(__file__).parent / "dryrun_results"

# canonical serving shapes: (batch, padded candidates per query)
ROUTING_SHAPES = ((8, 512), (64, 512), (256, 512))


def load(mesh: str | None = None) -> list[dict]:
    recs = []
    for f in sorted(RESULTS.glob("*.json")):
        r = json.loads(f.read_text())
        if mesh is None or r.get("mesh") == mesh:
            recs.append(r)
    return recs


def dryrun_table(recs: list[dict]) -> str:
    lines = ["| arch | shape | mesh | compile | peak GiB/dev | "
             "collective ops | status |",
             "|---|---|---|---|---|---|---|"]
    for r in recs:
        if not r.get("ok"):
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | - | - "
                         f"| - | FAIL: {r.get('error', '?')[:60]} |")
            continue
        peak = r["memory"]["peak_device_bytes"] / 2 ** 30
        nc = r["collectives"]["n_ops"]
        flag = "ok" if peak <= 16 else "ok (>16 GiB, see notes)"
        lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                     f"{r['compile_s']}s | {peak:.2f} | {nc} | {flag} |")
    return "\n".join(lines)


def roofline_table(recs: list[dict]) -> str:
    lines = ["| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | bound | "
             "MODEL_FLOPS | useful ratio | MFU bound |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in recs:
        if not r.get("ok") or "roofline" not in r:
            continue                # measured-only records (select_depths)
        rl = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {rl['compute_s']:.3e} | "
            f"{rl['memory_s']:.3e} | {rl['collective_s']:.3e} | "
            f"{rl['dominant']} | {rl.get('model_flops', 0):.2e} | "
            f"{rl.get('useful_flops_ratio', 0):.3f} | "
            f"{rl.get('mfu_upper_bound', 0):.3f} |")
    return "\n".join(lines)


def routing_record(batch: int, n_cand: int) -> dict:
    """Compile the fused retrieve-to-decision program at one shape and
    return a dry-run-style record (cost / collectives / roofline)."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core.router import RouterConfig, route_retrieved
    from repro.launch import hlo_cost
    from repro.launch.roofline import roofline_terms
    from repro.retrieval.scorer import ScorerConfig, init_scorer

    cfg = ScorerConfig()
    params = init_scorer(jax.random.PRNGKey(0), cfg)
    config = RouterConfig(metric="entropy", thresholds=(6.0,))

    def fn(feats, qemb, ncand):
        r = route_retrieved(feats, qemb, params, config, n_cand=ncand)
        return r.indices, r.probs, r.tiers, r.difficulty

    args = (jnp.zeros((batch, n_cand, cfg.d_triple), jnp.float32),
            jnp.zeros((batch, cfg.d_query), jnp.float32),
            jnp.full((batch,), n_cand, jnp.int32))
    rec: dict = {"arch": "route_retrieved",
                 "shape": f"B{batch}xN{n_cand}", "mesh": "single",
                 "n_devices": 1}
    t0 = time.monotonic()
    compiled = jax.jit(fn).lower(*args).compile()
    rec["compile_s"] = round(time.monotonic() - t0, 2)
    rec["ok"] = True
    ma = compiled.memory_analysis()
    if ma is not None:
        rec["memory"] = {"peak_device_bytes": int(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)}
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, list):        # older jax: one dict per device
        ca = ca[0] if ca else {}
    lc = hlo_cost.analyze(compiled.as_text())
    rec["cost"] = {"flops": lc["flops"],
                   "bytes_accessed": lc["bytes_accessed"],
                   "transcendentals": float(ca.get("transcendentals", 0.0))}
    rec["collectives"] = {"counts": lc["collective_counts"],
                          "bytes": lc["collective_bytes"],
                          "total_bytes": lc["collective_total_bytes"],
                          "n_ops": lc["collective_n_ops"]}
    rec["roofline"] = roofline_terms(rec)
    # MEASURED wall time next to the modeled terms: profile the program
    # we just compiled (no second compile) — block_until_ready best-of,
    # via the obs plane's profiling hook.
    from repro.obs import profile_program
    prof = profile_program(fn, args, name="route_retrieved",
                           shape=rec["shape"], iters=5, compiled=compiled)
    rec["measured"] = prof.to_dict()
    return rec


def select_depths_record(batch: int) -> dict:
    """Profile the jitted depth-selection program (`core.router.
    select_depths` — the adaptive_depth policy's second routed axis)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.router import select_depths
    from repro.obs import profile_program

    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.uniform(0, 8, batch).astype(np.float32)),
            jnp.asarray([4.0, 6.0], jnp.float32),
            jnp.asarray([25, 50, 100], jnp.int32))
    prof = profile_program(lambda d, c, o: select_depths(d, c, o), args,
                           name="select_depths", shape=f"B{batch}",
                           iters=5)
    return {"arch": "select_depths", "shape": f"B{batch}", "ok": True,
            "measured": prof.to_dict()}


def measured_table(recs: list[dict]) -> str:
    lines = ["| program | shape | compile (s) | wall (s) | GFLOP/s | "
             "GiB/s |",
             "|---|---|---|---|---|---|"]
    for r in recs:
        m = r.get("measured")
        if not m:
            continue
        lines.append(
            f"| {m['name']} | {m['shape']} | {m['compile_s']:.2f} | "
            f"{m['wall_s']:.3e} | {m['achieved_gflops']:.2f} | "
            f"{m['achieved_gbps'] / 1.073741824:.2f} |")
    return "\n".join(lines)


def routing_roofline(shapes=ROUTING_SHAPES) -> list[dict]:
    recs = [routing_record(b, n) for b, n in shapes]
    recs.append(select_depths_record(batch=shapes[-1][0]))
    print("## Roofline (fused retrieve-to-decision program)\n")
    print(roofline_table(recs))
    print("\n## Measured (block_until_ready best-of, this host)\n")
    print(measured_table(recs))
    return recs


def csv_rows(quick: bool = False) -> list[tuple]:
    """``benchmarks.run`` harness entry: measured + modeled numbers for
    the serving device programs (one shape when ``quick``)."""
    shapes = ROUTING_SHAPES[:1] if quick else ROUTING_SHAPES
    rows: list[tuple] = []
    for rec in routing_roofline(shapes):
        m = rec.get("measured") or {}
        tag = f"roofline/{rec['arch']}/{rec['shape']}"
        if m:
            rows.append((f"{tag}/wall_s", round(m["wall_s"], 6),
                         "measured block_until_ready best-of"))
            rows.append((f"{tag}/achieved_gbps",
                         round(m["achieved_gbps"], 3),
                         "HLO bytes_accessed / measured wall"))
        rl = rec.get("roofline")
        if rl:
            rows.append((f"{tag}/bound", rl["dominant"],
                         "modeled bottleneck (compute/memory/collective)"))
    return rows


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default=None, choices=[None, "single", "multi"])
    ap.add_argument("--routing", action="store_true",
                    help="compile + roofline the fused retrieve-to-"
                    "decision serving program instead of rendering the "
                    "dry-run tables")
    args = ap.parse_args()
    if args.routing:
        routing_roofline()
        return
    recs = load(args.mesh)
    print("## Dry-run\n")
    print(dryrun_table(recs))
    print("\n## Roofline (single-pod)\n")
    print(roofline_table([r for r in recs if r.get("mesh") == "single"]))


if __name__ == "__main__":
    main()
