"""Sharded-dispatch benchmark: the ``sharded`` backend vs ``auto``,
parity-gated bit-for-bit on a forced multi-device host mesh.

Forces ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (default
8, ``--devices`` overrides) BEFORE jax import, builds the dispatch mesh,
and runs two sections:

parity (the acceptance gate)
  ``sharded`` must reproduce ``auto`` EXACTLY — tiers, difficulty, all
  four skew metrics — at the headline shape B=1024 / K=100 with ragged
  ``n_valid``, plus a dense batch and the fused retrieve-to-decision
  path (indices, probs, tiers). Bit-for-bit, not allclose: the shards
  run the identical row-local programs, so any drift is a bug.

throughput (recorded, not gated)
  median wall time of ``route_batch`` over a batch sweep for both
  backends. On the forced HOST mesh the shards timeshare one CPU, so
  speedup here measures dispatch overhead, not the real-mesh win — the
  number worth tracking is that sharding costs ~nothing at the shapes
  where a real pod would fan out.

Full runs (default device count, no --smoke) also write structured JSON
to ``BENCH_sharded_dispatch.json`` at the repo root — the parity/perf
trajectory tracked across PRs (``--json`` overrides the path, ``--json
''`` disables writing).

  PYTHONPATH=src python -m benchmarks.sharded_dispatch_bench [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import sys
import time

DEFAULT_DEVICES = 8
GATE_SHAPE = (1024, 100)          # B, K of the headline parity gate
E2E_SHAPE = (96, 64, 32)          # B, N candidates, top-K end-to-end
FULL_SWEEP = (64, 256, 1024, 4096)
SMOKE_SWEEP = (64, 256)
DEFAULT_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_sharded_dispatch.json"


def _early_devices() -> int:
    """--devices must take effect before jax import; argparse runs too
    late, so peek at argv here."""
    argv = sys.argv
    if "--devices" in argv:
        try:
            return int(argv[argv.index("--devices") + 1])
        except (IndexError, ValueError):
            pass
    return DEFAULT_DEVICES


_FORCED = _early_devices()
if "jax" not in sys.modules and _FORCED > 1:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={_FORCED}"
        ).strip()

import jax                                                     # noqa: E402
import numpy as np                                             # noqa: E402
import numpy.testing as npt                                    # noqa: E402

from repro.api import make_backend                             # noqa: E402
from repro.api.sharded import make_dispatch_mesh               # noqa: E402
from repro.core.router import RouterConfig                     # noqa: E402
from repro.retrieval.scorer import ScorerConfig, init_scorer   # noqa: E402


def desc_scores(rng, b, k) -> np.ndarray:
    return -np.sort(-rng.uniform(0.01, 1, (b, k)).astype(np.float32),
                    axis=1)


def check_parity(cfg: RouterConfig) -> dict:
    """The acceptance gate: bit-for-bit equality with ``auto`` on the
    headline batch, a dense batch, and the fused end-to-end path."""
    auto, shard = make_backend("auto"), make_backend("sharded")
    b, k = GATE_SHAPE
    rng = np.random.default_rng(0)
    scores = desc_scores(rng, b, k)
    nv = rng.integers(5, k + 1, b)

    ra = auto.route_batch(scores, cfg, n_valid=nv)
    rs = shard.route_batch(scores, cfg, n_valid=nv)
    npt.assert_array_equal(np.asarray(ra.tiers), np.asarray(rs.tiers))
    npt.assert_array_equal(np.asarray(ra.difficulty),
                           np.asarray(rs.difficulty))
    npt.assert_array_equal(np.asarray(ra.metrics), np.asarray(rs.metrics))

    rd_a = auto.route_batch(scores, cfg)
    rd_s = shard.route_batch(scores, cfg)
    npt.assert_array_equal(np.asarray(rd_a.tiers), np.asarray(rd_s.tiers))
    npt.assert_array_equal(np.asarray(rd_a.metrics),
                           np.asarray(rd_s.metrics))

    eb, n, ek = E2E_SHAPE
    sc = ScorerConfig(d_emb=16, d_hidden=32)
    params = init_scorer(jax.random.PRNGKey(0), sc)
    feats = rng.standard_normal((eb, n, sc.d_triple)).astype(np.float32)
    qemb = rng.standard_normal((eb, sc.d_query)).astype(np.float32)
    nc = rng.integers(ek, n + 1, eb)
    ecfg = RouterConfig(metric=cfg.metric, thresholds=(3.0,), top_k=ek)
    ea = auto.route_retrieved(feats, qemb, params, ecfg, n_cand=nc)
    es = shard.route_retrieved(feats, qemb, params, ecfg, n_cand=nc)
    for field in ("indices", "probs", "n_valid", "tiers", "metrics"):
        npt.assert_array_equal(np.asarray(getattr(ea, field)),
                               np.asarray(getattr(es, field)))

    mesh = shard.mesh
    gates = {
        "gate_shape": {"B": b, "K": k},
        "e2e_shape": {"B": eb, "N": n, "K": ek},
        "mesh": {ax: int(sz) for ax, sz in mesh.shape.items()},
        "bit_for_bit": True,
        "passed": True,
    }
    print(f"parity PASSED: sharded == auto bit-for-bit at B={b} K={k} "
          f"(ragged + dense) and end-to-end B={eb} N={n} K={ek} on mesh "
          f"{gates['mesh']}")
    return gates


def _time(fn, reps: int) -> float:
    fn()                                   # warmup / compile
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn().tiers)
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


def sweep(cfg: RouterConfig, batches, reps: int) -> list[dict]:
    auto, shard = make_backend("auto"), make_backend("sharded")
    rng = np.random.default_rng(1)
    cells = []
    for b in batches:
        scores = desc_scores(rng, b, GATE_SHAPE[1])
        t_auto = _time(lambda: auto.route_batch(scores, cfg), reps)
        t_shard = _time(lambda: shard.route_batch(scores, cfg), reps)
        cell = {"B": b, "K": GATE_SHAPE[1],
                "auto_ms": 1e3 * t_auto, "sharded_ms": 1e3 * t_shard,
                "speedup": t_auto / t_shard}
        cells.append(cell)
        print(f"B={b:5d} K={GATE_SHAPE[1]}: auto {cell['auto_ms']:8.3f}ms  "
              f"sharded {cell['sharded_ms']:8.3f}ms  "
              f"x{cell['speedup']:.2f}")
    return cells


def csv_rows(quick: bool = True) -> list[tuple]:
    """``benchmarks.run`` harness entry: the bit-for-bit parity gate +
    a short throughput sweep. NOTE: when the harness imported jax before
    this module, the forced multi-device host mesh is whatever that
    import resolved (usually 1 device) — parity still gates; the
    timings measure dispatch overhead only."""
    cfg = RouterConfig(metric="entropy", thresholds=(4.0,),
                       top_k=GATE_SHAPE[1])
    gates = check_parity(cfg)
    cells = sweep(cfg, SMOKE_SWEEP if quick else FULL_SWEEP,
                  reps=3 if quick else 7)
    rows: list[tuple] = [
        ("sharded/parity", int(gates["bit_for_bit"]),
         f"sharded == auto bit-for-bit on mesh {gates['mesh']}"),
    ]
    for c in cells:
        rows.append((f"sharded/B{c['B']}_K{c['K']}/speedup",
                     round(c["speedup"], 2),
                     "auto wall / sharded wall (host mesh)"))
    return rows


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="short sweep (same parity gate)")
    ap.add_argument("--devices", type=int, default=DEFAULT_DEVICES,
                    help="forced host device count (applied before jax "
                    "import; ignored if jax was already imported)")
    ap.add_argument("--reps", type=int, default=None,
                    help="timing repetitions per cell")
    ap.add_argument("--json", default=None,
                    help="structured-output path ('' disables; default: "
                    "repo-root BENCH_sharded_dispatch.json for full "
                    "default-device runs)")
    args = ap.parse_args()

    n_dev = jax.local_device_count()
    print(f"devices: {n_dev} ({jax.devices()[0].platform}), mesh "
          f"{dict(make_dispatch_mesh().shape)}")
    cfg = RouterConfig(metric="entropy", thresholds=(4.0,),
                       top_k=GATE_SHAPE[1])
    gates = check_parity(cfg)
    batches = SMOKE_SWEEP if args.smoke else FULL_SWEEP
    reps = args.reps or (3 if args.smoke else 7)
    cells = sweep(cfg, batches, reps)

    if args.json is not None:
        json_path = pathlib.Path(args.json) if args.json else None
    elif not args.smoke and args.devices == DEFAULT_DEVICES:
        json_path = DEFAULT_JSON     # full default run: track it
    else:
        json_path = None
    if json_path is not None:
        payload = {
            "bench": "sharded_dispatch",
            "devices": n_dev,
            "platform": jax.devices()[0].platform,
            "gates": gates,
            "cells": cells,
        }
        json_path.write_text(json.dumps(payload, indent=1, sort_keys=True)
                             + "\n")
        print(f"wrote {json_path}")


if __name__ == "__main__":
    main()
