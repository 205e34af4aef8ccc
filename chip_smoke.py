#!/usr/bin/env python3
"""Bring-up check: the retrieve-to-decision path on a TPU, against its references.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Drives the router through the entry points a user calls, at the widths
the repo supports (``ScorerConfig()``: Dt=110, Dq=32, H=128, K=100, up to
512 candidates per question). Weights and data are made from ``--seed``.

A  ``session.submit`` of pre-scored rows, with tier engines generating
   tokens: B=8 (the oracle side of ``auto``) and B=1024, K=100, ragged
   (the fused side, the ``skew_metrics`` kernel), each compared with
   ``make_backend("oracle")`` on the same rows and thresholds.
B  ``session.route_retrieved`` at B=64, N=512 (``triple_score`` and
   ``skew_metrics`` kernels in one program), compared with
   ``core.router.route_retrieved(use_kernels=False)`` on the chip and with
   the host-staged ``route_retrieved_staged``.
S  (``--chips 4`` only) ``make_backend("sharded")`` against ``auto`` at
   B=1024, K=100, ragged, and ``route_retrieved`` at B=64, N=512 on a 2x2
   (request x candidate) mesh, whose candidate axis is all-gathered.

Every fused program's compiled text must hold ``tpu_custom_call``.
Earlier lines give each phase's wall and compile seconds and its mismatch
counts; the last line is one JSON object naming the device. Any failure
raises: the exit code is then non-zero and no result line is printed.
The script needs a TPU (it does not fall back to the CPU) and the repo's
``src/`` next to it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

# --- tolerances ----------------------------------------------------------
#: area / entropy / gini columns: both sides are f32 with sums in
#: different orders.
METRIC_RTOL = 1e-4
METRIC_ATOL = 1e-4
#: a cumulative-k difference is admitted only where the float64 reference
#: CDF lies within this of P between the two counts.
CDF_EPS = 1e-5
#: a tier difference is admitted only where the reference difficulty lies
#: within this of a threshold.
TIER_EPS = 1e-4
#: top-K candidates are equal up to ties: the two index sets' float64
#: reference logits, sorted, agree to this.
LOGIT_EPS = 1e-4
#: sigmoid top-K scores.
PROB_ATOL = 1e-5

K = 100
N_CAND = 512
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is timed as the read it was)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def snapshot(self):
        return self.seconds, self.count, self.hits


class Phase:
    """Times one phase and prints its line when it ends."""

    def __init__(self, name: str, meter: CompileMeter):
        self.name, self.meter, self.info = name, meter, {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.meter.snapshot()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        s, n, h = self.meter.snapshot()
        self.compile_s, self.cache_hits = s - self.c0[0], h - self.c0[2]
        line = {"phase": self.name,
                "wall_s": time.perf_counter() - self.t0,
                "compile_s": self.compile_s, "compiles": n - self.c0[1],
                "cache_hits": self.cache_hits, **self.info}
        print(json.dumps(line), flush=True)
        return False


# --- comparisons ---------------------------------------------------------

def _cdf64(scores: np.ndarray, n_valid: np.ndarray) -> np.ndarray:
    """The oracle's CDF in float64: prefix-masked, shifted only if
    negative, normalized, running sum (scores arrive descending)."""
    s = scores.astype(np.float64)
    valid = np.arange(s.shape[1])[None, :] < n_valid[:, None]
    lo = np.minimum(np.where(valid, s, np.inf).min(axis=1, keepdims=True), 0)
    shifted = np.where(valid, s - lo, 0.0)
    return np.cumsum(shifted / (shifted.sum(axis=1, keepdims=True) + 1e-12),
                     axis=1)


def compare_decisions(got, ref, thresholds, scores, n_valid, p_cdf) -> dict:
    """Tiers, difficulty and the four metrics of ``got`` against ``ref``
    (each a (tiers, difficulty, metrics) triple of host arrays)."""
    g_t, g_d, g_m = (np.asarray(a) for a in got)
    r_t, r_d, r_m = (np.asarray(a) for a in ref)
    n_valid = np.asarray(n_valid)
    require(np.all(np.isfinite(g_m)) and np.all(np.isfinite(g_d)),
            "non-finite metrics or difficulty")
    require(g_m.shape == r_m.shape and g_t.shape == r_t.shape,
            f"shape {g_m.shape} vs reference {r_m.shape}")
    cols = [0, 2, 3]  # area, entropy, gini
    close = np.isclose(g_m[:, cols], r_m[:, cols], rtol=METRIC_RTOL,
                       atol=METRIC_ATOL).all(axis=1)
    cdf = _cdf64(np.asarray(scores), n_valid)
    cum_diff = np.flatnonzero(g_m[:, 1] != r_m[:, 1])
    cum_bad = 0
    for r in cum_diff:
        lo, hi = sorted(int(v) for v in (g_m[r, 1], r_m[r, 1]))
        span = cdf[r, max(lo - 1, 0):hi]
        cum_bad += not np.any(np.abs(span - p_cdf) < CDF_EPS)
    tier_diff = np.flatnonzero(g_t != r_t)
    thr = np.asarray(thresholds, np.float64)
    near = np.abs(r_d[:, None].astype(np.float64) - thr[None, :]).min(1)
    tier_bad = int(np.sum(near[tier_diff] >= TIER_EPS))
    out = {"rows": int(len(r_t)), "tier_diff": int(len(tier_diff)),
           "tier_diff_outside_eps": tier_bad,
           "metric_rows_not_close": int(np.sum(~close)),
           "cum_k_diff": int(len(cum_diff)),
           "cum_k_diff_outside_eps": int(cum_bad),
           "max_abs_metric_err": float(np.abs(g_m[:, cols]
                                              - r_m[:, cols]).max())}
    require(tier_bad == 0 and out["metric_rows_not_close"] == 0
            and cum_bad == 0, f"decision mismatch: {out}")
    return out


def compare_topk(got, ref, logits64: np.ndarray) -> dict:
    """Indices, probs and n_valid of two retrieve-to-decision results;
    indices are equal up to ties under the float64 reference logits."""
    g_i, r_i = np.asarray(got.indices), np.asarray(ref.indices)
    g_nv, r_nv = np.asarray(got.n_valid), np.asarray(ref.n_valid)
    g_p, r_p = np.asarray(got.probs), np.asarray(ref.probs)
    require(np.array_equal(g_nv, r_nv), "n_valid differs")
    require(np.all(np.isfinite(g_p)), "non-finite probs")
    diff = bad = 0
    for r, nv in enumerate(r_nv):
        a, b = g_i[r, :nv], r_i[r, :nv]
        if np.array_equal(a, b):
            continue
        diff += 1
        bad += not np.allclose(np.sort(logits64[r, a]),
                               np.sort(logits64[r, b]), rtol=0,
                               atol=LOGIT_EPS)
    prob_err = float(np.abs(g_p - r_p).max())
    out = {"topk_rows_diff": diff, "topk_rows_diff_not_tied": bad,
           "max_abs_prob_err": prob_err}
    require(bad == 0 and prob_err <= PROB_ATOL, f"top-k mismatch: {out}")
    return out


def triple(res):
    return res.tiers, res.difficulty, res.metrics


# --- inputs --------------------------------------------------------------

def score_rows(rng, b: int):
    """[b, K] descending retrieval scores in (0, 1] with ragged valid
    prefixes: per-row power-law decay (spiky rows are easy, flat ones
    hard) with 5% noise; zero past ``n_valid``, which is not data."""
    alphas = rng.uniform(0.1, 2.5, b)
    base = 1.0 / np.arange(1, K + 1)[None, :] ** alphas[:, None]
    rows = np.sort(base * rng.uniform(0.95, 1.05, (b, K)), axis=1)[:, ::-1]
    n_valid = np.where(rng.uniform(size=b) < 0.3, K,
                       rng.integers(10, K + 1, b)).astype(np.int32)
    rows = np.where(np.arange(K)[None, :] < n_valid[:, None], 0.9 * rows, 0)
    return rows.astype(np.float32), n_valid


def retrieval_batch(seed: int, b: int):
    """B questions of the synthetic CWQ-like KG: candidate features padded
    to N_CAND, scorer weights from the seed, float64 reference logits."""
    import jax

    from repro.retrieval import scorer as sc
    from repro.retrieval import synthetic

    data = synthetic.make_dataset("cwq", n_queries=b, n_entities=4000,
                                  seed=seed)
    cfg = sc.ScorerConfig()
    params = sc.init_scorer(jax.random.key(seed), cfg)
    feats, qembs, _, n_cand = sc.batch_triple_features(
        data.kg, data.entity_emb, data.relation_emb, data.queries[:b],
        max_cands=N_CAND, seed=seed)
    feats = np.pad(feats, ((0, 0), (0, N_CAND - feats.shape[1]), (0, 0)))
    w = {k: np.asarray(v, np.float64) for k, v in params.items()}
    h = np.maximum(feats.astype(np.float64) @ w["w1_t"]
                   + (qembs.astype(np.float64) @ w["w1_q"]
                      + w["b1"])[:, None, :], 0.0)
    logits64 = h @ w["w2"][:, 0] + w["b2"][0]
    return feats, qembs, n_cand, params, logits64


def kernel_count(jitted, *args, **static) -> int:
    return jitted.lower(*args, **static).compile().as_text().count(
        "tpu_custom_call")


# --- phases --------------------------------------------------------------

def phase_a(meter, seed: int) -> None:
    """Pre-scored ``session.submit`` with tier engines."""
    import jax.numpy as jnp

    from repro.api import CalibrationSpec, RouteSpec, build, make_backend
    from repro.api.backends import AutoBackend
    from repro.core import calibrate_threshold, router
    from repro.models.layers import LMConfig
    from repro.obs import Observability
    from repro.serving.engine import EngineBank, make_engine

    rng = np.random.default_rng(seed)
    calib, calib_nv = score_rows(rng, 256)
    mask = np.arange(K)[None, :] < calib_nv[:, None]
    theta = calibrate_threshold(jnp.asarray(calib), 0.4, "gini",
                                mask=jnp.asarray(mask))
    spec = RouteSpec(
        metric="gini", thresholds=(theta,), tier_names=("qwen7b", "qwen72b"),
        backend="auto", micro_batch=8,
        calibration=CalibrationSpec(policy="streaming",
                                    target_shares=(0.6, 0.4),
                                    window=1024, min_samples=64))
    max_new = 8
    bank = EngineBank({
        0: make_engine(LMConfig(name="small-tier", n_layers=2, d_model=64,
                                n_heads=4, n_kv_heads=2, head_dim=16,
                                d_ff=128, vocab=512, dtype=jnp.float32),
                       seed=seed),
        1: make_engine(LMConfig(name="large-tier", n_layers=4, d_model=128,
                                n_heads=8, n_kv_heads=4, head_dim=16,
                                d_ff=256, vocab=512, dtype=jnp.float32),
                       seed=seed + 1),
    }, max_new=max_new)
    obs = Observability()
    session = build(spec, runners=bank, obs=obs)
    require(isinstance(session.backend, AutoBackend), "backend is not auto")
    oracle = make_backend("oracle")

    n_routed = 0
    for b, side in ((8, "oracle"), (1024, "fused")):
        rows, nv = score_rows(rng, b)
        prompts = [rng.integers(0, 512, 16).astype(np.int32)
                   for _ in range(b)]
        with Phase(f"A.submit.B{b}", meter) as ph:
            picks0 = obs.metrics.value("backend_pick_total", path=side) or 0
            cfg = session.dispatcher.router  # thresholds this batch uses
            got = session.submit(rows, prompts, n_valid=nv)
            picks = obs.metrics.value("backend_pick_total", path=side) or 0
            require(picks == picks0 + 1, f"B={b} did not take the {side} side")
            ref = oracle.route_batch(jnp.asarray(rows), cfg,
                                     n_valid=jnp.asarray(nv))
            ph.info = {"path": side, "thresholds": list(cfg.thresholds),
                       **compare_decisions(
                           (got.tiers, got.difficulty, got.metrics),
                           triple(ref), cfg.thresholds, rows, nv,
                           cfg.cumulative_p)}
            n_routed += b
            if side == "fused":
                n_kern = kernel_count(
                    router._decision_program, jnp.asarray(rows),
                    jnp.asarray(cfg.thresholds, jnp.float32),
                    jnp.asarray(nv), metric=cfg.metric,
                    p_cdf=cfg.cumulative_p, ragged=True, use_kernel=True,
                    interpret=False)
                require(n_kern >= 1, "fused decision program has no kernel")
                ph.info["tpu_custom_calls"] = n_kern

    with Phase("A.engines", meter) as ph:
        session.flush()
        executed = session.executed
        generated = sum(e.result.generated_tokens for e in executed)
        tokens = np.concatenate([e.result.tokens.ravel() for e in executed])
        require(generated == n_routed * max_new,
                f"engines generated {generated} tokens, "
                f"expected {n_routed * max_new}")
        require(bool(np.all((tokens >= 0) & (tokens < 512))),
                "token ids outside the vocabulary")
        ph.info = {"requests": session.stats.n_requests,
                   "tier_counts": {str(t): n for t, n
                                   in session.stats.tier_counts.items()},
                   "micro_batches": len(executed),
                   "generated_tokens": int(generated)}


def phase_b(meter, seed: int) -> None:
    """``session.route_retrieved`` at B=64, N=512 against the XLA program
    and the host-staged reference."""
    import jax.numpy as jnp

    from repro.api import RouteSpec, build
    from repro.core import router

    b = 64
    with Phase("B.inputs", meter) as ph:
        feats, qembs, n_cand, params, logits64 = retrieval_batch(seed, b)
        ph.info = {"shape": list(feats.shape),
                   "n_cand_min": int(n_cand.min()),
                   "n_cand_max": int(n_cand.max())}
    base = router.RouterConfig(metric="gini", thresholds=(0.0,), top_k=K)
    with Phase("B.xla_reference", meter) as ph:
        xla = router.route_retrieved(jnp.asarray(feats), jnp.asarray(qembs),
                                     params, base, n_cand=jnp.asarray(n_cand),
                                     use_kernels=False)
        # a threshold at the median difficulty splits the batch in two
        theta = float(np.median(np.asarray(xla.difficulty)))
        cfg = router.RouterConfig(metric="gini", thresholds=(theta,),
                                  top_k=K)
        xla = router.route_retrieved(jnp.asarray(feats), jnp.asarray(qembs),
                                     params, cfg, n_cand=jnp.asarray(n_cand),
                                     use_kernels=False)
        ph.info = {"thresholds": [theta]}
    with Phase("B.staged_reference", meter):
        staged = router.route_retrieved_staged(feats, qembs, params, cfg,
                                               n_cand=n_cand)
    session = build(RouteSpec(metric="gini", thresholds=(theta,),
                              top_k=K, backend="auto"))
    require(session.backend.pick(b) is session.backend.fused,
            f"B={b} does not take the fused side")
    with Phase("B.route_retrieved", meter) as ph:
        got = session.route_retrieved(feats, qembs, params, n_cand=n_cand)
        ph.info = {"path": "fused"}
        for name, ref in (("xla", xla), ("staged", staged)):
            dec = compare_decisions(triple(got.result), triple(ref),
                                    cfg.thresholds, np.asarray(ref.probs),
                                    np.asarray(ref.n_valid),
                                    cfg.cumulative_p)
            top = compare_topk(got, ref, logits64)
            ph.info[f"vs_{name}"] = {**dec, **top}
        n_kern = kernel_count(
            router._retrieved_program, jnp.asarray(feats),
            jnp.asarray(qembs), params["w1_t"], params["w1_q"], params["b1"],
            params["w2"], params["b2"],
            jnp.asarray(cfg.thresholds, jnp.float32),
            jnp.asarray(n_cand), top_k=K, metric=cfg.metric,
            p_cdf=cfg.cumulative_p, ragged=True, use_kernels=True,
            interpret=False, tile=128)
        require(n_kern >= 2, f"fused retrieve program holds {n_kern} "
                             f"kernels, expected scoring + skew")
        ph.info["tpu_custom_calls"] = n_kern


def cache_probe(meter, cache_dir: str, entries_at_start: int,
                seed: int) -> None:
    """Compile one fused decision program no other phase uses (entropy
    metric, B=256), drop every in-memory cache, and compile it again: the
    second compile is read from the persistent cache. The first is cold
    unless an earlier run in this checkout cached it."""
    import jax
    import jax.numpy as jnp

    from repro.api import make_backend
    from repro.core.router import RouterConfig

    rows, nv = score_rows(np.random.default_rng(seed + 7), 256)
    cfg = RouterConfig(metric="entropy", thresholds=(3.0,))
    first = None
    for name in ("cache.first_compile", "cache.after_clear_caches"):
        with Phase(name, meter) as ph:
            out = make_backend("fused").route_batch(
                jnp.asarray(rows), cfg, n_valid=jnp.asarray(nv))
            out.tiers.block_until_ready()
        if first is None:
            first = ph
            jax.clear_caches()
    require(ph.cache_hits > 0, "nothing was read from the persistent cache")
    print(json.dumps({
        "cache": cache_dir, "entries_at_start": entries_at_start,
        "entries_now": _entries(cache_dir),
        "cold_compile_s": first.compile_s,
        "cold_was_cache_hit": first.cache_hits > 0,
        "warm_compile_s": ph.compile_s}), flush=True)


def phase_sharded(meter, seed: int) -> None:
    """``sharded`` against ``auto`` on a four-chip mesh."""
    import jax
    import jax.numpy as jnp

    from repro.api import make_backend
    from repro.api.sharded import make_dispatch_mesh
    from repro.core import router

    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 needs 4 devices, "
                               f"JAX sees {len(devices)}")
    rng = np.random.default_rng(seed)
    rows, nv = score_rows(rng, 1024)
    cfg = router.RouterConfig(metric="gini", thresholds=(-0.5,), top_k=K)
    auto = make_backend("auto")
    with Phase("S.route_batch.B1024", meter) as ph:
        sharded = make_backend("sharded")
        mesh = sharded.mesh
        require(len(set(mesh.devices.flat)) == 4,
                f"dispatch mesh spans {mesh.devices.shape}")
        got = sharded.route_batch(jnp.asarray(rows), cfg,
                                  n_valid=jnp.asarray(nv))
        ref = auto.route_batch(jnp.asarray(rows), cfg,
                               n_valid=jnp.asarray(nv))
        spread = len(got.metrics.sharding.device_set)
        require(spread == 4, f"sharded metrics live on {spread} device(s)")
        ph.info = {"mesh": dict(mesh.shape), "result_devices": spread,
                   "bit_equal": bool(np.array_equal(np.asarray(got.metrics),
                                                    np.asarray(ref.metrics))),
                   **compare_decisions(triple(got), triple(ref),
                                       cfg.thresholds, rows, nv,
                                       cfg.cumulative_p)}

    b = 64
    feats, qembs, n_cand, params, logits64 = retrieval_batch(seed, b)
    with Phase("S.route_retrieved.B64.N512", meter) as ph:
        mesh = make_dispatch_mesh(2, 2)
        require(len(set(mesh.devices.flat)) == 4,
                f"2x2 mesh spans {mesh.devices.shape}")
        sharded = make_backend("sharded", mesh=mesh)
        args = (jnp.asarray(feats), jnp.asarray(qembs), params, cfg)
        got = sharded.route_retrieved(*args, n_cand=jnp.asarray(n_cand))
        ref = auto.route_retrieved(*args, n_cand=jnp.asarray(n_cand))
        xla = router.route_retrieved(*args, n_cand=jnp.asarray(n_cand),
                                     use_kernels=False)
        gathered = [k for k in sharded._programs
                    if k[0] == "retrieved" and k[-1]]
        require(bool(gathered), "candidate axis was not sharded")
        spread = len(got.probs.sharding.device_set)
        require(spread > 1, "sharded result lives on one device")
        ph.info = {"mesh": dict(mesh.shape), "result_devices": spread,
                   "bit_equal_auto": bool(np.array_equal(
                       np.asarray(got.metrics), np.asarray(ref.metrics)))}
        for name, r in (("auto", ref), ("xla", xla)):
            dec = compare_decisions(triple(got), triple(r), cfg.thresholds,
                                    np.asarray(r.probs),
                                    np.asarray(r.n_valid), cfg.cumulative_p)
            ph.info[f"vs_{name}"] = {**dec, **compare_topk(got, r, logits64)}


def _entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def main() -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    entries_at_start = _entries(cache_dir)

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU found: JAX sees platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.kernels.device import default_interpret
    require(default_interpret() is False,
            "kernels would run in interpret mode on this device")
    print(f"compile cache: {cache_dir} ({entries_at_start} entries at start)",
          flush=True)

    meter = CompileMeter(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(meter, args.seed)
    else:
        phase_a(meter, args.seed)
        phase_b(meter, args.seed)
        cache_probe(meter, cache_dir, entries_at_start, args.seed)
    print(json.dumps({"total_wall_s": time.perf_counter() - t0,
                      "total_compile_s": meter.seconds,
                      "compiles": meter.count, "cache_hits": meter.hits}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
