"""Unified observability plane: metrics registry, request tracing,
exporters, spans as profiler annotations — and ONE canonical LoadRunner
replay yields a complete per-request timeline (dispatch -> policy ->
[spill] -> execute -> complete) for EVERY request, verified by walking
the JSONL export."""

import gc
import glob
import json

import numpy as np
import pytest

from repro.api import CalibrationSpec, RouteSpec, build
from repro.obs import (NULL_OBS, DEFAULT_TIME_BUCKETS, ManualClock,
                       MetricsRegistry, Observability, gc_spans, int_keyed,
                       prometheus_text, request_timelines, span_tree,
                       str_keyed, to_jsonl)
from repro.serving.loadgen import canonical_load_runner, canonical_trace


def mk_spec(**overrides):
    kw = dict(metric="entropy", thresholds=(6.0,), top_k=50,
              tier_names=("qwen7b", "qwen72b"),
              calibration=CalibrationSpec(policy="streaming",
                                          target_shares=(0.7, 0.3),
                                          window=256, min_samples=32,
                                          tolerance=0.08, cooldown=64))
    kw.update(overrides)
    return RouteSpec(**kw)


def desc_scores(rng, b, k=50):
    return -np.sort(-rng.uniform(0.01, 1, (b, k)).astype(np.float32),
                    axis=1)


# -- registry -----------------------------------------------------------------

def test_registry_instruments_and_label_keying():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", tier="0")
    c.inc()
    c.inc(3)
    assert reg.value("requests_total", tier="0") == 4
    # same (name, labels) -> the same live instrument
    assert reg.counter("requests_total", tier="0") is c
    assert reg.counter("requests_total", tier="1") is not c
    g = reg.gauge("depth")
    g.set(7.5)
    g.inc(-0.5)
    assert reg.value("depth") == 7.0
    h = reg.histogram("lat", (0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.n == 3 and h.counts == [1, 1, 1]
    assert h.total == pytest.approx(5.55)


def test_registry_rejects_kind_clash_and_bad_buckets():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.histogram("h", (1.0, 1.0))          # not strictly increasing
    reg.histogram("h", (1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("h", (1.0, 3.0))          # bucket mismatch, same key


def test_registry_state_roundtrip_restores_in_place():
    reg = MetricsRegistry()
    c = reg.counter("n", tier="1")
    c.inc(5)
    h = reg.histogram("t", DEFAULT_TIME_BUCKETS)
    h.observe(0.01)
    state = json.loads(json.dumps(reg.state_dict()))

    reg2 = MetricsRegistry()
    c2 = reg2.counter("n", tier="1")          # instrument cached pre-load
    reg2.counter("other").inc(9)              # not in the snapshot
    reg2.load_state_dict(state)
    assert c2.value == 5                      # live handle sees the load
    assert reg2.value("other") == 0           # unseen metrics reset
    # the loaded subset round-trips exactly
    by_key = {(s["name"], tuple(sorted(s["labels"].items()))): s
              for s in reg2.state_dict()["samples"]}
    for s in state["samples"]:
        assert by_key[(s["name"],
                       tuple(sorted(s["labels"].items())))] == s


def test_null_plane_is_inert_and_shared():
    assert not NULL_OBS.enabled
    i1 = NULL_OBS.metrics.counter("a", x="1")
    i2 = NULL_OBS.metrics.histogram("b", (1.0,))
    assert i1 is i2                            # one shared no-op instrument
    i1.inc()
    i2.observe(3.0)
    assert NULL_OBS.metrics.state_dict() == {"samples": []}
    with NULL_OBS.tracer.span("s") as sp:
        sp.event("e", k=1)
    assert NULL_OBS.tracer.events() == []
    assert NULL_OBS.clock.now() == 0.0


# -- tracer -------------------------------------------------------------------

def test_tracer_span_nesting_and_deterministic_ids():
    obs = Observability(clock=ManualClock())
    with obs.tracer.span("outer", a=1) as outer:
        with obs.tracer.span("inner"):
            obs.tracer.event("tick", n=2)
        outer.event("done")
    evs = obs.tracer.events()
    tree = span_tree(evs)
    inner = next(n for n in tree.values() if n["name"] == "inner")
    out = next(n for n in tree.values() if n["name"] == "outer")
    assert inner["parent"] == out["span"] and out["parent"] is None
    assert inner["span"] in out["children"]
    # sequential ids, no RNG: a second identical run is byte-identical
    obs2 = Observability(clock=ManualClock())
    with obs2.tracer.span("outer", a=1) as o2:
        with obs2.tracer.span("inner"):
            obs2.tracer.event("tick", n=2)
        o2.event("done")
    assert to_jsonl(evs) == to_jsonl(obs2.tracer.events())


def test_tracer_bounded_buffer_counts_drops():
    obs = Observability(clock=ManualClock(), max_events=3)
    for i in range(6):
        obs.tracer.event("e", i=i)
    assert len(obs.tracer) == 3
    assert obs.tracer.n_dropped == 3
    obs.tracer.clear()
    assert len(obs.tracer) == 0 and obs.tracer.n_dropped == 0


# -- exporter goldens (seeded clock => byte-stable) ---------------------------

def golden_plane() -> Observability:
    obs = Observability(clock=ManualClock(start=1.0, step=0.5))
    obs.metrics.counter("routing_requests_total").inc(3)
    obs.metrics.gauge("pipeline_queue_depth", tier="0").set(2)
    h = obs.metrics.histogram("dispatch_seconds", (0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    with obs.tracer.span("dispatch", batch=2) as sp:
        sp.event("policy", first_id=0, tiers=np.asarray([0, 1]))
    return obs


GOLDEN_JSONL = (
    '{"attrs":{"batch":2},"kind":"span_start","name":"dispatch",'
    '"parent":null,"span":1,"trace":1,"ts":1.0}\n'
    '{"attrs":{"first_id":0,"tiers":[0,1]},"kind":"event",'
    '"name":"policy","span":1,"trace":1,"ts":1.5}\n'
    '{"kind":"span_end","name":"dispatch","span":1,"trace":1,"ts":2.0}')

GOLDEN_PROM = """\
# TYPE dispatch_seconds histogram
dispatch_seconds_bucket{le="0.1"} 1
dispatch_seconds_bucket{le="1"} 2
dispatch_seconds_bucket{le="+Inf"} 2
dispatch_seconds_sum 0.55
dispatch_seconds_count 2
# TYPE pipeline_queue_depth gauge
pipeline_queue_depth{tier="0"} 2
# TYPE routing_requests_total counter
routing_requests_total 3
"""


def test_jsonl_export_golden_bytes():
    assert golden_plane().jsonl() == GOLDEN_JSONL
    # and twice over: the export is a pure function of the plane
    assert golden_plane().jsonl() == golden_plane().jsonl()


def test_prometheus_export_golden_bytes():
    assert golden_plane().prometheus() == GOLDEN_PROM


def test_export_jsonl_writes_lines(tmp_path):
    p = tmp_path / "trace.jsonl"
    n = golden_plane().export_jsonl(p)
    lines = p.read_text().strip().split("\n")
    assert n == len(lines) == 3
    for line in lines:
        json.loads(line)


# -- keys helper (satellite: ONE int-key JSON round-trip) ---------------------

def test_keyed_helpers_roundtrip():
    d = {0: 5, 3: 7}
    assert str_keyed(d) == {"0": 5, "3": 7}
    assert int_keyed(str_keyed(d)) == d
    assert int_keyed({"1": 2.5}, value=float) == {1: 2.5}


def test_pipeline_tier_counts_survive_json_roundtrip():
    session = build(mk_spec(), runners={0: lambda b: b, 1: lambda b: b},
                    obs=Observability(clock=ManualClock()))
    rng = np.random.default_rng(0)
    session.submit(desc_scores(rng, 64), list(range(64)))
    session.flush()
    t = session.pipeline.telemetry
    state = json.loads(json.dumps(t.state_dict()))
    t2 = type(t)()
    t2.load_state_dict(state)
    assert t2.tier_counts == t.tier_counts
    assert all(isinstance(k, int) for k in t2.tier_counts)


# -- dispatcher / session integration ----------------------------------------

def test_route_emits_dispatch_and_policy_events():
    obs = Observability(clock=ManualClock())
    session = build(mk_spec(), obs=obs)
    rng = np.random.default_rng(1)
    res = session.route(desc_scores(rng, 16))
    tl = request_timelines(obs.tracer.events())
    assert sorted(tl) == list(range(16))
    for rid, stages in tl.items():
        assert [s["stage"] for s in stages] == ["dispatch", "policy"]
        assert stages[1]["kind"] == "threshold"
        assert stages[1]["tier"] == int(np.asarray(res.tiers)[rid])
    # registry mirrors moved too
    assert obs.metrics.value("routing_requests_total") == 16
    tiers = np.asarray(res.tiers)
    for t in (0, 1):
        assert obs.metrics.value("routing_tier_decisions_total",
                                 tier=str(t)) == int((tiers == t).sum())


def test_obs_is_runtime_config_not_spec():
    session = build(mk_spec())
    assert session.obs is NULL_OBS
    rng = np.random.default_rng(2)
    session.route(desc_scores(rng, 8))        # no obs, no events, no error
    snap = session.snapshot()
    assert "obs" not in snap["state"]         # envelope byte-compat


def test_backend_pick_counter_tracks_crossover():
    obs = Observability(clock=ManualClock())
    session = build(mk_spec(), obs=obs)
    rng = np.random.default_rng(3)
    session.route(desc_scores(rng, 4))        # below crossover -> oracle
    session.route(desc_scores(rng, 64))       # above -> fused
    assert obs.metrics.value("backend_pick_total", path="oracle") == 1
    assert obs.metrics.value("backend_pick_total", path="fused") == 1


#: The small graph of the question-path tests: embedding width, hidden
#: width, entities and triples.
KG_D, KG_H, KG_ENT, KG_TRI = 8, 16, 50, 400


def _small_kg(rng, max_cands):
    """A seeded graph of ``KG_ENT`` entities, 5 relations and ``KG_TRI``
    triples on the device, and seeded scorer weights."""
    from repro.retrieval.store import DeviceKG
    store = DeviceKG.put(rng.normal(0, 1, (KG_ENT, KG_D)),
                         rng.normal(0, 1, (5, KG_D)),
                         rng.integers(0, KG_ENT, KG_TRI),
                         rng.integers(0, 5, KG_TRI),
                         rng.integers(0, KG_ENT, KG_TRI),
                         max_cands=max_cands)
    params = {k: rng.normal(0, 0.3, s).astype(np.float32)
              for k, s in (("w1_t", (3 * KG_D + 14, KG_H)),
                           ("w1_q", (KG_D, KG_H)), ("b1", (KG_H,)),
                           ("w2", (KG_H, 1)), ("b2", (1,)))}
    return store, params


def _served_session(obs, rng):
    """A session with two identity runners and a small graph on the
    device, crossover 32, micro-batches of 8."""
    store, params = _small_kg(rng, max_cands=32)
    return build(mk_spec(crossover_batch=32, micro_batch=8),
                 runners={0: lambda b: b, 1: lambda b: b}, obs=obs,
                 store=store, scorer=params)


def _dispatch_calls(session, rng):
    """A submit on each side of auto's crossover (buckets 8 and 64), one
    end-to-end retrieve dispatch and one submit of 3 questions (bucket
    8)."""
    import jax.numpy as jnp
    params = {k: jnp.asarray(rng.normal(0, 0.3, s).astype(np.float32))
              for k, s in (("w1_t", (12, 16)), ("w1_q", (8, 16)),
                           ("b1", (16,)), ("w2", (16, 1)), ("b2", (1,)))}
    feats = rng.normal(0, 1, (3, 64, 12)).astype(np.float32)
    qemb = rng.normal(0, 1, (3, 8)).astype(np.float32)
    question = (rng.integers(0, KG_ENT, 3), rng.normal(0, 1, (3, KG_D)),
                rng.integers(0, KG_TRI, (3, 32)), np.asarray([32, 3, 17]))
    return [lambda: session.submit(desc_scores(rng, 5)),
            lambda: session.submit(desc_scores(rng, 40)),
            lambda: session.route_retrieved(feats, qemb, params),
            lambda: session.submit_questions(*question)]


@pytest.mark.parametrize("plane", ["null", "enabled"])
def test_dispatch_transfers_counter_reads_one_each_way_per_dispatch(plane):
    obs = Observability(clock=ManualClock()) if plane == "enabled" else None
    session = _served_session(obs, np.random.default_rng(16))
    for n, call in enumerate(_dispatch_calls(session,
                                             np.random.default_rng(13)), 1):
        call()
        if obs is None:
            assert NULL_OBS.metrics.state_dict() == {"samples": []}
            continue
        for direction in ("h2d", "d2h"):
            assert obs.metrics.value("dispatch_transfers_total",
                                     direction=direction) == n
    if obs is not None:
        assert obs.metrics.value("backend_pick_total", path="oracle") == 3
        assert obs.metrics.value("backend_pick_total", path="fused") == 1


# -- snapshot / restore -------------------------------------------------------

def test_obs_state_rides_the_envelope_and_restores():
    obs = Observability(clock=ManualClock())
    session = build(mk_spec(), runners={0: lambda b: b, 1: lambda b: b},
                    obs=obs)
    rng = np.random.default_rng(4)
    session.submit(desc_scores(rng, 48), list(range(48)))
    session.flush()
    snap = json.loads(json.dumps(session.snapshot()))
    assert "obs" in snap["state"]

    obs2 = Observability(clock=ManualClock())
    restored = build(mk_spec(), runners={0: lambda b: b, 1: lambda b: b},
                     obs=obs2)
    restored.restore(snap)
    assert (obs2.metrics.value("pipeline_submitted_total")
            == obs2.metrics.value("routing_requests_total") == 48)
    # live mirrors keep counting from the restored values
    restored.submit(desc_scores(rng, 16), list(range(48, 64)))
    restored.flush()
    t = restored.pipeline.telemetry
    assert t.n_submitted == t.n_executed + restored.pipeline.pending() == 64
    assert obs2.metrics.value("pipeline_submitted_total") == 64
    assert obs2.metrics.value("pipeline_executed_total") == t.n_executed


def test_obs_less_restore_of_obs_snapshot_is_fine():
    obs = Observability(clock=ManualClock())
    session = build(mk_spec(), obs=obs)
    rng = np.random.default_rng(5)
    session.route(desc_scores(rng, 8))
    snap = session.snapshot()
    plain = build(mk_spec())
    plain.restore(json.loads(json.dumps(snap)))   # obs block ignored
    assert plain.stats.n_requests == 8


def test_trace_events_never_serialize():
    obs = Observability(clock=ManualClock())
    session = build(mk_spec(), obs=obs)
    rng = np.random.default_rng(6)
    session.route(desc_scores(rng, 8))
    assert len(obs.tracer) > 0
    state = json.loads(json.dumps(session.snapshot()["state"]["obs"]))
    # metric samples only — no event list, no span ids (a restored
    # replica starts a fresh timeline; counters carry the history)
    assert set(state) == {"samples"}
    assert all(set(s) >= {"name", "labels", "kind"}
               for s in state["samples"])


# -- spans on the profiler's clock ---------------------------------------------

def _host_events(trace_dir) -> list[tuple]:
    """``(name, start_ns, end_ns, stats)`` of the ``repro.*`` and
    ``bench.*`` events on the host planes of the one trace under
    ``trace_dir``, outer before inner."""
    import jax
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("repro.", "bench.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


def _forest(events) -> list:
    """Nest the events by containment: ``[name, children]`` roots."""
    roots, stack = [], []
    for name, s, e, _ in events:
        node = (name, s, e, [])
        while stack and not (stack[-1][1] <= s and e <= stack[-1][2]):
            stack.pop()
        (stack[-1][3] if stack else roots).append(node)
        stack.append(node)

    def strip(node):
        return [node[0], [strip(c) for c in node[3]]]
    return [strip(r) for r in roots]


def _served_path_calls(obs, trace_dir):
    """The four calls of :func:`_dispatch_calls`, each in a
    ``bench.call`` annotation. Returns the trace's forest and the
    micro-batches each call executed."""
    import jax
    from jax.profiler import TraceAnnotation

    session = _served_session(obs, np.random.default_rng(17))
    calls = _dispatch_calls(session, np.random.default_rng(11))
    for call in calls:               # compile outside the trace
        call()
    ran = []
    jax.profiler.start_trace(str(trace_dir))
    try:
        for call in calls:
            before = session.pipeline.telemetry.n_microbatches
            with TraceAnnotation("bench.call"):
                call()
            ran.append(session.pipeline.telemetry.n_microbatches - before)
    finally:
        jax.profiler.stop_trace()
    return _forest(_host_events(trace_dir)), ran


@pytest.mark.parametrize("plane", ["null", "enabled"])
def test_served_path_spans_land_in_the_profiler_trace(plane, tmp_path):
    obs = Observability(clock=ManualClock()) if plane == "enabled" else None
    forest, ran = _served_path_calls(obs, tmp_path)
    assert [r[0] for r in forest] == ["bench.call"] * 4
    stages = [["repro.launch", []], ["repro.pull", []],
              ["repro.decide", []]]
    for (_, call), n_exec, span in zip(
            [forest[i] for i in (0, 1, 3)], [ran[i] for i in (0, 1, 3)],
            ["repro.dispatch", "repro.dispatch", "repro.dispatch_kg"]):
        # one submit a call: dispatch, then the hand-off with one execute
        # span per micro-batch the call filled
        assert call == [["repro.submit", [
            [span, stages],
            ["repro.handoff", [["repro.execute", []]] * n_exec]]]]
    assert ran[1] >= 4                # 40 rows fill at least 4 of 8
    assert forest[2][1] == [["repro.dispatch_retrieved", stages]]


def test_question_dispatch_spans_counters_and_store_gauge(tmp_path):
    """``submit_questions``: its dispatch span ``dispatch_kg`` under the
    pipeline's ``submit``, one transfer each way per call, the real
    candidates scored, and the store's bytes."""
    import jax
    from jax.profiler import TraceAnnotation

    rng = np.random.default_rng(14)
    store, params = _small_kg(rng, max_cands=32)
    obs = Observability(clock=ManualClock())
    session = build(mk_spec(top_k=10, micro_batch=2),
                    runners={0: lambda b: b, 1: lambda b: b}, obs=obs,
                    store=store, scorer=params)
    assert obs.metrics.value("kg_store_bytes") == store.nbytes == \
        4 * (KG_ENT * KG_D + 5 * KG_D + 3 * KG_TRI)
    n_cand = np.asarray([32, 3, 17])

    def call():
        return session.submit_questions(
            rng.integers(0, KG_ENT, 3), rng.normal(0, 1, (3, KG_D)),
            rng.integers(0, KG_TRI, (3, 32)), n_cand)
    call()                            # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.call"):
            before = session.pipeline.telemetry.n_microbatches
            call()
            n_exec = session.pipeline.telemetry.n_microbatches - before
    finally:
        jax.profiler.stop_trace()
    stages = [["repro.launch", []], ["repro.pull", []], ["repro.decide", []]]
    assert _forest(_host_events(tmp_path)) == [["bench.call", [[
        "repro.submit", [
            ["repro.dispatch_kg", stages],
            ["repro.handoff", [["repro.execute", []]] * n_exec]]]]]]
    for direction in ("h2d", "d2h"):
        assert obs.metrics.value("dispatch_transfers_total",
                                 direction=direction) == 2
    assert obs.metrics.value("kg_candidates_total") == 2 * n_cand.sum()
    # one 128-slot tile a question, of bucket 8's eight rows
    assert obs.metrics.value("kg_scorer_tiles_total", state="scored") == 6
    assert obs.metrics.value("kg_scorer_tiles_total", state="skipped") == 10


def test_scorer_tile_counter_counts_the_ragged_grid():
    """``kg_scorer_tiles_total{state}``: 37 questions of 385-512
    candidates in bucket 64 over 512 slots score four 128-slot tiles each
    (148) and skip the other 108 of the bucket's 256, the 27 padding
    rows' tiles; then 9 questions of 1-129 candidates score 1 or 2."""
    rng = np.random.default_rng(15)
    store, params = _small_kg(rng, max_cands=512)
    obs = Observability(clock=ManualClock())
    session = build(mk_spec(top_k=10, micro_batch=2),
                    runners={0: lambda b: b, 1: lambda b: b}, obs=obs,
                    store=store, scorer=params)

    def tiles():
        return [obs.metrics.value("kg_scorer_tiles_total", state=s)
                for s in ("scored", "skipped")]
    for b, counts, want in ((37, rng.integers(385, 513, 37), [148, 108]),
                            (9, [1, 128, 129, 64, 1, 2, 3, 4, 5],
                             [148 + 10, 108 + 246])):
        session.submit_questions(
            rng.integers(0, KG_ENT, b), rng.normal(0, 1, (b, KG_D)),
            rng.integers(0, KG_TRI, (b, 512)), np.asarray(counts))
        assert tiles() == want


def test_null_plane_records_no_events_on_the_served_path():
    session = build(mk_spec(), runners={0: lambda b: b, 1: lambda b: b})
    rng = np.random.default_rng(12)
    for b in (5, 40):
        session.submit(desc_scores(rng, b))
    session.flush()
    session.route(desc_scores(rng, 8))
    assert session.obs is NULL_OBS
    assert NULL_OBS.tracer.events() == [] and len(NULL_OBS.tracer) == 0
    assert NULL_OBS.metrics.state_dict() == {"samples": []}


def test_gc_spans_annotate_collections_and_restore_callbacks(tmp_path):
    import jax
    before = list(gc.callbacks)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with gc_spans():
            assert len(gc.callbacks) == len(before) + 1
            gc.collect()
        gc.collect()                  # after the block: no span
    finally:
        jax.profiler.stop_trace()
    assert gc.callbacks == before
    # young collections may come and go; the one full collection inside
    # the block is there, the one after it is not
    spans = [e for e in _host_events(tmp_path) if e[0] == "repro.gc"]
    full = [e for e in spans if e[3] == {"generation": 2}]
    assert len(full) == 1 and full[0][2] > full[0][1]
    with pytest.raises(RuntimeError):
        with gc_spans():
            raise RuntimeError("the hook goes on the way out too")
    assert gc.callbacks == before


# -- mode topology (satellite: no_rag tiers skip retrieval-sized prompts) -----

def test_mode_select_pools_serve_bare_question_prompts():
    trace = canonical_trace("smoke")
    runner = canonical_load_runner(False, trace, policy="mode_select")
    assert runner.pools[0].mode == "no_rag"
    assert runner.pools[1].mode == runner.pools[2].mode == "kg_rag"
    report = runner.run(trace)
    from repro.core.cost import TOKENS_BARE_QUESTION
    lens = {t: {r.prompt_len for r in p.done}
            for t, p in runner.pools.items() if p.done}
    assert lens.get(0, {TOKENS_BARE_QUESTION}) == {TOKENS_BARE_QUESTION}
    for t in (1, 2):
        assert lens.get(t, {1873}) == {1873}
    assert report.summary["tier_modes"]["0"] == "no_rag"


def test_scheduler_mode_defaults_to_kg_rag():
    from repro.serving.scheduler import Replica, TierScheduler
    pool = TierScheduler(0, [Replica(0, 0)])
    assert pool.mode == "kg_rag"


# -- THE acceptance test: full timeline from one canonical replay -------------

def replay_with_obs(policy=None):
    trace = canonical_trace("smoke")
    obs = Observability(clock=ManualClock())
    runner = canonical_load_runner(True, trace, policy=policy, obs=obs)
    report = runner.run(trace)
    return runner, report, obs


def test_canonical_replay_yields_complete_timelines(tmp_path):
    runner, report, obs = replay_with_obs()
    path = tmp_path / "trace.jsonl"
    obs.export_jsonl(path)
    events = [json.loads(line) for line in
              path.read_text().strip().split("\n")]
    tl = request_timelines(events)

    n = report.summary["n_arrivals"]
    assert n > 0 and sorted(tl) == list(range(n))
    spilled = set()
    for rid, stages in tl.items():
        names = [s["stage"] for s in stages]
        # every request: dispatched, policy-decided, executed, completed
        assert names[0] == "dispatch"
        assert names[1] == "policy"
        assert "execute" in names and "complete" in names
        assert names.index("execute") < names.index("complete")
        # the tier the request EXECUTED on is the policy tier unless an
        # admission spill moved it — and then the spill hop is recorded
        exec_tier = stages[names.index("execute")]["tier"]
        decided = stages[1]["tier"]
        if "spill" in names:
            hop = stages[names.index("spill")]
            assert hop["tier_in"] == decided and hop["tier"] == exec_tier
            spilled.add(rid)
        else:
            assert exec_tier == decided
        # timestamps are monotone within the request's life
        ts = [s["ts"] for s in stages]
        assert ts == sorted(ts)
    # spill hops in the trace == the controller's spill counter
    assert len(spilled) == report.summary["n_spilled"] > 0

    # span forest: every submit span contains a dispatch child, which
    # holds the launch, pull and decide stages; the hand-off follows it
    tree = span_tree(events)
    submits = [s for s in tree.values() if s["name"] == "submit"]
    assert submits
    for s in submits:
        kids = {tree[c]["name"] for c in s["children"]}
        assert "dispatch" in kids
        assert kids == {"dispatch", "handoff"}
        dispatch = next(tree[c] for c in s["children"]
                        if tree[c]["name"] == "dispatch")
        assert [tree[c]["name"] for c in dispatch["children"]] == [
            "launch", "pull", "decide"]

    # the registry tells the same aggregate story as the telemetry
    t = runner.session.pipeline.telemetry
    assert obs.metrics.value("pipeline_submitted_total") == t.n_submitted
    assert obs.metrics.value("pipeline_executed_total") == t.n_executed == n
    assert sum(obs.metrics.value("load_completed_total", tier=str(k))
               for k in runner.pools) == report.summary["n_completed"]


def test_cascade_escalations_appear_in_policy_stage():
    runner, report, obs = replay_with_obs(policy="cascade")
    tl = request_timelines(obs.tracer.events())
    policy_stages = [s for stages in tl.values() for s in stages
                     if s["stage"] == "policy"]
    assert {s["kind"] for s in policy_stages} == {"cascade"}
    # a cascade escalation = the request went past tier 0; the timeline
    # carries each one (and tier_in shows rows where the cascade
    # overrode the backend's threshold decision)
    escalated = sum(1 for s in policy_stages if s["tier"] > 0)
    pol = runner.session.policy.telemetry()
    assert escalated == pol["n_escalated"] > 0
    assert any("tier_in" in s for s in policy_stages)
