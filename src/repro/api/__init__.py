"""`repro.api` — the unified, declarative SkewRoute routing surface.

The entire routing policy is one JSON-round-trippable value; running it
is one call:

    from repro.api import RouteSpec, build

    spec = RouteSpec(metric="gini", thresholds=(theta,),
                     tier_names=("qwen7b", "qwen72b"))
    session = build(spec)                  # or build(spec, runners=bank)
    result = session.route(scores_desc)    # [B, K] -> tiers + telemetry

Policies ship between replicas as bytes (`spec.to_json()` /
`RouteSpec.from_json`), live state ships as `session.snapshot()` /
`restore()`. Difficulty computation is a named, registered backend
(``oracle`` | ``pallas`` | ``fused`` | ``auto``) — see
`repro.api.backends`; ``auto`` is the production batch-size crossover
(oracle below ``spec.crossover_batch``, the fused end-to-end kernels at
or above it).

What the session DOES with the skew metrics is a registered routing
policy (``threshold`` | ``cascade`` | ``adaptive_depth`` |
``mode_select``) selected by ``spec.policy`` — see `repro.policies`;
``policy=None`` is the default threshold compare, bit-for-bit the
pre-policy behavior.
"""

from repro.api.backends import (  # noqa: F401
    DEFAULT_CROSSOVER_BATCH,
    AutoBackend,
    DifficultyBackend,
    OracleBackend,
    PallasBackend,
    available_backends,
    default_interpret,
    make_backend,
    register_backend,
)
from repro.api.spec import (  # noqa: F401
    ENVELOPE_VERSION,
    SCHEMA_VERSION,
    AdmissionSpec,
    CalibrationSpec,
    CostSpec,
    RouteSpec,
    policy_fingerprint,
)
from repro.api.session import (  # noqa: F401
    EngineBankLike,
    SkewRouteSession,
    build,
)
from repro.policies import (  # noqa: F401
    AdaptiveDepthPolicySpec,
    CascadePolicySpec,
    ModeSelectPolicySpec,
    PolicySpec,
    ThresholdPolicySpec,
    available_policies,
    build_policy,
    policy_spec_from_dict,
)
