"""SkewRoute core: the paper's primary contribution.

Training-free LLM routing for KG-RAG via score skewness of retrieved
context — skewness metrics, threshold router, training-free calibration,
cost model, and KGQA evaluation.
"""

from repro.core.skewness import (  # noqa: F401
    METRICS,
    all_metrics,
    area_metric,
    cumulative_k,
    difficulty,
    entropy_metric,
    gini_metric,
    normalize_minmax,
    normalize_prob,
)
from repro.core.router import (  # noqa: F401
    RetrievedRouteResult,
    RouteBatchResult,
    RouterConfig,
    RoutingStats,
    difficulty_from_metrics,
    route,
    route_all_metrics,
    route_binary,
    route_from_difficulty,
    route_kg,
    route_retrieved,
    route_retrieved_staged,
)
from repro.core.streaming_calibrate import (  # noqa: F401
    DriftEvent,
    SlidingWindow,
    StreamingCalibrator,
)
from repro.core.calibrate import (  # noqa: F401
    SweepPoint,
    calibrate_multi_tier,
    calibrate_threshold,
    oracle_curve,
    random_mix_curve,
    sweep_thresholds,
)
from repro.core.cost import CostModel, PAPER_COST_PER_MTOK, PAPER_QUALITY  # noqa: F401
from repro.core.metrics import batch_metrics, f1_score, hit_at_1  # noqa: F401
