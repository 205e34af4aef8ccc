"""The one traffic generator: reads a mix file and draws the window's
arrivals from the seed.

A mix file (``perfbench/mixes/<config>.<traffic>.json``) holds parameters
only:

* ``loop``: ``"open"`` (requests arrive on a schedule, whether or not the
  router keeps up) or ``"closed"`` (one client sends its next batch when
  the last returns);
* ``rate`` (open): arrivals per second. The window holds exactly
  ``round(rate * seconds)`` arrivals at times drawn uniformly over it,
  which is a Poisson process conditioned on its count: every seed brings
  the same amount of work, in another order;
* ``max_batch`` (open): the most requests one call takes; ``batch``
  (closed): the requests in every call;
* ``rows`` (pre-scored deployments): the score-row draw of
  `perfbench.datagen.power_law_rows`;
* ``pool`` (pre-scored: rows; retrieve: questions): how many distinct
  inputs are made in set-up and cycled through.
"""

from __future__ import annotations

import numpy as np

# Independent generator streams drawn from one seed.
STREAM_ARRIVALS, STREAM_INPUTS, STREAM_CALIBRATION, STREAM_WEIGHTS, \
    STREAM_ORDER, STREAM_SAMPLE = range(6)


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of a run (any seed up to 2**63)."""
    return np.random.default_rng([int(seed), stream])


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Sorted arrival offsets in [0, seconds) of an open-loop window."""
    n = int(round(float(mix["rate"]) * seconds))
    return np.sort(rng(seed, STREAM_ARRIVALS).uniform(0.0, seconds, n))


def batch_limit(mix: dict) -> int:
    """The most requests one call of this mix carries."""
    return int(mix["max_batch"] if mix["loop"] == "open" else mix["batch"])


def batch_sizes(mix: dict, buckets) -> list[tuple[int, int]]:
    """(smallest, largest) batch of each of the program's batch buckets
    that this mix's calls can land in: every bucket up to the largest
    batch of an open loop, the one bucket of a closed loop's batch."""
    out, prev = [], 0
    for b in buckets:
        lo, hi = prev + 1, b
        prev = b
        if mix["loop"] == "closed":
            if lo <= int(mix["batch"]) <= hi:
                out.append((int(mix["batch"]), int(mix["batch"])))
        elif lo <= batch_limit(mix):
            out.append((lo, min(hi, batch_limit(mix))))
    return out
