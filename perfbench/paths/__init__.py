"""How each kind of deployment is built and driven; a configuration names
its kind in ``path``.

Every module here holds ``small(config, mix) -> (config, mix)``: copies of
a configuration and one of its mixes cut so that a whole run fits in a few
CPU seconds (the CPU tests run every mix through it, and refuse a kind
without it). And one ``Deployment(config, mix, seed, spans)`` with:

* ``max_batch``: the most requests one call takes;
* ``setup()``: data, weights, session, calibration and warm-up;
* ``serve(i, j)``: requests ``i .. j-1`` through the program's entry point;
* ``release()``: drop the program's state once the window has closed;
* ``check(window)``: the numbers of `perfbench.compare` against the plain
  reference;
* ``control(window)``: the same numbers for the reference one precision
  lower put in the program's place (run by ``perfbench/control.py``);
* ``tiers_column``: where each entry of its ``outputs`` (one per call)
  holds the tiers served;
* ``hands_off``: whether it hands requests to tier runners through the
  program's pipeline, each runner built by ``_handoff(tier)`` and queued
  in ``session.pipeline``.

The CPU tests plant their faults by these declarations, not by a kind's
name.
"""
