"""How each kind of deployment is built and driven; a configuration names
its kind in ``path``.

Every module here holds one ``Deployment(config, mix, seed, spans)`` with:

* ``max_batch``: the most requests one call takes;
* ``setup()``: data, weights, session, calibration and warm-up;
* ``serve(i, j)``: requests ``i .. j-1`` through the program's entry point;
* ``release()``: drop the program's state once the window has closed;
* ``check(window)``: the numbers of `perfbench.compare` against the plain
  reference;
* ``control(window)``: the same numbers for the reference one precision
  lower put in the program's place (run by ``perfbench/control.py``).
"""
