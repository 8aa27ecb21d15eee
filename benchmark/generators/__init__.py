"""Value generators that a configuration names (``"modules":
{"generator": "<name>"}`` in ``configs/<config>.json``), one module
each; a configuration that names none gets ``data.draw``: uniform
values between ``values.low`` and ``values.high``.

A module here is ``draw(rng, shape, values, seed)``: ``rng`` the child
generator of the slab or of the request, ``shape`` (rows, attributes),
``values`` the configuration's block, ``seed`` the run's seed: whatever
every slab and the load generator's process must agree on (cluster
centres) is derived from ``seed`` and ``values`` alone, what differs
from row to row from ``rng``. Corpus rows and request queries both come
through it. NumPy only: ``loadgen.py`` never imports jax. It comes with
the configuration that needs it.
"""
