"""Plain references that a configuration names (``"modules":
{"reference": "<name>"}`` in ``configs/<config>.json``), one module
each; a configuration that names none is held to
``benchmark/reference.py`` (float64 brute force, squared L2).

A module here is what ``check.py`` uses of that file: ``Answer`` (label,
ids, dists, checksum), ``knn_exact(rows, labels, queries, ks)`` and the
straightforward ``knn_plain(...)`` it is held to, and, where a score
may be zero or negative, ``dist_scale(want)``: the denominator of
``dist_rel_err_max`` (default ``max(|reference|, tiny)``). It imports
nothing of the program. It comes with the configuration that needs it.
"""
