"""The benchmark's own code: traffic, reductions, reference, comparison.

Nothing here is imported by the program, and only ``node.py`` imports the
program. ``corpus.py``, ``client.py``, ``peers.py``, ``reference.py``,
``stats.py``, ``roofline.py`` and ``tracered.py`` import neither JAX nor
``txflow_tpu``, so the worker and client processes never touch the chip.
What belongs to one traffic kind, one arrival schedule or one per-layer
metric is a file beside this directory (``../kinds``, ``../arrivals``,
``../metrics``), found by name (``cells.py``).
"""
