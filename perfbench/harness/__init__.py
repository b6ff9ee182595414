"""The benchmark's own code: traffic, reductions, reference, comparison.

Nothing here is imported by the program, and only ``node.py`` imports the
program. ``corpus.py``, ``client.py``, ``reference.py``, ``stats.py``,
``roofline.py`` and ``tracered.py`` import neither JAX nor ``txflow_tpu``,
so the worker and client processes never touch the chip.
"""
