"""Pallas TPU kernels (validated in interpret mode on CPU).

Nothing is imported eagerly: ``sched_step`` (the dispatch kernel and its
wrapper) stands alone, while ``ops`` and ``ref`` (the attention and SSD
wrappers and their oracles) reach into ``models``; import the submodule a
caller needs, e.g. ``from repro.kernels import ops, ref``.
"""
