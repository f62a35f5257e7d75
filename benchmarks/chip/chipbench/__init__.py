"""Chip benchmark harness: open-loop routed serving on one TPU.

Everything that belongs to one model configuration, one traffic mix, one
tier kind (architecture) or one metric lives in a file of its own under
``benchmarks/chip`` and is found by the name ``BENCHMARK.json`` or the
configuration gives it (see ``spec``).
"""
