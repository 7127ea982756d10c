"""Seconds of set-up in which jax LOWERED a traced jit to StableHLO
(`jit.lower` events of the span ring, summed): Python, and what PR 31 was
refused for (CPython's frame chunks under the lowering loop)."""
from benchmarks.lib import startup_readers

LAYER, UNIT, MOVES, SOURCE = "compile_cache", "s", "setup_s", "program_span"


def read(run):
    return startup_readers.jit_seconds(run, "lower")
