"""Seconds of set-up inside jax's backend compile (`jit.backend` events of
the span ring, summed): XLA's compile, or the load from the persistent
cache (`jit.cache_load` lies inside it)."""
from benchmarks.lib import startup_readers

LAYER, UNIT, MOVES, SOURCE = "compile_cache", "s", "setup_s", "program_span"


def read(run):
    return startup_readers.jit_seconds(run, "backend")
