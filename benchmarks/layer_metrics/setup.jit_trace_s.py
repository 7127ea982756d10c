"""Seconds of set-up in which jax TRACED a jit (`jit.trace` events of the
span ring, outermost intervals that end before the window, summed),
whichever jit it was: a registered executable's, an initializer's, an eager
rule's, the check's reference."""
from benchmarks.lib import startup_readers

LAYER, UNIT, MOVES, SOURCE = "compile_cache", "s", "setup_s", "program_span"


def read(run):
    return startup_readers.jit_seconds(run, "trace")
