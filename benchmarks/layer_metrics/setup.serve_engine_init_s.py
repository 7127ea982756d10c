"""Self time of the span `serve.engine.init` (`ServingEngine.__init__`: the
cache's allocation, the bf16 snapshot of the weights, the ladder) before the
window: its duration minus the jit events inside it."""
from benchmarks.lib import startup_readers

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "s", "setup_s", "program_span"


def read(run):
    return startup_readers.span_seconds(run, "serve.engine.init", "self_s")
