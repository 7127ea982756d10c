"""Self time of the span `engine.init` (`TrainStepEngine.__init__`, what
`fleet.distributed_engine` builds: the placed parameters and the optimizer's
state): its duration minus the jit events inside it."""
from benchmarks.lib import startup_readers

LAYER, UNIT, MOVES, SOURCE = "train_engine", "s", "setup_s", "program_span"


def read(run):
    return startup_readers.span_seconds(run, "engine.init", "self_s")
