"""The span `startup.import`: the first line of `paddle_tpu/__init__.py` to
its last (the package's 180 modules; jax is imported before it by
`benchmarks/run.py`, and that time is the `[start]` note's `jax_ready_s`)."""
from benchmarks.lib import startup_readers

LAYER, UNIT, MOVES, SOURCE = "entry_points", "s", "setup_s", "program_span"


def read(run):
    return startup_readers.span_seconds(run, "startup.import", "total_s")
