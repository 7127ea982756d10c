"""The part of `setup.jit_trace_s` + `setup.jit_lower_s` +
`setup.jit_backend_s` that lies inside no `exec.first_call`: jits no registry
holds, which `setup.compile_s` cannot see."""
from benchmarks.lib import startup_readers

LAYER, UNIT, MOVES, SOURCE = "compile_cache", "s", "setup_s", "program_span"


def read(run):
    return startup_readers.jit_unregistered_seconds(run)
