"""Wall time of the first call of each executable of the program during
set-up: compilation, or loading from the persistent cache
(`engine.compile_cold_ms` + `engine.compile_warm_ms`)."""
LAYER, UNIT, MOVES, SOURCE = "compile_cache", "s", "setup_s", "program_counter"


def read(run):
    counters = run.get("setup_counters")
    if counters is None:
        return None
    return (counters.get("engine.compile_cold_ms", 0)
            + counters.get("engine.compile_warm_ms", 0)) / 1e3
