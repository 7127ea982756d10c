"""Compilations of the train step inside the window (`engine.jit_compiles`);
0 expected, and any makes the run incorrect."""
LAYER, UNIT, MOVES, SOURCE = "train_engine", "count", "train_tokens_per_s", "program_counter"


def read(run):
    counters = run.get("window_counters")
    if counters is None or "engine.jit_compiles" not in counters:
        return None
    return counters["engine.jit_compiles"]
