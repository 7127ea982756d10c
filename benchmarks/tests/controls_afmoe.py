"""The controls of the Trinity cell's greedy check: references that must NOT
come out as correct, each fed through `runners/serve_afmoe.check_greedy`
itself in place of the plain reference, against the program as it serves.

  python3 benchmarks/tests/controls_afmoe.py SEED        (on the chip: chiprun)
  JAX_PLATFORMS=cpu python3 benchmarks/tests/controls_afmoe.py SEED --tiny

- `float8`: the reference computed in float8 e4m3, the nearest precision
  below the configuration's bfloat16 (weights, and the stream between
  layers, rounded with `lax.reduce_precision`, which the compiler may not
  drop as it drops a convert pair).
- `one_row_from_elsewhere`: the plain reference, but one of the 16 judged
  positions of each request read from another row. What GAP_TOLERANCE is for.
- the seven wrong references of tests/test_afmoe.py, a piece of the
  reference replaced by a wrong one.

One JSON line a control; the last line names those that passed as correct
though `MUST_FAIL` lists them, and the exit code is 1 if there is one (or if
the plain reference itself fails). `benchmarks/tests/test_trinity.py` runs
the same at the small size on the CPU, where float32 hides nothing. What the
chip read is in PERF.md §6 (PR 28).
"""
from __future__ import annotations

import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import reference_afmoe as reference  # noqa: E402
from benchmarks.runners import serve_afmoe as runner  # noqa: E402

_WINDOW_MASK, _ROUTE_WEIGHTS = reference.window_mask, reference.route_weights
WRONG = {
    "window_off_by_one": (
        "window_mask", lambda s, t, w: _WINDOW_MASK(s, t, w + 1)),
    "rope_on_the_full_layer": ("uses_rope", lambda layer_type: True),
    "gate_dropped": ("apply_gate", lambda o, g: o),
    "qk_norm_dropped": ("qk_norm", lambda x, w, eps: x),
    "bias_used_as_a_weight": (
        "route_weights",
        lambda s, sel, b, c: _ROUTE_WEIGHTS(s + b, sel, b, c)),
    "route_scale_dropped": (
        "route_weights",
        lambda s, sel, b, c: _ROUTE_WEIGHTS(s, sel, b,
                                            dict(c, route_scale=1.0))),
    "key_head_by_modulo": ("kv_head_of", lambda i, nh, kvh: i % kvh),
}
# bfloat16 hides this one on the chip (it moves a token's state by what
# bf16 moves it by; PERF.md §6); float32 sees it in tests/test_afmoe.py
HIDDEN_AT_BF16 = ("bias_used_as_a_weight",)
MUST_FAIL = ("float8", "one_row_from_elsewhere") + tuple(
    n for n in WRONG if n not in HIDDEN_AT_BF16)


@contextlib.contextmanager
def wrong_piece(name: str):
    """The reference with one piece replaced, compiled afresh."""
    piece, wrong = WRONG[name]
    right = getattr(reference, piece)
    setattr(reference, piece, wrong)
    runner._PROGRAMS.clear()
    try:
        yield
    finally:
        setattr(reference, piece, right)
        runner._PROGRAMS.clear()


def _e4m3(x):
    """x rounded to float8 e4m3 under one scale a tensor."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = 2.0 ** jnp.ceil(jnp.log2(jnp.abs(x32).max() / 240.0))
    return (jax.lax.reduce_precision(x32 / scale, 4, 3) * scale).astype(
        x.dtype)


def float8(state: dict, config: dict, ids, positions):
    return runner.reference_outputs(state, config, ids, positions,
                                    lower=runner._program("e4m3", _e4m3))


def one_row_from_elsewhere(state: dict, config: dict, ids, positions):
    """The plain reference, its sixth judged position read from the row
    half as far into the sequence."""
    return runner.reference_outputs(
        state, config, ids, positions.at[5].set(positions[5] // 2))


def readings(config: dict, engine: dict, seed: int, names=None) -> dict:
    """-> {control: what `check_greedy` returned}, `plain` first."""
    from paddle_tpu.serving import ServingEngine

    model = runner.build_model(config, seed)
    eng = ServingEngine(model, **dict(engine, ladder=tuple(engine["ladder"])))
    names = list(names if names is not None
                 else ("float8", "one_row_from_elsewhere") + tuple(WRONG))
    out = {"plain": runner.check_greedy(eng, model, config, seed)}
    for name in names:
        if name in WRONG:
            with wrong_piece(name):
                out[name] = runner.check_greedy(eng, model, config, seed)
        else:
            out[name] = runner.check_greedy(eng, model, config, seed,
                                            outputs=globals()[name])
    return out


TINY_ENGINE = {"slot_count": 4, "max_seq_len": 48, "ladder": [8, 16, 32],
               "max_new_cap": 16, "steps_per_dispatch": 4}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    tiny = "--tiny" in argv
    seed = int(next(a for a in argv if not a.startswith("--")))
    if tiny:
        from benchmarks.tests.test_trinity import TINY as config

        engine = TINY_ENGINE
    else:
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "trinity-mini.json")) as f:
            config = json.load(f)
        with open(os.path.join(ROOT, "benchmarks", "workloads",
                               "serve-trinity-mini-decode.json")) as f:
            engine = json.load(f)["engine"]
    got = readings(config, engine, seed)
    for name, check in got.items():
        print(json.dumps({"control": name, "seed": seed, **check}),
              flush=True)
    passed = [n for n in MUST_FAIL if n in got and got[n]["ok"]]
    print(json.dumps({"plain_ok": got["plain"]["ok"],
                      "passed_though_wrong": passed}))
    return 0 if got["plain"]["ok"] and not passed else 1


if __name__ == "__main__":
    sys.exit(main())
