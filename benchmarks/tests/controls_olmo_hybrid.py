"""The controls of the Olmo-Hybrid cell's greedy check: references that must
NOT come out as correct, each fed through `runners/serve_hybrid.check_greedy`
itself in place of the plain reference, against the program as it serves.

  python3 benchmarks/tests/controls_olmo_hybrid.py SEED     (on the chip: chiprun)
  JAX_PLATFORMS=cpu python3 benchmarks/tests/controls_olmo_hybrid.py SEED --tiny

- `float8`: the reference computed in float8 e4m3, the nearest precision
  below the configuration's bfloat16 (every matrix, and the stream between
  layers, rounded with `lax.reduce_precision`, which the compiler may not
  drop as it drops a convert pair).
- seven wrong references, a piece of the reference replaced by a wrong one:
  the pad not masked (the state runs on over the positions after the ones
  the slot absorbed), the tail taken from the pad (the last inputs of the
  padded sequence, not the last the slot absorbed), beta not doubled, the
  decay applied after the update, the convolution's inputs off by one
  position, q and k not normalised, the state kept in bfloat16 between two
  positions.
- one later layer wrong ALONE, the layers before and after it plain (the
  check holds only the first linear layer tightly, so these say what its
  loose all-layer limits see): beta not doubled in the last linear layer
  and the decay after the update in the third linear layer from the end
  must fail; the state kept in bfloat16 in that layer is read and printed
  but is not in `MUST_FAIL`: what it adds lies under what bf16 adds to the
  stream, and it reads as the plain reference does (PERF.md section 7
  names it as a fault that passes).

One JSON line a control; the last line names those that passed as correct
though `MUST_FAIL` lists them, and the exit code is 1 if there is one (or if
the plain reference itself fails). `benchmarks/tests/test_olmo_hybrid.py`
runs the same at the small size on the CPU, where float32 hides nothing.
What the chip read is in PERF.md §6 (PR 33).
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import reference_olmo_hybrid as reference  # noqa: E402
from benchmarks.runners import serve_hybrid as runner  # noqa: E402


def _all_real(s, length):
    import jax.numpy as jnp

    return jnp.ones((s,), bool)


def _sigmoid_only(b, cfg):
    import jax

    return jax.nn.sigmoid(b)


def _decay_last(S, k, v, alpha, beta):
    import jax.numpy as jnp

    u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
    return alpha[:, None, None] * (S + k[:, :, None] * u[:, None, :])


def _one_early(padded, t, width):
    import jax

    return jax.lax.dynamic_slice_in_dim(padded, t - 1, width, axis=0)


def _tail_of_the_pad(padded, length, width):
    return padded[-(width - 1):]


def _bfloat16(S):
    import jax

    return jax.lax.reduce_precision(S, 8, 7)


WRONG = {
    "pad_not_masked": ("real_positions", _all_real),
    "tail_from_the_pad": ("held_tail", _tail_of_the_pad),
    "beta_not_doubled": ("beta_of", _sigmoid_only),
    "decay_after_update": ("delta_update", _decay_last),
    "tail_off_by_one": ("conv_input", _one_early),
    "qk_not_normalised": ("unit", lambda x: x),
    "state_in_bfloat16": ("held_state", _bfloat16),
}
# one layer wrong alone: control -> (the wrong piece, which of the linear
# layers, counted from the last)
ALONE = {"last_layer_beta_not_doubled": ("beta_not_doubled", -1),
         "late_layer_decay_after_update": ("decay_after_update", -3),
         "late_layer_state_in_bfloat16": ("state_in_bfloat16", -3)}
MUST_FAIL = ("float8",) + tuple(WRONG) + (
    "last_layer_beta_not_doubled", "late_layer_decay_after_update")

_WRONG_PROGRAMS = {}    # control -> the reference's programs compiled with it


@contextlib.contextmanager
def wrong_piece(name: str):
    """The reference with one piece replaced, compiled apart from the plain
    programs (which stay compiled for the next control)."""
    piece, wrong = WRONG[name]
    right, plain = getattr(reference, piece), runner._PROGRAMS
    setattr(reference, piece, wrong)
    runner._PROGRAMS = _WRONG_PROGRAMS.setdefault(name, {})
    try:
        yield
    finally:
        setattr(reference, piece, right)
        runner._PROGRAMS = plain


def alone(name: str):
    """The reference with a piece wrong in ONE linear layer."""
    piece, which = ALONE[name]

    def outputs(state, config, ids, positions, length, layers=None):
        run = functools.partial(runner.reference_outputs, state, config, ids,
                                positions, length)
        if layers is not None:      # the first pass: layer 0 alone, plain
            return run(layers=layers)
        at = [l for l, kind in enumerate(config["layer_types"])
              if kind == "linear_attention"][which]
        h, before = run(layers=range(at))
        with wrong_piece(piece):
            h, one = run(layers=range(at, at + 1), stream=h)
        logits, after = run(
            layers=range(at + 1, config["num_hidden_layers"]), stream=h)
        return logits, before + one + after

    return outputs


def _e4m3(x):
    """x rounded to float8 e4m3 under one scale a tensor."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = 2.0 ** jnp.ceil(jnp.log2(jnp.abs(x32).max() / 240.0))
    return (jax.lax.reduce_precision(x32 / scale, 4, 3) * scale).astype(
        x.dtype)


def float8(state: dict, config: dict, ids, positions, length, **kw):
    return runner.reference_outputs(state, config, ids, positions, length,
                                    lower=runner._program("e4m3", _e4m3),
                                    **kw)


def readings(config: dict, engine: dict, seed: int, sampling: dict) -> dict:
    """-> {control: what `check_greedy` returned}: `plain`, those that must
    fail, then the one that passes."""
    from paddle_tpu.serving import ServingEngine

    model = runner.build_model(config, seed)
    eng = ServingEngine(model, **dict(engine, ladder=tuple(engine["ladder"])))
    check = functools.partial(runner.check_greedy, eng, model, config, seed,
                              sampling)
    out = {"plain": check()}
    for name in MUST_FAIL + tuple(n for n in ALONE if n not in MUST_FAIL):
        if name in WRONG:
            with wrong_piece(name):
                out[name] = check()
        else:
            out[name] = check(outputs=alone(name) if name in ALONE
                              else globals()[name])
    return out


TINY_ENGINE = {"slot_count": 4, "max_seq_len": 48, "ladder": [8, 16, 32],
               "max_new_cap": 16, "steps_per_dispatch": 4}


def cell_file(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)



def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    tiny = "--tiny" in argv
    seed = int(next(a for a in argv if not a.startswith("--")))
    cell = cell_file("workloads", "serve-olmo-hybrid-decode")
    sampling = cell_file("traffic", cell["traffic"])["sampling"]
    if tiny:
        from benchmarks.tests.test_olmo_hybrid import TINY as config

        engine = TINY_ENGINE
    else:
        config, engine = cell_file("configs", cell["config"]), cell["engine"]
    got = readings(config, engine, seed, sampling)
    for name, check in got.items():
        print(json.dumps({"control": name, "seed": seed, **check}),
              flush=True)
    passed = [n for n in MUST_FAIL if n in got and got[n]["ok"]]
    print(json.dumps({"plain_ok": got["plain"]["ok"],
                      "passed_though_wrong": passed}))
    return 0 if got["plain"]["ok"] and not passed else 1


if __name__ == "__main__":
    sys.exit(main())
