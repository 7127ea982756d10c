"""The controls of the DeepSeek-V2 cell's greedy check: references that must
NOT come out as correct, each fed through
`runners/serve_deepseek.check_greedy` itself in place of the plain
reference, against the program as it serves.

  python3 benchmarks/tests/controls_deepseek_v2.py SEED     (on the chip: chiprun)
  JAX_PLATFORMS=cpu python3 benchmarks/tests/controls_deepseek_v2.py SEED --tiny
  (`--only=NAME`, any number of times, reads those controls alone)

- `float8`: the reference computed in float8 e4m3, the nearest precision
  below the configuration's bfloat16 (every matrix, and the stream between
  layers, rounded with `lax.reduce_precision`, which the compiler may not
  drop as it drops a convert pair).
- `latent_cache_in_float8`: the reference keeps each position's row
  [n_t | rope(k_r,t)] in float8 e4m3 and projects its keys and values from
  that, everything else float32: how far a latent cache kept below bfloat16
  lies from the served one.
- six wrong references, a piece of the reference replaced by a wrong one:
  the softmax scale without `mscale^2`, plain RoPE without YaRN's blend, the
  top 6 of all 160 experts without the groups, the weights normalised (and
  so not scaled by 16), the shared expert left out, the values of another
  head (`W_uv` of head i + 1 for head i).
- `one_expert_layer_dropped`: the last expert layer's routed rows dropped
  (its result is the shared expert's alone), the layers before it plain.

One JSON line a control; the last line names those that passed as correct
though `MUST_FAIL` lists them, and the exit code is 1 if there is one (or if
the plain reference itself fails). `benchmarks/tests/test_deepseek_v2.py`
runs the same at the small size on the CPU, where float32 hides nothing.
What the chip read is in PERF.md §6 (PR 35).
"""
from __future__ import annotations

import contextlib
import functools
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import reference_deepseek_v2 as reference  # noqa: E402
from benchmarks.runners import serve_deepseek as runner  # noqa: E402

INV_FREQ, ROUTE_WEIGHTS = reference.inv_freq, reference.route_weights


def _e4m3(x):
    """x rounded to float8 e4m3 under one scale a tensor."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = 2.0 ** jnp.ceil(jnp.log2(jnp.abs(x32).max() / 240.0))
    return (jax.lax.reduce_precision(x32 / scale, 4, 3) * scale).astype(
        x.dtype)


def _no_mscale(cfg):
    return 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def _plain_rope(cfg):
    return INV_FREQ(dict(cfg, rope_scaling=None))


def _normalised(scores, sel, cfg):
    return ROUTE_WEIGHTS(scores, sel, dict(cfg, norm_topk_prob=True))


def _next_head(heads):
    return lambda i: (i + 1) % heads


WRONG = {
    "latent_cache_in_float8": ("kept_row", lambda cfg: _e4m3),
    "scale_without_mscale": ("softmax_scale", lambda cfg: _no_mscale),
    "plain_rope_without_yarn": ("inv_freq", lambda cfg: _plain_rope),
    "top_k_without_groups": ("group_limited", lambda cfg: lambda s, c: s),
    "weights_normalised": ("route_weights", lambda cfg: _normalised),
    "shared_expert_left_out": ("shared_expert",
                               lambda cfg: lambda p, m: 0.0),
    "values_of_the_wrong_head": (
        "value_head_of", lambda cfg: _next_head(cfg["num_attention_heads"])),
}
MUST_FAIL = ("float8",) + tuple(WRONG) + ("one_expert_layer_dropped",)

@contextlib.contextmanager
def wrong_piece(name: str, config: dict):
    """The reference with one piece replaced, for as long as the block
    lasts."""
    piece, make = WRONG[name]
    right = getattr(reference, piece)
    setattr(reference, piece, make(config))
    try:
        yield
    finally:
        setattr(reference, piece, right)


def float8(state: dict, config: dict, ids, positions):
    return runner.reference_outputs(state, config, ids, positions,
                                    lower=_e4m3)


def _dropping_layer(p, h, l, cfg, base=0):
    """`reference.layer`, but the last layer's routed experts give nothing."""
    if l == cfg["num_hidden_layers"] - 1:
        return reference.layer(p, h, l, cfg, experts=(base, 0), base=base)
    return reference.layer(p, h, l, cfg, base=base)


def one_expert_layer_dropped(state: dict, config: dict, ids, positions):
    return runner.reference_outputs(state, config, ids, positions,
                                    layer=_dropping_layer)


def readings(config: dict, engine: dict, seed: int, sampling: dict,
             note=lambda name, check: None, only=()) -> dict:
    """-> {control: what `check_greedy` returned}: `plain`, then those that
    must fail (or those of them that `only` names); `note` is told each
    as it is read."""
    from paddle_tpu.serving import ServingEngine

    model = runner.build_model(config, seed)
    eng = ServingEngine(model, **dict(engine, ladder=tuple(engine["ladder"])))
    check = functools.partial(runner.check_greedy, eng, model, config, seed,
                              sampling)
    out = {"plain": check()}
    note("plain", out["plain"])
    for name in (only or MUST_FAIL):
        # each control compiles its own programs and lets them go: the
        # chip has no room for two sets (`forget_programs`)
        runner.forget_programs()
        if name in WRONG:
            with wrong_piece(name, config):
                out[name] = check()
        else:
            out[name] = check(outputs=globals()[name])
        note(name, out[name])
    runner.forget_programs()
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
    cell = cell_file("workloads", "serve-deepseek-v2-decode")
    sampling = cell_file("traffic", cell["traffic"])["sampling"]
    if tiny:
        from benchmarks.tests.test_deepseek_v2 import TINY as config

        engine = TINY_ENGINE
    else:
        config, engine = cell_file("configs", cell["config"]), cell["engine"]
    got = readings(
        config, engine, seed, sampling,
        note=lambda name, check: print(
            json.dumps({"control": name, "seed": seed, **check}), flush=True),
        only=tuple(a[len("--only="):] for a in argv
                   if a.startswith("--only=")))
    passed = [n for n in MUST_FAIL if n in got and got[n]["ok"]]
    print(json.dumps({"plain_ok": got["plain"]["ok"],
                      "passed_though_wrong": passed}))
    return 0 if got["plain"]["ok"] and not passed else 1


if __name__ == "__main__":
    sys.exit(main())
