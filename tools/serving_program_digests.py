"""Take the digests of the serving programs again: sha256[:16] of the
StableHLO text of every prefill rung and both decode programs of a tiny
GPT-2, Trinity (`afmoe`), Olmo-Hybrid, DeepSeek-V2 and SDAR (`sdar`: its
block prefills and block-step decode programs) `ServingEngine`, as
tests/test_deepseek_v2.py::test_the_other_families_serving_programs_are_the_parents
and tests/test_sdar.py compare them with
tests/data/serving_program_digests.json.

    JAX_PLATFORMS=cpu python tools/serving_program_digests.py            # print
    JAX_PLATFORMS=cpu python tools/serving_program_digests.py --write AT # and
        rewrite the file, `AT` saying at which PR and commit they were taken

The texts are the CPU's, where every kernel's `supported()` says no, so they
pin the plain paths: a PR that leaves them alone compiles nothing anew in
those families' cells off the kernels. One that changes a program on
purpose runs this with `--write` and says so.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import hashlib
import json
import os
import sys

FILE = os.path.join(_bootstrap._REPO, "tests", "data",
                    "serving_program_digests.json")
ENGINE = dict(slot_count=3, ladder=(8, 16), max_seq_len=48, max_new_cap=8,
              steps_per_dispatch=4)


def models():
    """family -> (the seed its weights are drawn from, its constructor)."""
    from paddle_tpu.models import (AfmoeForCausalLM, DeepseekV2ForCausalLM,
                                   GPTForPretraining, OlmoHybridForCausalLM,
                                   SdarForCausalLM, afmoe_tiny,
                                   deepseek_v2_tiny, gpt_tiny,
                                   olmo_hybrid_tiny, sdar_tiny)

    return {"gpt": (0, lambda: GPTForPretraining(gpt_tiny())),
            "afmoe": (2, lambda: AfmoeForCausalLM(afmoe_tiny())),
            "olmo": (4, lambda: OlmoHybridForCausalLM(olmo_hybrid_tiny())),
            "sdar": (6, lambda: SdarForCausalLM(sdar_tiny())),
            "deepseek": (8, lambda: DeepseekV2ForCausalLM(
                deepseek_v2_tiny()))}


def program_texts(eng):
    """name -> the StableHLO text of each program `eng` would compile."""
    import jax.numpy as jnp

    kv, s = eng.slot_cache, eng.slot_count

    def vec(dtype):
        return jnp.zeros((s,), dtype)

    out = {}
    if getattr(eng.model, "generation", None) is not None:
        # a model that generates by diffusion over blocks: its own programs
        for rung in eng.ladder:
            out[f"prefill{rung}"] = eng._build_block_prefill(rung).lower(
                eng._params, *kv.args(), jnp.zeros((1, rung), jnp.int64),
                jnp.int32(4), jnp.int32(0)).as_text()
        for family in ("greedy", "sample"):
            out[f"decode_{family}"] = eng._build_block_decode(family).lower(
                eng._params, *kv.args(), *eng._host_carry(),
                *eng._host_consts()).as_text()
        return out
    for rung in eng.ladder:
        out[f"prefill{rung}"] = eng._build_prefill(rung).lower(
            eng._params, *kv.args(), jnp.zeros((1, rung), jnp.int64),
            jnp.int32(3), jnp.int32(0), jnp.float32(0.0), jnp.int32(0),
            jnp.float32(1.0), jnp.int32(0)).as_text()
    for family in ("greedy", "sample"):
        out[f"decode_{family}"] = eng._build_decode(family).lower(
            eng._params, *kv.args(), vec(jnp.int32), vec(jnp.int32),
            vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.int32),
            vec(jnp.int32)).as_text()
    return out


def digests(family: str) -> dict:
    """`family.program` -> digest, of one family's tiny engine."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine

    seed, make = models()[family]
    paddle.seed(seed)
    model = make()
    model.eval()
    eng = ServingEngine(model, **ENGINE)
    return {f"{family}.{k}": hashlib.sha256(t.encode()).hexdigest()[:16]
            for k, t in program_texts(eng).items()}


def main(argv) -> int:
    got = {}
    for family in ("afmoe", "deepseek", "gpt", "olmo", "sdar"):
        got.update(digests(family))
    got = dict(sorted(got.items()))
    if "--write" in argv:
        at = argv[argv.index("--write") + 1]
        with open(FILE, "w") as f:
            json.dump({"at": at, "digests": got}, f, indent=1)
            f.write("\n")
    print(json.dumps(got, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
