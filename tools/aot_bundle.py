"""Build-time AOT executable bundle for warm-start serving replicas.

A bundle is a directory holding (a) the XLA persistent compile cache files
that ``ServingEngine.precompile()`` wrote while AOT-compiling the full
serving ladder, and (b) a ``manifest.json`` recording the exact engine
configuration and model seed the executables were lowered against. A fresh
replica process that loads the bundle reconstructs the same model + engine,
re-runs ``precompile()`` against the bundled store, and every compile
deserializes WARM — the replica serves its first request with ZERO cold
compiles (``engine.compile_cold`` delta 0 while ``engine.compile_warm``
grew; the warm>0 half of the assertion matters because both counters stay
flat when the cache is off).

The persistent cache keys hash the optimized HLO + compile options, not the
traced weight values, so a same-config model built in a different process
hits the same entries. Bit-identical tokens across build and join processes
additionally need the same model weights — the manifest pins the init seed
for that.

Multi-device gating rides on ``ServingEngine.precompile()``'s probe
(analysis.backend.aot_serving_reason): the engine's single-device programs
precompile anywhere; a future sharded serving mesh on XLA CPU would skip
(cache-served multi-device executables are nondeterministic on this jax)
and the manifest records the skip reason instead of a fake warm bundle.

Usage:
  python tools/aot_bundle.py build --out DIR [--slots 4 --ladder 8,16,32
      --max-new 16 --max-seq-len 64 --steps-per-dispatch 8 --seed 0
      --families greedy,sample]
  python tools/aot_bundle.py inspect DIR
"""
from __future__ import annotations

import _bootstrap  # noqa: F401  (checkout-hermetic sys.path, tools/_bootstrap.py)

import argparse
import json
import os
from typing import Any, Dict, Optional, Tuple

MANIFEST = "manifest.json"
FORMAT = 1


def _engine_kwargs(manifest: Dict[str, Any]) -> Dict[str, Any]:
    eng = dict(manifest["engine"])
    eng["ladder"] = tuple(eng["ladder"])
    eng["spec_ladder"] = tuple(eng["spec_ladder"])
    return eng


def _build_model(manifest: Dict[str, Any]):
    """Reconstruct the model the bundle was lowered against. Same seed ->
    same weights -> bit-identical tokens across build/join processes."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForPretraining, gpt_tiny

    if manifest["model"] != "gpt_tiny":
        raise ValueError(f"unknown bundle model {manifest['model']!r}")
    paddle.seed(int(manifest["seed"]))
    model = GPTForPretraining(gpt_tiny())
    model.eval()
    return model


def bundle_manifest(bundle_dir: str) -> Dict[str, Any]:
    with open(os.path.join(bundle_dir, MANIFEST)) as f:
        return json.load(f)


def store_files(bundle_dir: str) -> Tuple[int, int]:
    """(count, total bytes) of persistent-cache payload files."""
    n = b = 0
    for name in os.listdir(bundle_dir):
        if name == MANIFEST:
            continue
        p = os.path.join(bundle_dir, name)
        if os.path.isfile(p):
            n += 1
            b += os.path.getsize(p)
    return n, b


def _refuse_outside_cache() -> None:
    """A bundle IS a compile-cache directory, pointed at through
    FLAGS_compile_cache_dir while it is built or loaded. A cache placed from
    outside is never moved by the program (core/compile_cache.py), so the
    two cannot be combined."""
    from paddle_tpu.core import compile_cache as _cc

    if _cc.placed_from_outside():
        raise RuntimeError(
            f"{_cc.ENV_DIR}={_cc.placed_from_outside()} places the compile "
            f"cache from outside; unset it to build or load an AOT bundle")


def build_bundle(out_dir: str, *, slots: int = 4,
                 ladder: Tuple[int, ...] = (8, 16, 32),
                 max_new_cap: int = 16, max_seq_len: int = 64,
                 steps_per_dispatch: int = 8, seed: int = 0,
                 families: Tuple[str, ...] = ("greedy", "sample"),
                 kv_layout: str = "contiguous",
                 kv_page_tokens: Optional[int] = None,
                 spec_ladder: Tuple[int, ...] = (4,),
                 draft: str = "none",
                 force: bool = False) -> Dict[str, Any]:
    """AOT-compile the full serving ladder into ``out_dir`` and write the
    manifest. Returns the manifest dict (``report.skipped`` non-None means
    the backend probe refused and the bundle holds no executables)."""
    import datetime

    import paddle_tpu as paddle
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.core import compile_cache as _cc
    from paddle_tpu.serving import ServingEngine

    _refuse_outside_cache()
    os.makedirs(out_dir, exist_ok=True)
    engine_kwargs = {
        "slot_count": int(slots), "ladder": tuple(int(x) for x in ladder),
        "max_new_cap": int(max_new_cap), "max_seq_len": int(max_seq_len),
        "steps_per_dispatch": int(steps_per_dispatch),
        "kv_layout": kv_layout, "kv_page_tokens": kv_page_tokens,
        "spec_ladder": tuple(int(x) for x in spec_ladder),
    }
    prev = _flags.flag("compile_cache_dir")
    paddle.set_flags({"compile_cache_dir": out_dir})
    try:
        manifest = {
            "format": FORMAT, "model": "gpt_tiny", "seed": int(seed),
            "engine": {k: (list(v) if isinstance(v, tuple) else v)
                       for k, v in engine_kwargs.items()},
            "families": list(families), "draft": draft,
            "created": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
        }
        model = _build_model(manifest)
        eng = ServingEngine(
            model, draft_model=(model if draft == "self" else None),
            **engine_kwargs)
        report = eng.precompile(families=families, force=force)
        manifest["report"] = {k: v for k, v in report.items()
                              if k != "cache_dir"}
        manifest["store_entries"] = _cc.entries()
        with open(os.path.join(out_dir, MANIFEST), "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
        return manifest
    finally:
        paddle.set_flags({"compile_cache_dir": prev})


def load_engine(bundle_dir: str, model=None, *, force: bool = False,
                keep_cache_flag: bool = False, sink=None):
    """Warm-start a serving replica from a bundle: reconstruct the engine
    at the manifest's exact configuration, point the persistent store at
    the bundle, and precompile — every compile deserializes warm.

    Returns ``(engine, report)``. Pass ``model`` to reuse one already built
    in-process (it must match the manifest config; the executables are
    weight-agnostic so any same-config weights hit). ``keep_cache_flag``
    leaves FLAGS_compile_cache_dir pointing at the bundle after the load
    (lazy late compiles — e.g. an unplanned spec rung — then also classify
    against it); the default restores the caller's flag value."""
    import paddle_tpu as paddle
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.serving import ServingEngine

    _refuse_outside_cache()
    manifest = bundle_manifest(bundle_dir)
    if manifest.get("format") != FORMAT:
        raise ValueError(f"bundle format {manifest.get('format')!r} != "
                         f"{FORMAT} at {bundle_dir}")
    if model is None:
        model = _build_model(manifest)
    kwargs = _engine_kwargs(manifest)
    prev = _flags.flag("compile_cache_dir")
    paddle.set_flags({"compile_cache_dir": bundle_dir})
    try:
        eng = ServingEngine(
            model, sink=sink,
            draft_model=(model if manifest.get("draft") == "self" else None),
            **kwargs)
        report = eng.precompile(families=tuple(manifest["families"]),
                                force=force)
        return eng, report
    finally:
        if not keep_cache_flag:
            paddle.set_flags({"compile_cache_dir": prev})


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    b = sub.add_parser("build", help="AOT-compile a serving bundle")
    b.add_argument("--out", required=True)
    b.add_argument("--slots", type=int, default=4)
    b.add_argument("--ladder", default="8,16,32")
    b.add_argument("--max-new", type=int, default=16)
    b.add_argument("--max-seq-len", type=int, default=64)
    b.add_argument("--steps-per-dispatch", type=int, default=8)
    b.add_argument("--seed", type=int, default=0)
    b.add_argument("--families", default="greedy,sample")
    b.add_argument("--draft", default="none", choices=("none", "self"))
    b.add_argument("--force", action="store_true",
                   help="precompile even where the backend probe refuses")
    i = sub.add_parser("inspect", help="print a bundle's manifest + store")
    i.add_argument("dir")
    args = ap.parse_args()

    if args.cmd == "build":
        manifest = build_bundle(
            args.out, slots=args.slots,
            ladder=tuple(int(x) for x in args.ladder.split(",")),
            max_new_cap=args.max_new, max_seq_len=args.max_seq_len,
            steps_per_dispatch=args.steps_per_dispatch, seed=args.seed,
            families=tuple(args.families.split(",")), draft=args.draft,
            force=args.force)
        n, nbytes = store_files(args.out)
        print(json.dumps(dict(manifest, store_files=n,
                              store_bytes=nbytes), indent=2,
                         sort_keys=True))
    else:
        manifest = bundle_manifest(args.dir)
        n, nbytes = store_files(args.dir)
        print(json.dumps(dict(manifest, store_files=n,
                              store_bytes=nbytes), indent=2,
                         sort_keys=True))


if __name__ == "__main__":
    main()
