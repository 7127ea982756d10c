"""Generate API.spec: the public API signature inventory.

Reference: paddle/fluid/API.spec + tools/check_api_compatible.py — CI diffs
the committed spec against the live package so accidental signature breaks
fail a test instead of shipping. Regenerate after an intentional API change:

    PYTHONPATH=. python tools/gen_api_spec.py > API.spec
"""
from __future__ import annotations

import inspect

MODULES = [
    "paddle_tpu",
    "paddle_tpu.nn",
    "paddle_tpu.nn.functional",
    "paddle_tpu.nn.initializer",
    "paddle_tpu.optimizer",
    "paddle_tpu.optimizer.lr",
    "paddle_tpu.amp",
    "paddle_tpu.autograd",
    "paddle_tpu.distributed",
    "paddle_tpu.distributed.fleet",
    "paddle_tpu.static",
    "paddle_tpu.static.nn",
    "paddle_tpu.jit",
    "paddle_tpu.io",
    "paddle_tpu.metric",
    "paddle_tpu.vision.models",
    "paddle_tpu.vision.transforms",
    "paddle_tpu.text",
    "paddle_tpu.sparse",
    "paddle_tpu.fft",
    "paddle_tpu.linalg",
    "paddle_tpu.distribution",
    "paddle_tpu.incubate",
    "paddle_tpu.inference",
    "paddle_tpu.serving",
    "paddle_tpu.profiler",
    "paddle_tpu.observability",
    "paddle_tpu.onnx",
    "paddle_tpu.analysis",
]


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def collect() -> list[str]:
    import importlib

    lines = []
    for mod_name in MODULES:
        try:
            mod = importlib.import_module(mod_name)
        except ImportError:
            lines.append(f"{mod_name} MISSING")
            continue
        public = getattr(mod, "__all__", None)
        if public is None:
            public = [n for n in dir(mod) if not n.startswith("_")]
        for name in sorted(set(public)):
            obj = getattr(mod, name, None)
            if obj is None:
                continue
            if inspect.ismodule(obj):
                continue
            if inspect.isclass(obj):
                lines.append(f"{mod_name}.{name} class{_sig(obj.__init__)}")
            elif callable(obj):
                lines.append(f"{mod_name}.{name} {_sig(obj)}")
            else:
                lines.append(f"{mod_name}.{name} value:{type(obj).__name__}")
    return lines


def main():
    for line in collect():
        print(line)


if __name__ == "__main__":
    main()
