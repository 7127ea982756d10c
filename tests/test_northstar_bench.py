"""tools/northstar_bench.py must stay runnable: a bitrotted bench discovered
at measurement time wastes chip time."""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_northstar_bench_smoke_all_configs():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "northstar_bench.py"),
         "--device", "cpu", "--smoke"],
        capture_output=True, text=True, timeout=540, cwd=repo)
    rows = [json.loads(ln) for ln in out.stdout.splitlines() if ln.strip()]
    assert len(rows) == 3, (out.stdout, out.stderr[-800:])
    by = {r["config"]: r for r in rows}
    for name in ("mnist_dygraph", "resnet50", "widedeep"):
        assert "error" not in by[name], by[name]
        assert by[name]["value"] > 0
    # the eager path must actually train (loss finite and sane)
    assert by["mnist_dygraph"]["final_loss"] < 3.0
