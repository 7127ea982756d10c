"""Every throughput number README quotes must be a committed BENCH_HISTORY row.

VERDICT r4 #10: "a reader can reproduce every number in README from
committed tools". This pins the mechanical half of that promise — the
quoted tok/s figures are exact `value` / `extra.decode_tokens_per_sec`
fields of BENCH_HISTORY.jsonl rows, so README cannot drift into
aspirational numbers without this test failing.
"""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _history_values():
    vals = set()
    with open(os.path.join(ROOT, "BENCH_HISTORY.jsonl")) as f:
        for ln in f:
            try:
                row = json.loads(ln)
            except json.JSONDecodeError:
                continue
            v = row.get("value")
            if isinstance(v, (int, float)):
                vals.add(round(float(v), 1))
            d = (row.get("extra") or {}).get("decode_tokens_per_sec")
            if isinstance(d, (int, float)):
                vals.add(round(float(d), 1))
    return vals


@pytest.mark.slow  # spawns a full collection subprocess (~seconds)
def test_readme_test_count_matches_collection():
    """README's quoted suite size must be the live collected count — a
    stale number is exactly the drift this gate exists for."""
    readme = open(os.path.join(ROOT, "README.md")).read()
    m = re.search(r"collects \*\*(\d+) tests\*\*", readme)
    assert m, "README no longer quotes the collected test count"
    quoted = int(m.group(1))
    res = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/", "--collect-only", "-q",
         "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=240, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    m2 = re.search(r"(\d+) tests collected", res.stdout)
    assert m2, res.stdout[-1500:]
    collected = int(m2.group(1))
    assert quoted == collected, (
        f"README quotes {quoted} tests; collection finds {collected} — "
        f"update the README figure")


def test_readme_round5_numbers_are_committed_history_rows():
    readme = open(os.path.join(ROOT, "README.md")).read()
    m = re.search(r"Round-5 on-chip results(.*?)\n## ", readme, re.S)
    assert m, "README round-5 results section not found"
    section = m.group(1)
    # quoted figures: thousands-separated numbers with or without decimals
    # (94,683.7 AND a rounded 95,000 must both be backed); plain unseparated
    # integers like '16 GB' / years and bracketed block pairs like
    # [512,512] are out of scope
    quoted = {float(x.replace(",", ""))
              for x in re.findall(
                  r"(?<!\[)\b(\d{1,3}(?:,\d{3})+(?:\.\d+)?)\b(?!\])",
                  section)}
    assert quoted, "no quoted tok/s figures found in the round-5 section"
    hist = _history_values()
    missing = {q for q in quoted if round(q, 1) not in hist}
    assert not missing, (
        f"README quotes figures with no committed BENCH_HISTORY row: "
        f"{sorted(missing)}")
