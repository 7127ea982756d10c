"""Lint BENCHMARK.json and the files it names against the contract, before
hand-in:

    python3 benchmarks/lint_manifest.py [root of the checkout]

Prints each problem on a line and exits 1 if there is any. The rules are the
contract's own (names, units, lengths, bounds, chips) plus this harness's:
every cell, configuration, traffic mix, runner and per-layer metric that is
named has its file, every such file is named, and what a file says of itself
(a reader's LAYER, UNIT, MOVES, SOURCE; a cell's config, traffic, chips and
its list of per-layer metrics) agrees with the manifest. Imports nothing of
the program and not jax.
"""
from __future__ import annotations

import ast
import json
import os
import re
import sys
from typing import List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.-][A-Za-z0-9_./-]{0,199}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|head_dim|expan|experts_per_tok|n_embd|n_inner)")


def _line(text, what, problems, limit=200):
    if not (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text):
        problems.append(f"{what}: must be 1 to {limit} characters on one "
                        f"line with no tab, not {text!r}")


def _reader_constants(path: str) -> dict:
    """LAYER, UNIT, MOVES, SOURCE of a reader, read without importing it."""
    tree = ast.parse(open(path).read())
    out = {}
    for node in tree.body:
        if isinstance(node, ast.Assign):
            target = node.targets[0]
            names = ([e.id for e in target.elts]
                     if isinstance(target, ast.Tuple) else [target.id])
            values = (node.value.elts if isinstance(node.value, ast.Tuple)
                      else [node.value])
            for n, v in zip(names, values):
                if isinstance(v, ast.Constant):
                    out[n] = v.value
    return out


def lint(root: str) -> List[str]:
    problems: List[str] = []
    path = os.path.join(root, "BENCHMARK.json")
    raw = open(path).read()
    if len(raw.encode()) > 64 * 1024:
        problems.append("BENCHMARK.json is over 64 KiB")
    man = json.loads(raw)
    if set(man) != TOP_KEYS:
        problems.append(f"top-level keys {sorted(man)} are not exactly "
                        f"{sorted(TOP_KEYS)}")
        return problems
    for section, keys in KEYS.items():
        for entry in man[section]:
            extra = set(entry) - keys - ({"workloads"} if section in (
                "end_to_end", "per_layer") else set())
            missing = keys - set(entry)
            if extra or missing:
                problems.append(f"{section} {entry.get('name')!r}: extra "
                                f"keys {sorted(extra)}, missing "
                                f"{sorted(missing)}")
    if problems:
        return problems

    paths = man["paths"]
    if not 1 <= len(paths) <= 16:
        problems.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            problems.append(f"paths: {p!r} is not a relative path of "
                            f"letters, digits, _ . - /")
    cmd = man["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        problems.append("command: a list of at most 32 strings")
    for word in cmd:
        _line(word, "command word", problems)
        if word.startswith("/") or ".." in word.split("/"):
            problems.append(f"command: {word!r} leads out of the repo")
        if os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p + "/") for p in paths):
            problems.append(f"command: {word!r} is a file outside paths")
    rs = man["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        problems.append(f"run_seconds: a whole number from 1 to 51, not {rs}")
    here = os.path.join(root, paths[0])

    def names_of(section, limit):
        seen = [e["name"] for e in man[section]]
        if not 1 <= len(seen) <= limit:
            problems.append(f"{section}: 1 to {limit} entries, not "
                            f"{len(seen)}")
        for n in seen:
            if not NAME.match(str(n)):
                problems.append(
                    f"{section} name {n!r}: must be 1 to 64 characters from "
                    f"letters, digits, '_', '.' and '-', starting with a "
                    f"letter, digit or '_'")
        if len(set(seen)) != len(seen):
            problems.append(f"{section}: a name appears twice")
        return seen

    configs = names_of("configs", 24)
    cells = names_of("workloads", 24)
    names_of("end_to_end", 16)
    names_of("per_layer", 128)
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    if len(set(metric_names)) != len(metric_names):
        problems.append("two metrics share a name")

    files = set()
    for c in man["configs"]:
        _line(c["source"], f"config {c['name']} source", problems)
        _line(c["why"], f"config {c['name']} why", problems)
        f = c["file"]
        if not any(f.startswith(p + "/") for p in paths) or f in files:
            problems.append(f"config {c['name']}: file {f!r} must lie under "
                            f"paths and be no other configuration's")
        files.add(f)
        if not os.path.isfile(os.path.join(root, f)):
            problems.append(f"config {c['name']}: no file {f}")
        elif not isinstance(json.load(open(os.path.join(root, f))), dict):
            problems.append(f"config {c['name']}: {f} is not a JSON object")
        if len(c["reduced"]) > 16:
            problems.append(f"config {c['name']}: over 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key) or WIDTH.search(key):
                problems.append(f"config {c['name']}: reduced names "
                                f"{key!r}, a width or not a name")
        if c["name"] not in {w["config"] for w in man["workloads"]}:
            problems.append(f"config {c['name']}: used by no cell")
    if os.path.isdir(os.path.join(here, "configs")):
        for f in sorted(os.listdir(os.path.join(here, "configs"))):
            if f"{paths[0]}/configs/{f}" not in files:
                problems.append(f"configs/{f}: named by no configuration")

    pairs = set()
    for w in man["workloads"]:
        _line(w["why"], f"cell {w['name']} why", problems)
        for key in ("config", "traffic"):
            if not NAME.match(str(w[key])):
                problems.append(f"cell {w['name']}: {key} {w[key]!r} is "
                                f"not a name")
        if w["config"] not in configs:
            problems.append(f"cell {w['name']}: no configuration "
                            f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            problems.append(f"cell {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            problems.append(f"cell {w['name']}: its pair of configuration "
                            f"and traffic appears twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in man["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        problems.append(f"{four} of {len(cells)} cells ask for four chips: "
                        f"at most a quarter, rounded down, and one always")

    def reporting(metric):
        return set(metric.get("workloads", cells))

    e2e = {m["name"]: m for m in man["end_to_end"]}
    if "setup_s" not in e2e:
        problems.append("end_to_end: setup_s is missing")
    elif "workloads" in e2e["setup_s"]:
        problems.append("setup_s: every cell reports it; no workloads key")
    for m in man["end_to_end"] + man["per_layer"]:
        if not UNIT.match(str(m["unit"])):
            problems.append(f"metric {m['name']}: unit {m['unit']!r} is not "
                            f"1 to 16 of letters, digits, _ / % . -")
        if m["better"] not in ("lower", "higher"):
            problems.append(f"metric {m['name']}: better is lower or higher")
        if m["source"] not in SOURCES:
            problems.append(f"metric {m['name']}: source {m['source']!r}")
        for c in reporting(m) - set(cells):
            problems.append(f"metric {m['name']}: no cell {c!r}")
    for m in man["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            problems.append(f"end-to-end {m['name']}: source is host_clock "
                            f"or device_trace")
        if not (isinstance(m["bound"], (int, float))
                and 0 < m["bound"] <= 0.1):
            problems.append(f"end-to-end {m['name']}: bound {m['bound']} "
                            f"is not in (0, 0.1]")
    for m in man["per_layer"]:
        if not NAME.match(str(m["layer"])):
            problems.append(
                f"per_layer metric {m['name']}: layer must be 1 to 64 "
                f"characters from letters, digits, '_', '.' and '-', "
                f"starting with a letter, digit or '_', not {m['layer']!r}")
        if "bound" in m:
            problems.append(f"per_layer metric {m['name']}: has a bound")
        target = e2e.get(m["moves"])
        if target is None:
            problems.append(f"per_layer metric {m['name']}: moves "
                            f"{m['moves']!r}, which is no end-to-end metric")
        elif not reporting(m) <= reporting(target):
            problems.append(
                f"per_layer metric {m['name']}: moves {m['moves']}, which "
                f"{sorted(reporting(m) - reporting(target))} do not report")
    for c in cells:
        mine = [m["name"] for m in man["end_to_end"] if c in reporting(m)]
        if "setup_s" not in mine or len(mine) < 2:
            problems.append(f"cell {c}: reports setup_s and at least one "
                            f"other end-to-end metric, not {mine}")
        if not any(c in reporting(m) for m in man["per_layer"]):
            problems.append(f"cell {c}: reports no per-layer metric")

    # this harness's files: each named thing has one, each file is named
    runners, traffics = set(), set()
    for w in man["workloads"]:
        f = os.path.join(here, "workloads", w["name"] + ".json")
        if not os.path.isfile(f):
            problems.append(f"cell {w['name']}: no file workloads/"
                            f"{w['name']}.json")
            continue
        cell = json.load(open(f))
        for key in ("config", "traffic", "chips"):
            if cell.get(key) != w[key]:
                problems.append(f"cell {w['name']}: {key} is "
                                f"{cell.get(key)!r} in its file and "
                                f"{w[key]!r} in BENCHMARK.json")
        runners.add(cell.get("runner"))
        traffics.add(w["traffic"])
        if not os.path.isfile(os.path.join(
                here, "runners", f"{cell.get('runner')}.py")):
            problems.append(f"cell {w['name']}: no runner "
                            f"runners/{cell.get('runner')}.py")
        if not os.path.isfile(os.path.join(
                here, "traffic", w["traffic"] + ".json")):
            problems.append(f"cell {w['name']}: no traffic file "
                            f"traffic/{w['traffic']}.json")
        want = sorted(m["name"] for m in man["per_layer"]
                      if w["name"] in reporting(m))
        if sorted(cell.get("layer_metrics", [])) != want:
            problems.append(f"cell {w['name']}: its file lists per-layer "
                            f"metrics {sorted(cell.get('layer_metrics', []))}"
                            f", BENCHMARK.json gives it {want}")
    for m in man["per_layer"]:
        f = os.path.join(here, "layer_metrics", m["name"] + ".py")
        if not os.path.isfile(f):
            problems.append(f"per_layer metric {m['name']}: no reader "
                            f"layer_metrics/{m['name']}.py")
            continue
        said = _reader_constants(f)
        for const, key in (("LAYER", "layer"), ("UNIT", "unit"),
                           ("MOVES", "moves"), ("SOURCE", "source")):
            if said.get(const) != m[key]:
                problems.append(f"per_layer metric {m['name']}: its reader "
                                f"says {const} = {said.get(const)!r}, "
                                f"BENCHMARK.json says {m[key]!r}")
    for folder, named, ext in (
            ("workloads", set(cells), ".json"), ("traffic", traffics, ".json"),
            ("layer_metrics", {m["name"] for m in man["per_layer"]}, ".py"),
            ("runners", runners | {"__init__", "common"}, ".py")):
        d = os.path.join(here, folder)
        for f in sorted(os.listdir(d)) if os.path.isdir(d) else []:
            if f.endswith(ext) and f[:-len(ext)] not in named:
                problems.append(f"{folder}/{f}: named by nothing in "
                                f"BENCHMARK.json")
    for p in paths:
        for d, dirs, fs in os.walk(os.path.join(root, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), root)
                if not PATH.match(rel):
                    problems.append(f"{rel}: a file under paths is named "
                                    f"from letters, digits, _ . - and /")
    return problems


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    problems = lint(root)
    for p in problems:
        print(p)
    print(f"{len(problems)} problem(s) in {os.path.join(root, 'BENCHMARK.json')}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
