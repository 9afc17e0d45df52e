"""A copy of the benchmark at CPU-test sizes, made from data alone.

Every configuration `bench/configs/<config>.json` has its CPU-test size
in a file beside it, `bench/configs/<config>.tiny.json`:

    {"why": "<the readings its limits were set from>",
     "overrides": {"graph.scale": 10, "limits": {...}, ...}}

Each key of `overrides` is a dotted path to a key the configuration
already has, and its value replaces that key's. `tiny_root(dst, src)`
copies `bench/` and `BENCHMARK.json` from the tree `src` into `dst`,
writes each configuration with its overrides applied as
`<config>_tiny.json`, and points that configuration's `file` at it. Cell,
configuration and traffic names stay as they are, so a test runs a cell
by its own name: the same drivers, metrics and references, on graphs a
CPU test can hold. A configuration joins by its files alone.
"""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


class TinySizeError(ValueError):
    """A configuration without its CPU-test size, or an override that
    names no key of the configuration."""


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cells(root: str = ROOT) -> list:
    """The names of the cells of `<root>/BENCHMARK.json`."""
    return [w["name"] for w in benchmark(root)["workloads"]]


def tiny_path(config_file: str) -> str:
    """The CPU-test size beside a configuration's file."""
    return os.path.splitext(config_file)[0] + ".tiny.json"


def tiny_config(config_file: str) -> dict:
    """The configuration in `config_file` with its CPU-test overrides."""
    path = tiny_path(config_file)
    if not os.path.isfile(path):
        raise TinySizeError(f"{config_file} has no CPU-test size: "
                            f"{path} is missing")
    with open(config_file) as f:
        cfg = json.load(f)
    with open(path) as f:
        overrides = json.load(f).get("overrides")
    if not isinstance(overrides, dict):
        raise TinySizeError(f"{path}: no \"overrides\" object")
    for dotted, value in overrides.items():
        *parents, key = dotted.split(".")
        node = cfg
        for part in parents:
            node = node.get(part) if isinstance(node, dict) else None
        if not isinstance(node, dict) or key not in node:
            raise TinySizeError(f"{path}: override {dotted!r} names no key "
                                f"of {config_file}")
        node[key] = value
    return cfg


def tiny_root(dst: str, src: str = ROOT) -> str:
    """`dst` made a benchmark checkout at CPU-test sizes, from the tree
    `src`."""
    shutil.copytree(os.path.join(src, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "_out",
                                                  "__pycache__"))
    bench = benchmark(src)
    for c in bench["configs"]:
        cfg = tiny_config(os.path.join(src, c["file"]))
        c["file"] = os.path.splitext(c["file"])[0] + "_tiny.json"
        with open(os.path.join(dst, c["file"]), "w") as f:
            json.dump(cfg, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst
