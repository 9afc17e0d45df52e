"""A copy of the benchmark at CPU-test sizes, made only by adding files.

`tiny_root(dst)` copies `bench/` into `dst/bench`, writes a small
configuration and traffic mix beside each real one (`<name>_tiny.json`)
and a `BENCHMARK.json` whose cells run them: the same drivers, metrics
and references, on graphs a CPU test can hold.

With fewer walks the sampling noise is wider than at the cells' own
sizes, so the tiny configurations carry limits of their own, set like the
real ones between the readings at this size: sound CPU runs read
grouped_l1 up to 0.0103 (10 job keys), the control 0.037 (3 keys).
"""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY = {
    "g500_batch_counts": dict(scale=10, limits=dict(grouped_l1=0.02),
                              max_rounds=500),
}
CELLS = {"batch.g500": "batch.tiny"}


def tiny_config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    t = TINY[name]
    cfg["name"] = name + "_tiny"
    cfg["graph"]["scale"] = t["scale"]
    cfg["limits"] = t["limits"]
    cfg["engine"]["max_rounds"] = t["max_rounds"]
    return cfg


def tiny_root(dst: str) -> str:
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "_out",
                                                  "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        c["name"] += "_tiny"
        c["file"] = c["file"].replace(".json", "_tiny.json")
        with open(os.path.join(dst, c["file"]), "w") as f:
            json.dump(tiny_config(c["name"][:-5]), f)
    for w in bench["workloads"]:
        old = w["name"]
        w["name"] = CELLS[old]
        w["config"] += "_tiny"
        path = os.path.join(dst, "bench", "traffic", w["traffic"])
        with open(path + ".json") as f:
            traffic = json.load(f)
        w["traffic"] += "_tiny"
        with open(path + "_tiny.json", "w") as f:
            json.dump(traffic, f)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if old in m.get("workloads", []):
                m["workloads"] = [CELLS[old]]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst
