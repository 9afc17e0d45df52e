"""The harness: device refusal, every cell end to end at its CPU-test
size, and a configuration and cell added by files alone."""
import json
import os
import shutil
import types

import pytest

from bench import harness
from bench.tests.tiny import (BENCH, ROOT, TinySizeError, benchmark, cells,
                              tiny_config, tiny_path, tiny_root)

V5E = "TPU v5 lite"


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_cpu_and_unknown_devices_are_refused():
    peaks = harness.load_peaks()
    with pytest.raises(harness.DeviceError, match="no TPU"):
        harness.check_devices([_dev("cpu", "cpu")], 1, peaks)
    with pytest.raises(harness.DeviceError, match="not in bench/peaks"):
        harness.check_devices([_dev("tpu", "TPU v9 imaginary")], 1, peaks)
    with pytest.raises(harness.DeviceError, match="needs 4 chips"):
        harness.check_devices([_dev("tpu", V5E)], 4, peaks)
    device, peak = harness.check_devices([_dev("tpu", V5E)] * 4, 1, peaks)
    assert device == dict(platform="tpu", kind=V5E, count=1)
    assert peak["hbm_bytes_per_s"] == 819e9


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    run = harness.load_module(os.path.join(BENCH, "run.py"), "bench_run")
    assert run.main(["--workload", "batch.g500", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_every_cell_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(ROOT, m["name"]))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", cells())
def test_tiny_cell_runs_correct(root, cell):
    out = harness.run(cell, 2**33 + 5, 2.0, False, root=root,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"]
                                   for m in benchmark()["end_to_end"]
                                   if harness._reports(m, cell)}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == 1


@pytest.mark.parametrize("config", benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_every_configuration_has_a_cpu_size(config):
    """Beside each configuration's file, its CPU-test size: a reason, and
    overrides of keys the configuration has."""
    path = tiny_path(os.path.join(ROOT, config["file"]))
    with open(path) as f:
        tiny = json.load(f)
    assert tiny["why"].strip() and tiny["overrides"]
    with open(os.path.join(ROOT, config["file"])) as f:
        full = json.load(f)
    assert tiny_config(os.path.join(ROOT, config["file"])) != full


@pytest.mark.parametrize("fault,match", [
    ("missing", r"g500_x\.tiny\.json is missing"),
    ("unknown_key", r"g500_x\.tiny\.json: override 'engine\.nope'"),
    ("under_a_number", r"g500_x\.tiny\.json: override 'eps\.scale'"),
])
def test_a_bad_cpu_size_names_its_file_and_key(tmp_path, fault, match):
    """A tree whose one configuration lacks its CPU-test size, or whose
    override names no key of it."""
    configs = tmp_path / "tree" / "bench" / "configs"
    configs.mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "configs", "g500_batch_counts.json"),
                configs / "g500_x.json")
    key = dict(unknown_key="engine.nope", under_a_number="eps.scale")
    if fault in key:
        (configs / "g500_x.tiny.json").write_text(json.dumps(
            dict(why="test", overrides={"graph.scale": 9, key[fault]: 1})))
    bench = benchmark()
    bench["configs"][0]["file"] = "bench/configs/g500_x.json"
    (tmp_path / "tree" / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(TinySizeError, match=match):
        tiny_root(str(tmp_path / "tiny"), src=str(tmp_path / "tree"))


# A new kind of traffic: each job key of the pool once, in the pool's
# order, however long the window; its own driver, built on batch_jobs.
CYCLE_DRIVER = '''"""Traffic driver `batch_cycle`: the pool's job keys once each."""
import time

import numpy as np
from jax.sharding import Mesh

from bench.data import csr_graph
from bench.drivers.batch_jobs import (checks, failed, job_keys,
                                      reference_pi, run_job)
from bench.harness import Window
from repro.core.distributed import AXIS


def setup(run):
    graph = csr_graph(run.config["graph"])
    mesh = Mesh(np.array(run.devices[:1]), (AXIS,))
    keys = job_keys(run.traffic)
    run_job(run.config, graph, mesh, keys[0], max_rounds=2)
    return dict(graph=graph, mesh=mesh, keys=keys, jobs=[])


def measure(run, st, seconds):
    t0 = time.perf_counter()
    st["jobs"] = [run_job(run.config, st["graph"], st["mesh"], k)
                  for k in st["keys"]]
    n = len(st["jobs"])
    return Window(e2e=dict(batch_job_s=(time.perf_counter() - t0) / n),
                  counters=dict(jobs=n), attempted=n,
                  failed=sum(failed(run.config, j) for j in st["jobs"]))


def check(run, st, window):
    return checks(run.config, st["jobs"],
                  reference_pi(run.config, st["graph"]))
'''


def _appended_only(old, new) -> bool:
    """`new` is `old` with entries appended to lists, and nothing else."""
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(map(_appended_only, old, new)))
    if isinstance(old, dict):
        return (isinstance(new, dict) and old.keys() == new.keys()
                and all(_appended_only(old[k], new[k]) for k in old))
    return old == new


def _files(tree) -> dict:
    return {str(p.relative_to(tree)): p.read_bytes()
            for p in tree.rglob("*") if p.is_file()}


def test_a_cell_is_added_by_new_files_only(tmp_path):
    """A new configuration with its CPU-test size, traffic mix, driver and
    per-layer metric, added to a copy of the benchmark by new files and
    appended BENCHMARK.json entries, runs correct at its CPU-test size;
    every file that was there is unchanged."""
    tree = tmp_path / "tree"
    shutil.copytree(BENCH, tree / "bench", ignore=shutil.ignore_patterns(
        "tests", "_out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    before = _files(tree)
    old_bench = json.loads(before.pop("BENCHMARK.json"))

    configs = tree / "bench" / "configs"
    cfg = json.loads((configs / "g500_batch_counts.json").read_text())
    cfg["name"] = "g500_batch_other"
    cfg["graph"]["seed"] = 23
    (configs / "g500_batch_other.json").write_text(json.dumps(cfg))
    (configs / "g500_batch_other.tiny.json").write_text(json.dumps(dict(
        why="test", overrides={"graph.scale": 9, "engine.max_rounds": 500,
                               "limits": {"grouped_l1": 0.02}})))
    (tree / "bench" / "traffic" / "batch_pair.json").write_text(json.dumps(
        {"driver": "batch_cycle", "job_keys": 2, "key_seed": 7}))
    (tree / "bench" / "drivers" / "batch_cycle.py").write_text(CYCLE_DRIVER)
    (tree / "bench" / "metrics" / "batch_jobs_done.py").write_text(
        "def read(r):\n    return r.counters.get(\"jobs\")\n")
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="g500_batch_other",
                                 file="bench/configs/g500_batch_other.json"))
    bench["workloads"].append(dict(name="batch.other.pair",
                                   config="g500_batch_other",
                                   traffic="batch_pair", chips=1, why="test"))
    (batch_job_s,) = [m for m in bench["end_to_end"]
                      if m["name"] == "batch_job_s"]
    batch_job_s["workloads"].append("batch.other.pair")
    bench["per_layer"].append(dict(name="batch_jobs_done", unit="jobs",
                                   better="higher", source="program_counter",
                                   layer="round driver",
                                   moves="batch_job_s",
                                   workloads=["batch.other.pair"]))
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    assert _appended_only(old_bench, bench)
    after = _files(tree)
    assert {k: after[k] for k in before} == before

    root = tiny_root(str(tmp_path / "tiny"), src=str(tree))
    out = harness.run("batch.other.pair", 3, 0.5, False, root=root,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "batch_job_s"}
    out = harness.run("batch.other.pair", 4, 0.5, True, root=root,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["batch_jobs_done"]["value"] == out["attempted"] == 2
    assert "counts_sample_ms" not in out["metrics"]
