"""The harness: device refusal, the tiny cells end to end on the CPU,
and a cell added by files alone."""
import filecmp
import json
import os
import types

import pytest

from bench import harness
from bench.tests.tiny import BENCH, ROOT, tiny_root

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


@pytest.mark.parametrize("cell,metrics", [
    ("batch.tiny", {"setup_s", "batch_job_s"}),
])
def test_tiny_cell_runs_correct(root, cell, metrics):
    out = harness.run(cell, 2**33 + 5, 2.0, False, root=root,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == metrics
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == 1


def test_a_cell_is_added_by_new_files_only(root, tmp_path):
    """A new configuration, traffic mix and per-layer metric: new files
    and new BENCHMARK.json entries; every file that was there is
    unchanged."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "bench/configs/g500_batch_counts_tiny.json")
              ) as f:
        cfg = json.load(f)
    cfg["name"] = "g500_batch_small"
    cfg["graph"]["scale"] = 9
    with open(os.path.join(root, "bench/configs/g500_batch_small.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench/traffic/batch_pair.json"), "w") as f:
        json.dump({"driver": "batch_jobs", "job_keys": 2, "key_seed": 7,
                   "warmup_max_rounds": 2}, f)
    with open(os.path.join(root, "bench/metrics/batch_jobs_done.py"),
              "w") as f:
        f.write("def read(r):\n    return len(r.counters['rounds'])\n")
    bench["configs"].append(dict(bench["configs"][0], name="g500_batch_small",
                                 file="bench/configs/g500_batch_small.json"))
    bench["workloads"].append(dict(name="batch.small.pair",
                                   config="g500_batch_small",
                                   traffic="batch_pair", chips=1, why="test"))
    for m in bench["end_to_end"]:
        if "batch.tiny" in m.get("workloads", []):
            m["workloads"].append("batch.small.pair")
    bench["per_layer"].append(dict(name="batch_jobs_done", unit="jobs",
                                   better="higher", source="program_counter",
                                   layer="round driver",
                                   moves="batch_job_s",
                                   workloads=["batch.small.pair"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    out = harness.run("batch.small.pair", 3, 0.5, True, root=root,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["batch_jobs_done"]["value"] == out["attempted"] > 0
    assert "counts_sample_ms" not in out["metrics"]
    cmp = filecmp.dircmp(BENCH, os.path.join(root, "bench"),
                         ignore=["tests", "_out", "__pycache__"])
    changed = []

    def walk(d):
        changed.extend(os.path.join(d.left, f) for f in d.diff_files)
        for sub in d.subdirs.values():
            walk(sub)
    walk(cmp)
    assert changed == []
