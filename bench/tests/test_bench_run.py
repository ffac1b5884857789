"""Whole runs of the harness at a tiny size on the CPU.

The harness's look for a chip is skipped (``require_tpu=False``) and the
store uses its CPU engine, so a run takes seconds.  A sound run comes out
correct: the dict reference agrees with the store.  With the timed path
broken underneath (``bench/faults.py``), ``correct`` comes out false.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import spec  # noqa: E402

TINY = {"records": 3000, "sst_bytes": 65536, "memtable_bytes": 16384,
        "l1_base_bytes": 131072, "block_cache_blocks": 64, "engine": "cpu"}

#: the faults each traffic mix can have
FAULTS = {
    "load": ["lost_ack", "put_unapplied", "altered_answer"],
    "ycsb-a": ["lost_ack", "put_unapplied", "altered_answer"],
    "mget-c": ["bloom_false_negative", "half_batch", "altered_answer"],
}
#: each mix over a configuration, and the end-to-end metrics it gives
MIXES = {
    "load": ("luda-1kb", ("ops_per_s", "put_p99_us", "setup_s")),
    "ycsb-a": ("luda-128b", ("ops_per_s", "put_p99_us", "get_p99_us",
                             "setup_s")),
    "mget-c": ("luda-128b", ("ops_per_s", "multi_get_p99_us", "setup_s")),
}


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench"))


def tiny_cell(mix: str, work: str) -> spec.Cell:
    config, metrics = MIXES[mix]
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = dict(json.load(f), **TINY)
    path = os.path.join(work, f"{config}.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    return spec.Cell(
        name=f"{mix}.tiny", config_name=config, chips=1, config=cfg,
        config_path=path, traffic=spec.load_traffic(mix, BENCH),
        end_to_end=tuple(spec.Metric(name=m, unit="x", better="lower",
                                     source="host_clock") for m in metrics),
        per_layer=())


def run(mix, work, faults=(), seed=2**31 + 101):
    return harness.run_cell(tiny_cell(mix, work), seed=seed, seconds=0.5,
                            trace=False, t_start=time.perf_counter(),
                            require_tpu=False, faults=faults,
                            work_root=work)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_sound_run_is_correct(mix, work):
    r = run(mix, work)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == set(MIXES[mix][1])
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("mix,fault", [(m, f) for m in sorted(FAULTS)
                                       for f in FAULTS[m]])
def test_planted_fault_is_not_correct(mix, fault, work):
    r = run(mix, work, faults=[fault])
    assert not r["correct"]
    assert r["checks"]["wrong_answers"]["value"] > 0


def test_a_run_leaves_no_working_copy(work):
    run("load", work)
    assert not os.listdir(os.path.join(work, ".work"))


def test_no_chip_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "load.1kb", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_alone_is_not_a_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "load.1kb", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
