"""The benchmark's files hang together: ``BENCHMARK.json`` names only what
exists, every cell finds its configuration, traffic and metric readers by
name, the operation counts match the hand figures, and the harness refuses
to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import costs, loadgen, model, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = run.load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1] == "bench/run.py"
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_load(cell):
    w = CELLS[cell]
    cfg = model.load_config(w["config"])
    assert cfg["name"] == w["config"]
    assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                       w["traffic"] + ".json"))
    e2e, per = run.cell_metrics(BENCH, cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and per


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "bench", "traffic"))))
def test_every_traffic_file_loads_and_names_its_loop(name):
    traffic = run.load_traffic(name)
    assert callable(loadgen.loop_module(traffic["loop"]).workers)
    assert traffic["edges"] >= 1 and traffic["pool"] >= 1
    assert traffic["warm_s"] > 0


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_file_is_the_one_named(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == name and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert name in {w["config"] for w in BENCH["workloads"]}
    assert cfg["limits"]["logit_err"] > 0


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader_and_its_cells_report_what_it_moves(
        metric):
    m = {x["name"]: x for x in BENCH["per_layer"]}[metric]
    assert callable(run.reader(metric).read)
    for cell in m["workloads"]:
        e2e, per = run.cell_metrics(BENCH, cell)
        assert metric in per and m["moves"] in e2e, cell


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]])
def test_end_to_end_metric_has_a_reader_and_its_cells_exist(metric):
    m = {x["name"]: x for x in BENCH["end_to_end"]}[metric]
    assert callable(run.reader(metric).read)
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))


# hand figures per request at deployed widths (every conv after the first
# pruned to half its channels): edge GFLOP, cloud GFLOP, cloud float32
# weight MB, edge im2col patch GB; AlexNet with the paper's 38 classes,
# VGG-16 with its source's 1000
HAND = {"alexnet224": (0.55, 0.07, 143, None),
        "vgg16": (8.7, 0.145, 289, 0.22)}


@pytest.mark.parametrize("name", sorted(HAND))
def test_operation_and_byte_counts_match_the_hand_figures(name):
    cfg = model.load_config(name)
    edge, cloud, weights_mb, patches_gb = HAND[name]
    flops = costs.split_flops(cfg)
    assert flops["edge"] / 1e9 == pytest.approx(edge, rel=0.03)
    assert flops["cloud"] / 1e9 == pytest.approx(cloud, rel=0.03)
    assert costs.cloud_weight_bytes(cfg) / 1e6 == pytest.approx(weights_mb,
                                                                 rel=0.01)
    if patches_gb is not None:
        patches = sum(m * k * 4 for m, k, _ in costs.edge_gemms(cfg))
        assert patches / 1e9 == pytest.approx(patches_gb, rel=0.03)


def test_vgg16_edge_gemms_are_its_13_convs():
    gemms = costs.edge_gemms(model.load_config("vgg16"))
    assert len(gemms) == 13
    assert gemms[0] == (224 * 224, 27, 64)         # conv1_1, unpruned
    assert gemms[-1] == (14 * 14, 512 // 2 * 9, 512 // 2)


def test_roofline_floor_names_its_bound():
    t, bound = costs.masked_matmul_floor_s(3025, 363, 64, 197e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(costs.masked_matmul_bytes(3025, 363, 64)
                              / 819e9)
    t, bound = costs.masked_matmul_floor_s(4096, 4096, 4096, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(2 * 4096**3 / 197e12)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed",
         str(2**33 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no chip" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_run_exits_nonzero_with_only_the_benchmarks_files(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "not in this checkout" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_seeds_wider_than_32_bits_give_distinct_streams():
    a = model.seed_rng(2**33 + 1, 2).integers(1 << 30)
    b = model.seed_rng(1, 2).integers(1 << 30)
    assert a != b
    k = model.seed_key(2**40 + 3, 0)
    assert k.shape == (2,) and str(k.dtype) == "uint32"
