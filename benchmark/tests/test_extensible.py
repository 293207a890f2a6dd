"""A later PR adds a cell, a configuration, a traffic mix and a
per-layer metric by adding files and one entry each, and edits no file
that is here: shown on a temporary copy of the benchmark."""

import json
import os
import shutil

from test_rehearsal import ROOT, last_line, run_py


def test_dummy_cell_config_mix_and_metric_by_files_alone(tmp_path):
    before = {}
    for d, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            before[os.path.relpath(p, ROOT)] = open(p, "rb").read()
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    bm = tmp_path / "benchmark"

    # a configuration: its file of sizes (the plain reference it names
    # is one that is there; a new architecture brings its own file)
    cfg = json.load(open(bm / "configs" / "mistral-7b-v0.3-train-L2.json"))
    cfg.update(num_hidden_layers=1, deployment="dummy")
    json.dump(cfg, open(bm / "configs" / "dummy-L1.json", "w"))
    # a traffic mix: a data file for the one general generator
    mix = json.load(open(bm / "traffic" / "pretrain-seq4k.json"))
    mix.update(batch=2, what="dummy job")
    json.dump(mix, open(bm / "traffic" / "dummy-job.json", "w"))
    # the cell's limits, and a per-layer metric's reader
    shutil.copy(bm / "limits" / "train.mistral7b-L2.seq4k.json",
                bm / "limits" / "train.dummy.json")
    (bm / "layer_metrics" / "train.dummy_steps.py").write_text(
        "def read(ctx):\n    return float(ctx['run']['steps'])\n")
    (bm / "layer_metrics" / "train.dummy_silent.py").write_text(
        "def read(ctx):\n    return None\n")

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "dummy-L1", "source": cfg["source"],
        "file": "benchmark/configs/dummy-L1.json",
        "reduced": cfg["reduced"], "why": "dummy"})
    bench["workloads"].append({
        "name": "train.dummy", "config": "dummy-L1", "traffic": "dummy-job",
        "chips": 1, "why": "dummy"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("train.dummy")
    for name in ("train.dummy_steps", "train.dummy_silent"):
        bench["per_layer"].append({
            "name": name, "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "train step",
            "moves": "train_tokens_per_s", "workloads": ["train.dummy"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    tiny = os.path.join("benchmark", "tests", "tiny_train.json")
    common = ["--workload", "train.dummy", "--seed", "9", "--seconds", "2",
              "--rehearsal", tiny]
    line = last_line(run_py([*common, "--trace", "0"], root=str(tmp_path)))
    assert line["correct"] is True
    assert "cpu_rehearsal.train_tokens_per_s" in line["metrics"]
    line = last_line(run_py([*common, "--trace", "1"], root=str(tmp_path)))
    got = line["metrics"]
    assert got["cpu_rehearsal.train.dummy_steps"]["value"] >= 1
    # a reader that finds nothing is left out, never reported as 0
    assert "cpu_rehearsal.train.dummy_silent" not in got
    # metrics without a workloads key follow the cell's end-to-end metric
    assert "cpu_rehearsal.train.report_ms_p50" in got
    assert "cpu_rehearsal.train.flash_roofline" not in got   # lists its cells
    assert not any(k.startswith("cpu_rehearsal.serve") for k in got)

    # and nothing that was there changed
    for rel, data in before.items():
        assert open(tmp_path / rel, "rb").read() == data, rel
