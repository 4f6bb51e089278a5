"""A cell, a metric and a configuration added as new files are found
without touching ``run.py``."""

import json
import os
import shutil

from conftest import TINY_ROOT


def test_new_files_are_found(run_cell, tmp_path):
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"

    def edit(path, fn):
        data = json.loads(path.read_text())
        fn(data)
        path.write_text(json.dumps(data))

    # a configuration: its file of sizes
    cfg = json.loads((bdir / "configs" / "gpt2-tiny.json").read_text())
    cfg.update(n_layer=3, n_head=4)
    (bdir / "configs" / "gpt2-tiny3.json").write_text(json.dumps(cfg))
    # a traffic mix: a data file of parameters
    mix = json.loads((bdir / "traffic" / "serve-tiny-open.json").read_text())
    mix["arrivals"] = {"process": "poisson", "rate": 6.0}
    (bdir / "traffic" / "serve-tiny-burst.json").write_text(json.dumps(mix))
    # the cell's file, and a metric's reader
    shutil.copy(bdir / "workloads" / "gpt2-tiny.serve-tiny-open.json",
                bdir / "workloads" / "gpt2-tiny3.serve-tiny-burst.json")
    (bdir / "layer_metrics" / "engine_steps.json").write_text(json.dumps(
        {"name": "engine_steps", "reducer": "engine_stat",
         "args": {"key": "steps"}}))
    cell = "gpt2-tiny3.serve-tiny-burst"

    def entries(b):
        b["configs"].append({"name": "gpt2-tiny3", "source": "test",
                             "file": "benchmark/configs/gpt2-tiny3.json",
                             "reduced": [], "why": "test"})
        b["workloads"].append({"name": cell, "config": "gpt2-tiny3",
                               "traffic": "serve-tiny-burst", "chips": 1,
                               "why": "test"})
        for m in b["end_to_end"] + b["per_layer"]:
            if "workloads" in m and any("serve" in w for w in m["workloads"]):
                m["workloads"].append(cell)
        b["per_layer"].append(
            {"name": "engine_steps", "unit": "count", "better": "lower",
             "source": "program_counter", "layer": "serving front end",
             "moves": "serve_out_tokens_per_s", "workloads": [cell]})

    edit(root / "BENCHMARK.json", entries)
    result, _ = run_cell(cell, root=str(root), trace=1)
    assert result["correct"] is True
    assert result["metrics"]["engine_steps"]["value"] > 0
    assert "decode_batch_fill.open" in result["metrics"]
    result, _ = run_cell(cell, root=str(root), trace=0)
    assert result["metrics"]["serve_request_p95_ms"]["value"] > 0
