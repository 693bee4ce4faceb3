"""The harness finds every piece of a cell by name, and BENCHMARK.json
agrees with the files under the benchmark's directory."""
import json
import time
from pathlib import Path

import harness
import loader

REPO = Path(__file__).resolve().parents[3]


def test_added_files_are_found_without_edits(bench_root):
    """A new configuration, traffic mix, cell and per-layer metric are
    four new files: nothing that exists changes."""
    before = {p: p.read_bytes() for p in bench_root.rglob("*")
              if p.is_file()}
    (bench_root / "configs" / "dummy.json").write_text(json.dumps({
        "name": "dummy", "generator": "lattice", "side": 6,
        "engine": {"workers": 4, "backend": "dense", "layout": "csr",
                   "balance": "hash", "mirroring": True}}))
    (bench_root / "traffic" / "dummy-pr.json").write_text(json.dumps({
        "algo": "pagerank", "params": {"n_iters": 3, "tol": 0.0,
                                       "damping": 0.85},
        "ref": "pagerank", "counter": "msgs_total",
        "limits": {"pr_max_rel_err": 1e-4}}))
    (bench_root / "workloads" / "pr.dummy.json").write_text(json.dumps({
        "config": "dummy", "traffic": "dummy-pr", "chips": 1}))
    (bench_root / "metrics" / "dummy_jobs.py").write_text(
        'UNIT = "jobs"\n\n\ndef read(rec):\n    return len(rec["jobs"])\n')
    for p, data in before.items():
        assert p.read_bytes() == data
    cell = loader.load_cell("pr.dummy", bench_root)
    assert cell.chips == 1 and cell.config["side"] == 6
    readers = loader.metric_readers(bench_root)
    assert "dummy_jobs" in readers
    res = harness.run_cell(cell, 2**31 + 2, 0.0, False, time.perf_counter(),
                           None)
    assert res["correct"]
    assert readers["dummy_jobs"].read({"jobs": [1, 2]}) == 2


def test_unknown_cell_is_an_error(bench_root):
    import pytest
    with pytest.raises(FileNotFoundError):
        loader.load_cell("nope.nothing", bench_root)


def test_benchmark_json_matches_the_files():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    root = loader.HERE
    readers = loader.metric_readers(root)
    for cfg in spec["configs"]:
        data = json.loads((REPO / cfg["file"]).read_text())
        assert Path(cfg["file"]) == Path("benchmarks/chip/configs") \
            / f"{cfg['name']}.json"
        assert data["name"] == cfg["name"]
        assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    for w in spec["workloads"]:
        data = json.loads((root / "workloads" / f"{w['name']}.json")
                          .read_text())
        assert (data["config"], data["traffic"], data["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        assert (root / "traffic" / f"{w['traffic']}.json").is_file()
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert readers[m["name"]].UNIT == m["unit"]
        assert m["moves"] == ("setup_s" if m["name"] == "partition_s"
                              else "superstep_s")
        assert set(m["workloads"]) <= cells
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    assert units == {"superstep_s": "s", "hbm_peak_gb": "GB",
                     "setup_s": "s"}
