"""The harness finds configurations, cells and metrics by name."""
import json
import shutil
from pathlib import Path

import pytest

from benchlib import catalog

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def test_every_benchmark_entry_has_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]) == BENCH / "configs" / f"{c['name']}.json"
        assert catalog.load_json(ROOT / c["file"])["name"] == c["name"]
    for w in spec["workloads"]:
        wl = catalog.load_json(BENCH / "workloads" / f"{w['name']}.json")
        assert wl["config"] == w["config"]
    for m in spec["per_layer"]:
        assert callable(catalog.reader(m["name"]))


@pytest.mark.parametrize("name", ["cnn-xdevice-hics", "mlp-xsilo-hics",
                                  "cnn-paper-hics"])
def test_cell_loads_with_its_metrics(name):
    cell = catalog.cell(name)
    assert cell.config["name"] == cell.entry["config"]
    assert {m["name"] for m in cell.end_to_end} == {
        "updates_per_s", "peak_hbm_mb", "setup_s"}
    assert len(cell.per_layer) == 11
    assert set(cell.workload["limits"]) == {
        "loss", "update", "delta_b", "entropy", "distance", "selection"}


def test_a_new_cell_is_found_from_its_files_alone(tmp_path):
    """A throwaway cell added as a workload file and a BENCHMARK.json
    entry: the harness finds it with no file of the harness edited."""
    (tmp_path / "bench").mkdir()
    shutil.copytree(BENCH / "configs", tmp_path / "bench" / "configs")
    shutil.copytree(BENCH / "workloads", tmp_path / "bench" / "workloads")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "throwaway", "config": "paper-mlp",
                              "traffic": "throwaway", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "only.throwaway", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "updates_per_s",
                              "workloads": ["throwaway"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    wl = json.loads((BENCH / "workloads" / "mlp-xsilo-hics.json")
                    .read_text())
    wl.update(name="throwaway", num_clients=7)
    (tmp_path / "bench" / "workloads" / "throwaway.json").write_text(
        json.dumps(wl))
    cell = catalog.cell("throwaway", root=tmp_path)
    assert cell.workload["num_clients"] == 7
    assert cell.config["name"] == "paper-mlp"
    assert "only.throwaway" in {m["name"] for m in cell.per_layer}
    # a metric listed for one cell only stays out of the others
    other = catalog.cell("mlp-xsilo-hics", root=tmp_path)
    assert "only.throwaway" not in {m["name"] for m in other.per_layer}


def test_a_new_metric_reader_is_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "x.share.py").write_text(
        "def read(rec):\n    return rec.get('x')\n")
    read = catalog.reader("x.share", bench_dir=tmp_path)
    assert read({"x": 3.5}) == 3.5
    assert read({}) is None


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="unknown workload"):
        catalog.cell("no-such-cell")
