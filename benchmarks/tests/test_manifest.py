"""BENCHMARK.json against the data files it names: a cell, a configuration,
a traffic mix or a per-layer metric is added by files and entries alone, so
the entries and the files have to agree."""

import importlib
import json

import pytest

from benchmarks.harness import loops, spec


@pytest.fixture(scope="module")
def man():
    return spec.manifest()


def test_every_cell_has_its_files(man):
    for cell in man["workloads"]:
        cfg = spec.config(man, cell["config"])
        tr = spec.traffic(cell["traffic"])
        assert tr["kind"] in loops.KINDS
        assert cfg["reduced"] == next(
            c for c in man["configs"]
            if c["name"] == cell["config"])["reduced"]
        assert (spec.ROOT / cfg["reference"]).is_file()


def test_every_per_layer_metric_can_be_read(man):
    """BENCHMARK.json alone says what a metric is; its file says only how
    it is read, and a suffixed name falls back to its stem's file."""
    for m in man["per_layer"]:
        f = spec.layer_metric_file(m["name"])
        assert set(f) <= {"reader", "params"}, m["name"]
        reader = importlib.import_module(f"benchmarks.readers.{f['reader']}")
        assert callable(reader.read)
    assert spec.layer_metric_file("decode_step_ms.some-new-cells") == \
        spec.layer_metric_file("decode_step_ms")
    with pytest.raises(FileNotFoundError):
        spec.layer_metric_file("no_such_metric.batch")


def test_every_cell_reports_what_the_contract_asks(man):
    e2e = {m["name"] for m in man["end_to_end"]}
    for cell in man["workloads"]:
        mine = {m["name"] for m in spec.metrics_of(man["end_to_end"],
                                                   cell["name"])}
        assert "setup_s" in mine and len(mine) >= 2
        layer = spec.metrics_of(man["per_layer"], cell["name"])
        assert layer
        for m in layer:
            assert m["moves"] in mine, (cell["name"], m["name"])
    assert all(m["moves"] in e2e for m in man["per_layer"])


def test_manifest_is_small_and_well_formed(man):
    raw = (spec.ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) < 64 * 1024
    assert set(json.loads(raw)) == {"command", "paths", "run_seconds",
                                    "configs", "workloads", "end_to_end",
                                    "per_layer"}
    for w in man["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
