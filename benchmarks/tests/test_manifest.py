"""BENCHMARK.json against the data files it names: a cell, a configuration,
a traffic mix, a per-layer metric or an architecture is added by files and
entries alone, so the entries and the files have to agree."""

import importlib
import inspect
import json
from pathlib import Path

import pytest

from benchmarks import arch
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


def test_every_configuration_names_an_adapter_that_offers_everything(man):
    """The adapter imports by the name the configuration gives, offers every
    name the harness asks by, states the four tolerances each with its
    reason, and uses the reference file the configuration names."""
    for c in man["configs"]:
        cfg = spec.config(man, c["name"])
        adapter = spec.adapter(cfg)
        assert adapter.__name__ == cfg["adapter"]
        for name in arch.OFFERS:
            assert callable(getattr(adapter, name, None)), (c["name"], name)
        tol = adapter.tolerances(cfg)
        assert set(tol) == {"logit_err", "token_gap", "loss_rel",
                            "grad_norm_rel"}
        for name, t in tol.items():
            assert t["limit"] > 0 and len(t["why"]) > 10, (c["name"], name)
        assert Path(adapter.reference(cfg).__file__) \
            == spec.ROOT / cfg["reference"]
        low, high = adapter.id_range(cfg)
        assert 0 <= low < high and adapter.positions(cfg) > 0
        assert adapter.total_params(cfg) > 0


def test_gpt2s_tolerances_are_the_ones_it_was_measured_with(man):
    tol = spec.adapter(spec.config(man, "gpt2-large")).tolerances({})
    assert {k: t["limit"] for k, t in tol.items()} == {
        "logit_err": 0.025, "token_gap": 0.01, "loss_rel": 2e-4,
        "grad_norm_rel": 6e-3}


def test_an_adapter_offers_what_the_harness_asks_and_with_those_arguments():
    """The table in ``benchmarks/arch/__init__.py`` against GPT-2's
    adapter: the same names, and as many positional arguments as the table's
    signature says."""
    from benchmarks.arch import gpt2

    for name, said in arch.OFFERS.items():
        want = said[1:said.index(")")].split(", ")
        have = [p.name for p in inspect.signature(
            getattr(gpt2, name)).parameters.values()
            if p.default is inspect.Parameter.empty]
        assert have == want, name


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
