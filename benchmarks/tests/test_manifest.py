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
    assert spec.layer_metric_path("decode_step_ms.some-new-cells") == \
        spec.layer_metric_path("decode_step_ms")
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


# ------------------------------------------- one entry a quantity (PR 53)

BEFORE = json.loads((Path(__file__).parent / "data"
                     / "per_layer_before_pr53.json").read_text())["entries"]


def test_no_two_entries_name_one_quantity(man):
    """Two entries that one file reads with the same parameters, and that
    move the same metric in the same unit, layer, source and direction, are
    one quantity under two names: they are one entry with a ``workloads``
    list.  There is room for those to come: at most 72 of the 128."""
    seen = {}
    for m in man["per_layer"]:
        key = (spec.layer_metric_path(m["name"]), m["moves"], m["unit"],
               m["layer"], m["source"], m["better"])
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]
    assert len(man["per_layer"]) <= 72


def test_every_folded_entry_lists_the_cells_its_predecessors_listed(man):
    now = {m["name"]: m for m in man["per_layer"]}
    for was in BEFORE:
        if was["now"] is not None:
            assert set(was["workloads"]) <= set(
                now[was["now"]]["workloads"]), was["was"]
    retired = {w["was"].split(".")[0] for w in BEFORE if w["now"] is None}
    assert retired == {"decode_device_ms", "prefill_device_ms",
                       "moe_block_fill"}
    assert not retired & {n.split(".")[0] for n in now}
    for name in list(retired) + ["hetu_device_ms"]:
        assert not list((spec.BENCH / "layer_metrics").glob(name + "*"))
        assert not (spec.BENCH / "readers" / f"{name}.py").exists()


def test_every_cell_reports_the_quantities_it_reported_each_under_one_name(
        man):
    for cell in man["workloads"]:
        was = [w["now"] for w in BEFORE
               if cell["name"] in w["workloads"] and w["now"] is not None]
        assert len(was) == len(set(was)), cell["name"]
        assert set(was) == {m["name"] for m in spec.metrics_of(
            man["per_layer"], cell["name"])}, cell["name"]


def test_a_traced_rehearsal_prints_the_names_the_manifest_gives_the_cell(
        man, monkeypatch, capsys):
    """Off a chip the device's metrics have nothing to read and are left
    out; what is printed is printed under the manifest's names."""
    import os

    from benchmarks import run as bench

    for var in ("JAX_PLATFORMS", "XLA_FLAGS"):   # --rehearse sets them
        monkeypatch.setenv(var, os.environ.get(var, ""))
    rc = bench.main(["--workload", "gpt2-large.batch", "--seconds", "1",
                     "--seed", "3000000019", "--rehearse", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"]
    mine = {m["name"] for m in spec.metrics_of(man["per_layer"],
                                               "gpt2-large.batch")}
    assert {"decode_step_ms", "prefill_chunk_ms", "decode_host_ms",
            "prefill_host_ms", "sched_host_ms", "decode_batch_mean",
            "engine_compiles"} <= set(line["metric_names"]) <= mine
    assert "steps_over_1p5x_median" in line["detail"]["counts"]
