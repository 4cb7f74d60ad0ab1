"""Every configuration, traffic mix and metric reader is a file of its own
that the harness finds by the name ``BENCHMARK.json`` gives."""

import json
import shutil

import pytest

import harness

BENCHMARK = harness.load_json(harness.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("config", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_each_configuration_loads_by_name(config):
    loaded = harness.load_config(config["name"])
    assert loaded["name"] == config["name"]
    assert (harness.ROOT / config["file"]) == (
        harness.BENCH / "configs" / f"{config['name']}.json")
    harness.entry(loaded["entry"])


@pytest.mark.parametrize("cell", BENCHMARK["workloads"],
                         ids=lambda w: w["name"])
def test_each_cell_resolves_its_mix_and_metrics(cell):
    resolved = harness.Cell(BENCHMARK, cell["name"])
    harness.generator(resolved.mix["kind"])
    names = {m["name"] for m in resolved.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert resolved.per_layer


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_each_per_layer_metric_has_a_reader(metric):
    assert callable(harness.reader(metric["name"]).read)


def test_an_added_configuration_is_found_without_editing_a_file(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH / "configs", bench / "configs")
    shutil.copytree(harness.BENCH / "traffic", bench / "traffic")
    new = dict(harness.load_config("vc_c125"), name="vc_c140")
    new["graph"] = dict(new["graph"], n=140)
    (bench / "configs" / "vc_c140.json").write_text(json.dumps(new))
    benchmark = dict(BENCHMARK, workloads=BENCHMARK["workloads"] + [
        {"name": "vc_c140.solve", "config": "vc_c140", "traffic": "solve",
         "chips": 1, "why": "test"}])
    cell = harness.Cell(benchmark, "vc_c140.solve", bench)
    assert cell.config["graph"]["n"] == 140
    assert harness.Cell(benchmark, "vc_c125.solve", bench).config[
        "graph"]["n"] == 125


def test_an_unknown_cell_or_file_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.Cell(BENCHMARK, "no_such.cell")
    with pytest.raises(harness.BenchError):
        harness.load_config("no_such_config")


def test_the_peak_table_names_its_source_and_the_v5e():
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    assert peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_a_sweep_sets_one_setting_of_the_configuration_or_the_mix():
    import sweep
    cell = harness.Cell(BENCHMARK, "vc_c125.solve")
    sweep.with_setting(cell, "lanes", 4096)
    sweep.with_setting(cell, "graph.n", 60)
    sweep.with_setting(cell, "instance_seeds", [7])
    assert cell.config["lanes"] == 4096 and cell.config["graph"]["n"] == 60
    assert cell.mix["instance_seeds"] == [7]
    assert harness.load_config("vc_c125")["graph"]["n"] == 125
