"""BENCHMARK.json against the contract's rules it can break by an edit."""

import importlib
import os
import re

import pytest

import perf_toy

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head_dim|n_embd"
                   r"|n_inner|_dim$|_rank$|expansion|experts_per)")
M = perf_toy.manifest()
CELLS = {w["name"]: w for w in M["workloads"]}
E2E = {m["name"]: m for m in M["end_to_end"]}


def reports(cell: str, metric: dict) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def test_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51
    # a full check of 24 cells has to fit 43,200 s
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(open(os.path.join(perf_toy.ROOT, "BENCHMARK.json")).read()) \
        < 64 * 1024
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_keys(kind):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[kind]
    names = [e["name"] for e in M[kind]]
    assert len(names) == len(set(names))
    for e in M[kind]:
        assert set(e) <= allowed, e
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        if kind == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace")
            assert 0.01 <= e["bound"] <= 0.1


def test_cells_name_files_that_exist_and_a_quarter_take_four_chips():
    configs = {c["name"]: c for c in M["configs"]}
    pairs = set()
    for w in M["workloads"]:
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        cfg = configs[w["config"]]
        body = perf_toy.load(cfg["file"])
        for rel in (cfg["file"], f"perf/traffic/{w['traffic']}.json",
                    f"perf/reference/{body['reference']}.py",
                    f"perf/families/{body['family']}.py"):
            assert os.path.exists(os.path.join(perf_toy.ROOT, rel)), rel
        traffic = perf_toy.load(f"perf/traffic/{w['traffic']}.json")
        assert os.path.exists(os.path.join(
            perf_toy.ROOT, "perf", "drivers", traffic["driver"] + ".py"))
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)
    assert {w["config"] for w in M["workloads"]} == set(configs)
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("config", [c["name"] for c in M["configs"]])
def test_reduced_names_no_width_and_is_explained(config):
    entry = next(c for c in M["configs"] if c["name"] == config)
    body = perf_toy.load(entry["file"])
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert key in body and key in body["reduced"], key
    assert body["source"] == entry["source"]
    assert any(p and entry["file"].startswith(p + "/") for p in M["paths"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = [m["name"] for m in M["end_to_end"] if reports(cell, m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in M["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e)]
    assert layer
    for m in layer:  # what it should move is reported where it is read
        assert m["moves"] in e2e, (cell, m["name"])


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"]])
def test_every_layer_metric_has_a_reader_that_agrees(metric):
    entry = next(m for m in M["per_layer"] if m["name"] == metric)
    assert entry["moves"] in E2E and entry["moves"] != "setup_s"
    reader = importlib.import_module(f"perf.layer_metrics.{metric}")
    assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
        entry["unit"], entry["layer"], entry["source"], entry["moves"])
    assert callable(reader.read)
    if "roofline" in metric or "mfu" in metric:
        assert entry["unit"] == "%"
    for cell in entry.get("workloads", []):
        assert reports(cell, E2E[entry["moves"]])


READERS = sorted(f[:-3] for f in os.listdir(os.path.join(
    perf_toy.ROOT, "perf", "layer_metrics"))
    if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("name", READERS)
def test_every_reader_file_is_whole(name):
    """Also those of the cell that waits (`chat_*`): a later PR names them
    in the manifest and edits nothing."""
    reader = importlib.import_module(f"perf.layer_metrics.{name}")
    assert NAME.match(name) and UNIT.match(reader.UNIT)
    assert reader.SOURCE in SOURCES and reader.LAYER and "\n" not in \
        reader.LAYER
    assert NAME.match(reader.MOVES) and callable(reader.read)
    assert reader.__doc__.strip()


def test_paths_hold_the_command_and_nothing_leads_out():
    assert 1 <= len(M["paths"]) <= 16
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
    assert M["command"][-1].startswith(M["paths"][0] + "/")
