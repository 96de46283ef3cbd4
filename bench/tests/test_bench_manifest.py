"""``BENCHMARK.json`` against the benchmark's contract, and every name in it
resolved to the file that serves it."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|"
                   r"head|expan|experts_per_tok|top_k", re.I)
CELLS = MANIFEST["workloads"]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert all(not p.startswith("/") and ".." not in p
               and (ROOT / p).is_dir() for p in MANIFEST["paths"])
    assert len(MANIFEST["command"]) <= 32
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


def test_names_are_unique():
    for group in (MANIFEST["configs"], CELLS, METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("entry", MANIFEST["configs"] + CELLS + METRICS,
                         ids=lambda e: e["name"])
def test_name_and_unit_characters(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_file_and_cuts(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("bench/")
    data = json.loads((ROOT / config["file"]).read_text())
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    assert (ROOT / "bench" / "drivers" / f"{data['driver']}.py").is_file()
    assert any(c["config"] == config["name"] for c in CELLS)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_resolves_and_reports(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()

    def reports(metric):
        return cell["name"] in metric.get("workloads", [cell["name"]])

    e2e = [m["name"] for m in MANIFEST["end_to_end"] if reports(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(m) for m in MANIFEST["per_layer"])


def test_four_chip_cells_at_most_half():
    four = sum(c["chips"] == 4 for c in CELLS)
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_bounds(metric):
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    for w in metric.get("workloads", []):
        assert w in {c["name"] for c in CELLS}


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    """Every cell a per-layer metric lists reports the end-to-end metric it
    moves, and the metric has a reader of its own."""
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moves = {m["name"]: m for m in MANIFEST["end_to_end"]}[metric["moves"]]
    all_cells = [c["name"] for c in CELLS]
    for w in metric.get("workloads", all_cells):
        assert w in moves.get("workloads", all_cells)
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()


def test_run_seconds_fit_a_full_check():
    """A check of 24 cells: 2 + 14 * 24 runs of run_seconds + 60 s, two
    compiles of 90 s a cell and 1200 s spare within 43,200 s."""
    t = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (t + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_peak_table_names_its_source():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "Google Cloud" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
