"""BENCHMARK.json: every cell resolves, by name, to its configuration,
mix and metric files, and the file keeps to the benchmark's contract."""

import json
import re

import pytest

from bench import model as bm
from bench import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = spec.resolve(name)
    assert cell.config["source"].startswith("https://huggingface.co/")
    bm.model_config(cell.config)                  # maps onto ModelConfig
    assert spec.traffic_path(cell.traffic_name).exists()
    for m in cell.per_layer + cell.end_to_end:
        assert callable(spec.metric_reader(m["name"]))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer


def test_metrics_move_what_their_cells_report():
    for m in BENCH["per_layer"]:
        for cell in m["workloads"]:
            assert m["moves"] in {e["name"] for e in spec.resolve(cell)
                                  .end_to_end}, (m["name"], cell)


def test_names_units_and_files():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.fullmatch(n) for n in names)
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"] == []
        assert conf["source"] == c["source"]


@pytest.mark.parametrize("name", CELLS)
def test_mix_says_where_each_length_comes_from(name):
    """Each length is cited under ``sources`` or owned under ``assumed``."""
    mix = spec.resolve(name).traffic
    said = {k.split(".")[0] for k in [*mix["sources"], *mix["assumed"]]}
    lengths = {k for k, v in mix.items()
               if isinstance(v, dict) and ("dist" in v or "length" in v)}
    assert {"user", "answer", "max_turns"} | lengths <= said


def test_whys_and_sources_fit_the_contract():
    for entry in BENCH["workloads"] + BENCH["configs"]:
        for key in ("why", "source"):
            text = entry.get(key)
            if text is not None:
                assert 1 <= len(text) <= 200 and "\n" not in text
                assert "\t" not in text
