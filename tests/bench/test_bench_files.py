"""``BENCHMARK.json`` and the files it names: present, well formed, and
found by name, so that a cell, a mix or a metric is added with new files
and an entry alone."""
import json
import re
import shutil
from pathlib import Path

import pytest

from benchmarks.chip import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection|_dim|_rank|head|expansion|per_tok)")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert (ROOT / BENCH["command"][1]).is_file()


def test_names_units_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200


def test_configs_used_and_never_cut_in_width():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmarks/chip/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if WIDTHS.search(k)]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_with_its_files(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4) and c.limits
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(harness.load_reader(m["name"]))
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert len(w["why"]) <= 200


@pytest.mark.parametrize("path", sorted((ROOT / "benchmarks/chip/traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_traffic_file_validates(path):
    traffic = harness.load_traffic(path.stem)
    assert traffic["kind"] in ("train", "serve")


def test_at_most_half_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)


def test_new_cell_from_new_files_only(tmp_path):
    """A later change adds a configuration, a traffic mix, a cell and a
    per-layer metric as new files plus entries; nothing there is edited."""
    shutil.copytree(ROOT / "benchmarks/chip", tmp_path / "benchmarks/chip")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks/chip").rglob("*")
              if p.is_file()}
    chip = tmp_path / "benchmarks/chip"
    cfg = json.loads((chip / "configs/ssfn_mnist_m20.json").read_text())
    (chip / "configs/ssfn_wide.json").write_text(json.dumps(dict(cfg, hidden=2048)))
    (chip / "traffic/train_exact_probe.json").write_text(json.dumps(
        {"kind": "train", "policy": "exact", "tolerance": 1e-6, "trace_every": 0}))
    (chip / "limits/ssfn_wide.train_exact_probe.json").write_text('{"gap_l0": 1e-3}')
    (chip / "metrics/probe_ms.train.py").write_text("def read(r):\n    return 1.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "ssfn_wide", "source": "https://example.org",
                             "file": "benchmarks/chip/configs/ssfn_wide.json",
                             "reduced": [], "why": "a wider stack"})
    bench["workloads"].append({"name": "ssfn_wide.train_exact_probe", "config": "ssfn_wide",
                               "traffic": "train_exact_probe", "chips": 1, "why": "probe"})
    bench["per_layer"].append({"name": "probe_ms.train", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "device",
                               "moves": "train_s", "workloads": ["ssfn_wide.train_exact_probe"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("ssfn_wide.train_exact_probe", root=tmp_path)
    assert cell.config["hidden"] == 2048 and cell.traffic["policy"] == "exact"
    assert cell.limits == {"gap_l0": 1e-3}
    assert [m["name"] for m in cell.per_layer] == ["probe_ms.train"]
    assert harness.load_reader("probe_ms.train", chip)(None) == 1.0
    assert {p: p.read_bytes() for p in before} == before


def test_malformed_traffic_is_refused(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic/bad.json").write_text('{"kind": "train", "policy": "gossip"}')
    with pytest.raises(harness.BenchError, match="lacks"):
        harness.load_traffic("bad", tmp_path)
    (tmp_path / "traffic/odd.json").write_text('{"kind": "../x"}')
    with pytest.raises(harness.BenchError, match="not a generator"):
        harness.load_traffic("odd", tmp_path)
