"""Cells resolve to their files by name; a new configuration, mix or
per-layer metric is new files and entries only."""

import json
import shutil

import run

ROOT = run.ROOT


def test_every_cell_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["mix"]["name"] == w["traffic"]
        assert {m["name"] for m in cell["end_to_end"]} >= {"qps", "setup_s"}
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(run.reader(m["name"]))
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_new_cell_is_new_files_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    cfg = json.loads((ROOT / "bench/configs/hprd.json").read_text())
    cfg["name"] = "hprd2"
    (tmp_path / "bench/configs/hprd2.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/mixes/c16-child3.json").read_text())
    mix.update(name="c2-child3", clients=2)
    (tmp_path / "bench/mixes/c2-child3.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/served_count.py").write_text(
        "def read(w):\n    return w.delta('server_served')\n")
    bench["configs"].append({"name": "hprd2", "source": "x",
                             "file": "bench/configs/hprd2.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "hprd2-c2", "config": "hprd2",
                               "traffic": "c2-child3", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "served_count", "unit": "count",
                               "better": "higher", "source":
                               "program_counter", "layer": "serving",
                               "moves": "qps", "workloads": ["hprd2-c2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.load_cell("hprd2-c2", root=tmp_path)
    assert cell["config"]["name"] == "hprd2"
    assert cell["mix"]["clients"] == 2
    assert [m["name"] for m in cell["per_layer"]] == ["served_count"]
    w = run.Window(cell=cell, before={"server_served": 3},
                   after={"server_served": 10})
    assert run.reader("served_count", root=tmp_path)(w) == 7
    for rel, data in before.items():
        assert (tmp_path / rel).read_bytes() == data
