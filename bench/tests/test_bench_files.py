"""The benchmark's files: every cell resolves by name, names and units keep
to their alphabets, and a cell added as files alone is found."""
import json
import re
import shutil

import pytest

from bench import run

ROOT = run.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = run.resolve(cell)
    assert (ROOT / "bench" / "modes" / f"{c['traffic']['mode']}.py").is_file()
    for m in c["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    assert c["limits"]


def test_names_and_units():
    groups = [BENCH["configs"], BENCH["workloads"], BENCH["end_to_end"],
              BENCH["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    moves = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in moves for m in BENCH["per_layer"])


def test_cell_added_as_files_alone_is_found(tmp_path):
    shutil.copytree(ROOT / "bench" / "configs", tmp_path / "bench" / "configs")
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "workloads").mkdir()
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          "full-cm.blogcatalog.json").read_text())
    traffic["graph"]["row"] = "pubmed"
    (tmp_path / "bench" / "traffic" / "full.pubmed.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "workloads" / "gcn-256x3.full.pubmed.json"
     ).write_text(json.dumps({"limits": {"loss_gap": 1.0}}))
    bench = dict(BENCH)
    bench["workloads"] = [dict(name="gcn-256x3.full.pubmed",
                               config="gcn-256x3", traffic="full.pubmed",
                               chips=1, why="a cell of files alone")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = run.resolve("gcn-256x3.full.pubmed", root=tmp_path)
    assert c["traffic"]["graph"]["row"] == "pubmed"
    assert c["limits"] == {"loss_gap": 1.0}
    assert {m["name"] for m in c["end_to_end"]} == {"setup_s"}


def test_unknown_cell_is_refused():
    with pytest.raises(run.SetupError):
        run.resolve("no-such-cell")


def test_peaks_refuse_unknown_device_kind():
    assert run.load_peaks("TPU v5 lite")["flops_per_s"] > 0
    with pytest.raises(KeyError):
        run.load_peaks("TPU v99")


def test_no_tpu_means_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
