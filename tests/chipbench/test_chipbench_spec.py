"""The harness finds every piece by name, from files alone, and the
committed BENCHMARK.json holds to the shape its contract fixes."""
import json
import re

import pytest

from tinybench import REPO, TINY_MIXES, TINY_SERVE, _dump
from tinybench import tiny_root  # noqa: F401  (fixture)
from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


def test_finds_config_mix_cell_and_metrics(tiny_root):
    cell = spec.find_cell(tiny_root, "tiny.open")
    assert cell.config["hidden_size"] == 128
    assert cell.traffic["kind"] == "open"
    assert cell.deploy["serve"]["max_slots"] == 4
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p90_s", "itl_p95_s", "setup_s"}
    assert "queue_wait_p90_s" in {m["name"] for m in cell.per_layer}
    reader = spec.metric_reader(tiny_root, "decode_seats_mean")
    assert reader(type("Ctx", (), {"decode_calls": [[1, 2], [], [3]]})) == 1.5


def test_a_cell_added_from_files_alone(tiny_root):
    """A new mix, config, cell and per-layer metric: new files and new
    entries, no edit of any existing file."""
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny2", "source": "test",
                             "file": "chipbench/configs/tiny2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny2.burst", "config": "tiny2",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny2.burst")
    bench["per_layer"].append({"name": "steps_count", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "ttft_p90_s",
                               "workloads": ["tiny2.burst"]})
    _dump(tiny_root / "BENCHMARK.json", bench)
    conf = json.loads((tiny_root / "chipbench/configs/tiny.json").read_text())
    _dump(tiny_root / "chipbench/configs/tiny2.json",
          dict(conf, num_hidden_layers=3))
    _dump(tiny_root / "chipbench/traffic/burst.json",
          dict(TINY_MIXES["tiny_open"], cv=4.0))
    _dump(tiny_root / "chipbench/cells/tiny2.burst.json",
          {"driver": "serve", "serve": TINY_SERVE,
           "check": {"requests": 2, "limits": {"widest_gap": 1}}})
    (tiny_root / "chipbench/metrics/steps_count.py").write_text(
        "def read(ctx):\n    return len(ctx.decode_calls) or None\n")
    cell = spec.find_cell(tiny_root, "tiny2.burst")
    assert cell.config["num_hidden_layers"] == 3
    assert cell.traffic["cv"] == 4.0
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p90_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["steps_count"]
    ctx = type("Ctx", (), {"decode_calls": [[1]]})
    assert spec.read_per_layer(tiny_root, cell, ctx) == {
        "steps_count": {"value": 1.0, "unit": "steps"}}
    ctx.decode_calls = []
    assert spec.read_per_layer(tiny_root, cell, ctx) == {}


def test_unknown_cell_is_an_error(tiny_root):
    with pytest.raises(KeyError):
        spec.find_cell(tiny_root, "no.such")
    with pytest.raises(KeyError):
        spec.load(tiny_root, "drivers", "no_such")


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all((REPO / p).is_dir() for p in BENCH["paths"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert (REPO / "chipbench/metrics" / f"{m['name']}.py").is_file()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_and_reports_enough(cell):
    c = spec.find_cell(REPO, cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    assert {"driver", "serve", "check"} <= set(c.deploy)
    assert spec.load(REPO, "drivers", c.deploy["driver"]).run
    assert spec.load(REPO, "generators", c.traffic["kind"]).window
    for m in c.end_to_end:
        if m["name"] != "setup_s":
            assert spec.load(REPO, "end_to_end", m["name"]).read
    assert set(c.deploy["check"]["limits"]) <= {"widest_gap", "mean_gap",
                                               "flip_share"}
    assert c.deploy["check"]["limits"]
