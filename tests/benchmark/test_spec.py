"""``BENCHMARK.json`` against the data files it names."""
import json
import os
import re

import pytest

from benchmarks.lib import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(_size$|_dim$|_rank$|_width|_widths$|expansion|"
                   r"per_tok|ratio$)")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in bench["paths"])
    assert os.path.isfile(os.path.join(ROOT, bench["command"][1]))
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_whys(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), group,
                          entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    metrics = [n for is_metric, _g, n in names if is_metric]
    assert len(metrics) == len(set(metrics))
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_configuration_is_used_and_cuts_no_width(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] and doc["builder"] and doc["reference"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert key in doc and key in doc["published"]
            assert doc[key] != doc["published"][key]
        for kind, name in (("builders", doc["builder"]),
                           ("references", doc["reference"])):
            assert spec.load_module(kind, name)


def test_every_cell_resolves(bench):
    for name in _cells(bench):
        cell = spec.Cell(ROOT, name)
        assert spec.load_module("drivers", cell.traffic["driver"])
        assert spec.load_module("generators", cell.traffic["kind"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            # BENCHMARK.json alone says what a metric is and which cells
            # report it; its file says only how it is read, so a cell
            # that a later PR adds edits no metric's file
            doc = cell.metric_file(m["name"])
            assert set(doc) == {"reader", "params"}, m["name"]
            reader = spec.load_module("readers", doc["reader"])
            assert callable(reader.read)
        assert cell.limits and "rehearse" not in cell.limits
        assert all(isinstance(v, float) for v in cell.limits.values())
        # a rehearsal lays tiny sizes over the same files
        tiny = spec.Cell(ROOT, name, rehearse=True)
        assert "rehearse" not in tiny.config and "rehearse" not in tiny.traffic
        assert tiny.limits and "rehearse" not in tiny.limits


def test_every_per_layer_metric_names_cells_that_report_what_it_moves(bench):
    cells = _cells(bench)
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for w in m["workloads"]:
            assert w in cells and w in e2e[m["moves"]], (m["name"], w)
        layers.setdefault(m["layer"], []).append(m["name"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, "PERF.md's list of layers lacks %r" % layer
    # beside every roofline the whole step's share of the peak moves the
    # same end-to-end metric
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in bench["per_layer"]), m["name"]


def test_a_missing_file_or_module_is_named():
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.Cell(ROOT, "no_such_cell")
    with pytest.raises(spec.SpecError, match="no module"):
        spec.load_module("readers", "no_such_reader")
    with pytest.raises(spec.SpecError, match="bad readers module name"):
        spec.load_module("readers", "../run")


def test_run_py_names_no_cell_configuration_or_metric(bench):
    with open(os.path.join(ROOT, "benchmarks", "run.py")) as f:
        text = f.read()
    names = [e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[g]]
    names += [w["traffic"] for w in bench["workloads"]]
    for name in names:
        whole = r"(?<![\w.\-])" + re.escape(name) + r"(?![\w.\-])"
        assert not re.search(whole, text), "run.py names %r" % name
