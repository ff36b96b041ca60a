"""``BENCHMARK.json`` against the data files it names, and appended to
only: a cell, its configuration and its metrics go at the end, and each
cell's own test module checks its entries by name (``check_entries``)."""
import copy
import glob
import importlib
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
    check_names_units_and_whys(bench)


def check_names_units_and_whys(bench):
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
    check_per_layer_metrics(bench)


def check_per_layer_metrics(bench):
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


def test_cells_are_appended_in_order(bench):
    check_appended_in_order(bench)


def check_appended_in_order(bench):
    """What appending leaves true: every metric lists its cells in the
    order ``workloads`` holds them, and the configurations stand in the
    order of their first cells. A cell put ahead of another while its name
    is appended to the lists breaks the first; a configuration put ahead
    of an older one breaks the second."""
    cells = _cells(bench)
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", [])
        assert set(listed) <= set(cells), m["name"]
        at = [cells.index(w) for w in listed]
        assert at == sorted(set(at)), \
            "%s lists its cells out of cell order" % m["name"]
    first = {}
    for i, w in enumerate(bench["workloads"]):
        first.setdefault(w["config"], i)
    assert set(first) >= {c["name"] for c in bench["configs"]}
    at = [first[c["name"]] for c in bench["configs"]]
    assert at == sorted(at), "configurations out of their first cells' order"


PROBE = "appended_probe"


def _with_probe(bench, where):
    """A deep copy of ``bench`` with a serving cell added as a later PR
    adds one: its name at the end of ``tpot_p90_ms``'s list and of every
    per-layer metric that all serving cells report, one per-layer metric
    of its own, and the cell itself at the end (``where`` "end"), at the
    end with a configuration of its own ("end_with_its_configuration"),
    before the last cell that reports ``tpot_p90_ms`` ("before_the_last"),
    or at the end with its configuration put first
    ("configuration_first")."""
    out = copy.deepcopy(bench)
    config = out["configs"][0]["name"]
    if where in ("end_with_its_configuration", "configuration_first"):
        config = "appended-probe"
        entry = {"name": config, "source": "https://example.org/" + config,
                 "file": "benchmarks/configs/%s.json" % config,
                 "reduced": [], "why": "a configuration appended by a test"}
        out["configs"].insert(0 if where == "configuration_first"
                              else len(out["configs"]), entry)
    used = {w["traffic"] for w in out["workloads"] if w["config"] == config}
    traffic = [w["traffic"] for w in out["workloads"]
               if w["traffic"] not in used][0]
    tpot, = [m for m in out["end_to_end"] if m["name"] == "tpot_p90_ms"]
    serving = set(tpot["workloads"])
    at = len(out["workloads"])
    if where == "before_the_last":
        at = _cells(out).index(tpot["workloads"][-1])
    out["workloads"].insert(at, {
        "name": PROBE, "config": config, "traffic": traffic, "chips": 1,
        "why": "a serving cell appended by a test"})
    generic = [m for m in out["per_layer"] if serving <= set(m["workloads"])]
    assert generic
    for m in [tpot] + generic:
        m["workloads"].append(PROBE)
    out["per_layer"].append({
        "name": PROBE + ".share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": generic[0]["layer"],
        "moves": "tpot_p90_ms", "workloads": [PROBE]})
    return out


def _cells_own_checks():
    """``check_entries`` of every cell's test module
    (``test_<cell>_cell.py``), found as files, as a later cell's is."""
    here = os.path.dirname(os.path.abspath(__file__))
    names = sorted(os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(here, "test_*_cell.py")))
    checks = [getattr(importlib.import_module(n), "check_entries", None)
              for n in names]
    return [c for c in checks if c is not None]


@pytest.mark.parametrize("where", ["end", "end_with_its_configuration",
                                   "before_the_last", "configuration_first"])
def test_a_cell_appended_at_the_end_leaves_the_checks_green(bench, where):
    """The next cell, appended, passes every check of the file's shape and
    every cell's own; put anywhere but at the end, it fails the order
    check alone."""
    probed = _with_probe(bench, where)
    assert PROBE in _cells(probed) and PROBE not in _cells(bench)
    own = _cells_own_checks()
    assert own
    for check in [check_names_units_and_whys, check_per_layer_metrics] + own:
        check(probed)
    if where.startswith("end"):
        check_appended_in_order(probed)
    else:
        match = ("out of cell order" if where == "before_the_last"
                 else "first cells' order")
        with pytest.raises(AssertionError, match=match):
            check_appended_in_order(probed)


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
