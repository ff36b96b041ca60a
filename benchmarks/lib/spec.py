"""Resolve a cell of ``BENCHMARK.json`` to its data files.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own that is found by the name in
``BENCHMARK.json``; this module holds no such name.
"""
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = "benchmarks"


class SpecError(Exception):
    """The benchmark's data files do not resolve."""


def _read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError("missing benchmark file %s" % path) from None


def _merge(base, over):
    """``over`` laid on ``base``, nested groups merged key by key."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


class Cell:
    """One workload with its configuration, traffic, metrics and limits."""

    def __init__(self, root, name, rehearse=False):
        self.root = root
        self.rehearse = rehearse
        bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.benchmark = bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SpecError("no workload %r in BENCHMARK.json (has %s)"
                            % (name, ", ".join(sorted(cells))))
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        if self.workload["config"] not in configs:
            raise SpecError("workload %r names config %r, which "
                            "BENCHMARK.json lacks"
                            % (name, self.workload["config"]))
        entry = configs[self.workload["config"]]
        self.config = self._sized(_read_json(
            os.path.join(root, entry["file"])))
        self.traffic = self._sized(_read_json(os.path.join(
            root, BENCH_DIR, "traffic",
            self.workload["traffic"] + ".json")))
        self.limits = self._sized(_read_json(os.path.join(
            root, BENCH_DIR, "limits", name + ".json")), replace=True)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def _sized(self, doc, replace=False):
        """The file as it is run; a rehearsal lays the file's own
        ``rehearse`` group (tiny sizes for the CPU) over it, or with
        ``replace`` takes the group in the file's place."""
        doc = dict(doc)
        over = doc.pop("rehearse", {})
        if not self.rehearse:
            return doc
        return dict(over) if replace and over else _merge(doc, over)

    def metric_file(self, metric_name):
        return _read_json(os.path.join(
            self.root, BENCH_DIR, "metrics", metric_name + ".json"))


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py``, found by the name a data file
    gives (``kind`` is builders, generators, drivers, readers or
    references)."""
    if not name.replace("_", "").isalnum():
        raise SpecError("bad %s module name %r" % (kind, name))
    try:
        return importlib.import_module("%s.%s.%s" % (BENCH_DIR, kind, name))
    except ModuleNotFoundError as e:
        if e.name and e.name.startswith(BENCH_DIR):
            raise SpecError("no module %s/%s/%s.py" % (BENCH_DIR, kind,
                                                       name)) from None
        raise
