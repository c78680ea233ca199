"""``BENCHMARK.json`` as the runner reads it: validated against the
driver's character rules at every start-up (a bad name fails here, in
the sandbox, not at the driver), then resolved to the files of one
cell.  The runner knows kinds of traffic, never names of cells."""

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def _line(text, what):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        raise ValueError("%s must be 1 to 200 characters on one line: %r"
                         % (what, text))


def _name(text, what):
    if not (isinstance(text, str) and NAME.match(text)):
        raise ValueError(
            "%s must be 1 to 64 characters from letters, digits, '_', "
            "'.' and '-', starting with a letter, digit or '_': %r"
            % (what, text))


def _keys(entry, required, optional, what):
    extra = set(entry) - set(required) - set(optional)
    missing = set(required) - set(entry)
    if extra or missing:
        raise ValueError("%s: unexpected keys %s, missing keys %s"
                         % (what, sorted(extra), sorted(missing)))


def validate(m):
    """Raise ValueError on the first breach of the manifest's rules."""
    _keys(m, ("command", "paths", "run_seconds", "configs", "workloads",
              "end_to_end", "per_layer"), (), "BENCHMARK.json")
    if not 1 <= len(m["command"]) <= 32:
        raise ValueError("command: 1 to 32 strings")
    for word in m["command"]:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise ValueError("command names a path outside the repo: %r"
                             % word)
    if not 1 <= len(m["paths"]) <= 16:
        raise ValueError("paths: 1 to 16 directories")
    for p in m["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            raise ValueError("bad path %r" % p)
    if not (isinstance(m["run_seconds"], int)
            and 1 <= m["run_seconds"] <= 51):
        raise ValueError("run_seconds: a whole number from 1 to 51")

    def under_paths(f):
        return any(f.startswith(p.rstrip("/") + "/") for p in m["paths"])

    configs, files = set(), set()
    if not 1 <= len(m["configs"]) <= 24:
        raise ValueError("configs: 1 to 24")
    for c in m["configs"]:
        _keys(c, ("name", "source", "file", "reduced", "why"), (),
              "config %r" % c.get("name"))
        _name(c["name"], "config name")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        if not (PATH.match(c["file"]) and under_paths(c["file"])):
            raise ValueError("config file %r not under paths" % c["file"])
        if c["name"] in configs or c["file"] in files:
            raise ValueError("config %r appears twice" % c["name"])
        configs.add(c["name"])
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            raise ValueError("reduced: at most 16 keys")
        for key in c["reduced"]:
            _name(key, "reduced key")

    cells, pairs, used = set(), set(), set()
    if not 1 <= len(m["workloads"]) <= 24:
        raise ValueError("workloads: 1 to 24 cells")
    for w in m["workloads"]:
        _keys(w, ("name", "config", "traffic", "chips", "why"), (),
              "workload %r" % w.get("name"))
        for key in ("name", "config", "traffic"):
            _name(w[key], "workload " + key)
        _line(w["why"], "workload why")
        if w["chips"] not in (1, 4):
            raise ValueError("chips is 1 or 4")
        if w["config"] not in configs:
            raise ValueError("cell %r names no configuration" % w["name"])
        pair = (w["config"], w["traffic"])
        if w["name"] in cells or pair in pairs:
            raise ValueError("cell %r appears twice" % w["name"])
        cells.add(w["name"])
        pairs.add(pair)
        used.add(w["config"])
    if used != configs:
        raise ValueError("configurations no cell uses: %s"
                         % sorted(configs - used))
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    if four > max(1, len(m["workloads"]) // 4):
        raise ValueError("too many four-chip cells")

    metrics, e2e = set(), {}
    if not 1 <= len(m["end_to_end"]) <= 16:
        raise ValueError("end_to_end: 1 to 16 metrics")
    if not 1 <= len(m["per_layer"]) <= 128:
        raise ValueError("per_layer: 1 to 128 metrics")

    def metric(x, required, what):
        _keys(x, required, ("workloads",), what)
        _name(x["name"], what + " name")
        if not UNIT.match(x["unit"]):
            raise ValueError("%s %r: bad unit %r" % (what, x["name"],
                                                     x["unit"]))
        if x["better"] not in ("lower", "higher"):
            raise ValueError("%s %r: better is lower or higher"
                             % (what, x["name"]))
        if x["source"] not in SOURCES:
            raise ValueError("%s %r: bad source" % (what, x["name"]))
        if x["name"] in metrics:
            raise ValueError("metric %r appears twice" % x["name"])
        metrics.add(x["name"])
        for cell in x.get("workloads", ()):
            if cell not in cells:
                raise ValueError("%s %r lists no cell %r"
                                 % (what, x["name"], cell))

    for x in m["end_to_end"]:
        metric(x, ("name", "unit", "better", "bound", "source"),
               "end_to_end metric")
        if x["source"] not in ("host_clock", "device_trace"):
            raise ValueError("end_to_end %r: source" % x["name"])
        if not 0.01 <= x["bound"] <= 0.1:
            raise ValueError("end_to_end %r: bound 0.01 to 0.1" % x["name"])
        e2e[x["name"]] = x
    if "setup_s" not in e2e or "workloads" in e2e["setup_s"]:
        raise ValueError("end_to_end needs setup_s, in every cell")
    for x in m["per_layer"]:
        metric(x, ("name", "unit", "better", "source", "layer", "moves"),
               "per_layer metric")
        _name(x["layer"], "per_layer metric %s: layer" % x["name"])
        if x["moves"] not in e2e:
            raise ValueError("per_layer %r moves no end_to_end metric"
                             % x["name"])
        moved = e2e[x["moves"]].get("workloads")
        for cell in x.get("workloads", ()):
            if moved is not None and cell not in moved:
                raise ValueError(
                    "per_layer %r lists cell %r, which does not report %r"
                    % (x["name"], cell, x["moves"]))
    for cell in cells:
        if not any(a != "setup_s" for a in applicable(m["end_to_end"], cell)):
            raise ValueError("cell %r reports no end_to_end metric" % cell)
        if not applicable(m["per_layer"], cell):
            raise ValueError("cell %r reports no per_layer metric" % cell)
    return m


def applicable(metrics, cell):
    """Names (in order) of the metrics a cell reports."""
    return [x["name"] for x in metrics
            if "workloads" not in x or cell in x["workloads"]]


def find_data(directory, name):
    """The one data file ``<directory>/<name><suffix>``."""
    for suffix in DATA_SUFFIXES:
        path = os.path.join(directory, name + suffix)
        if os.path.exists(path):
            return path
    raise FileNotFoundError("no %s{%s}" % (os.path.join(directory, name),
                                           ",".join(DATA_SUFFIXES)))


def reader_path(metric):
    """``readers/<metric>.py`` beside this file, or ``readers/<stem>.py``
    for a metric split by cell kind as ``<stem>.<kind>``."""
    here = os.path.dirname(os.path.abspath(__file__))
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(here, "readers", stem + ".py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError("no reader for per_layer metric %r" % metric)


def load(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return validate(json.load(f))


class Cell:
    """One entry of ``workloads`` with its files read."""

    def __init__(self, root, m, name):
        self.root, self.manifest, self.name = root, m, name
        entry = next((w for w in m["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit("benchmark: BENCHMARK.json has no cell %r (%s)"
                             % (name, [w["name"] for w in m["workloads"]]))
        self.chips = entry["chips"]
        self.bench_dir = os.path.join(root, m["paths"][0])
        config = next(c for c in m["configs"] if c["name"] == entry["config"])
        with open(os.path.join(root, config["file"])) as f:
            self.config = json.load(f)
        self.traffic_file = find_data(
            os.path.join(self.bench_dir, "traffic"), entry["traffic"])
        with open(self.traffic_file) as f:
            self.traffic = json.load(f)
        with open(find_data(os.path.join(self.bench_dir, "limits"),
                            name)) as f:
            self.limits = json.load(f)
        self.end_to_end = applicable(m["end_to_end"], name)
        self.per_layer = applicable(m["per_layer"], name)
        self.units = {x["name"]: x["unit"]
                      for x in m["end_to_end"] + m["per_layer"]}
