"""BENCHMARK.json against the driver's character rules, and every file
it names: a bad name must fail here, in the sandbox."""

import copy
import json
import os

import pytest

from benchmarks import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny")


@pytest.fixture(scope="module")
def m():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_manifest_obeys_the_rules(m):
    manifest.validate(copy.deepcopy(m))
    manifest.load(TINY)


def test_every_name_layer_and_unit_is_made_of_the_allowed_characters(m):
    names = [c["name"] for c in m["configs"]]
    names += [k for c in m["configs"] for k in c["reduced"]]
    for w in m["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for x in m["end_to_end"] + m["per_layer"]:
        names.append(x["name"])
        assert manifest.UNIT.match(x["unit"]), x
    names += [x["layer"] for x in m["per_layer"]]
    for n in names:
        assert manifest.NAME.match(n), n


def test_every_config_traffic_limit_and_reader_has_its_file(m):
    bench = os.path.join(ROOT, m["paths"][0])
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"]), c["name"]
    for w in m["workloads"]:
        cell = manifest.Cell(ROOT, m, w["name"])
        assert cell.traffic["kind"] in ("train", "serve_closed")
        assert os.path.exists(os.path.join(
            bench, "kinds", cell.traffic["kind"] + ".py"))
        assert cell.limits["limits"]
        assert cell.end_to_end[-1] == "setup_s" or "setup_s" in \
            cell.end_to_end
    for x in m["per_layer"]:
        assert os.path.exists(manifest.reader_path(x["name"]))


def test_run_py_names_no_cell_and_no_configuration(m):
    with open(os.path.join(ROOT, m["paths"][0], "run.py")) as f:
        text = f.read()
    for entry in m["workloads"] + m["configs"]:
        assert entry["name"] not in text


@pytest.mark.parametrize("breach", [
    lambda m: m["per_layer"][0].update(layer="staged step"),
    lambda m: m["per_layer"][0].update(layer="a/b"),
    lambda m: m["per_layer"][0].update(name="step.ms(p50)"),
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["end_to_end"][0].update(bound=0.2),
    lambda m: m["end_to_end"][0].update(why="x"),
    lambda m: m["workloads"][0].update(chips=2),
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["workloads"].append(dict(m["workloads"][0])),
    lambda m: m["configs"][0].update(file="elsewhere/x.json"),
    lambda m: m["per_layer"][0].update(moves="no_such_metric"),
    lambda m: m.update(run_seconds=52),
    lambda m: m["command"].append("/abs/path"),
])
def test_a_breach_of_the_rules_is_refused(m, breach):
    bad = copy.deepcopy(m)
    breach(bad)
    with pytest.raises(ValueError):
        manifest.validate(bad)
