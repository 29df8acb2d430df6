"""BENCHMARK.json against the contract it is written to, and against the
files it names."""
import json
import os
import re

import pytest

from bench_helpers import REPO, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return manifest()


def test_keys_and_limits(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 10 <= m["run_seconds"] <= 51
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert 2 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    # a full check must fit 43200 s with all 24 cells a later PR may add
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines(m):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in m[kind]]
        assert len(seen) == len(set(seen)), kind
        names += seen
    names += [c[k] for c in m["workloads"] for k in ("config", "traffic")]
    for n in names:
        assert NAME.match(n), n
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({e["name"] for e in metrics}) == len(metrics)
    for e in metrics:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    texts = [c["why"] for c in m["workloads"]] \
        + [c["why"] for c in m["configs"]] \
        + [c["source"] for c in m["configs"]] \
        + [e["layer"] for e in m["per_layer"]] + m["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t


def test_cells_point_at_files_that_exist(m):
    configs = {c["name"]: c for c in m["configs"]}
    assert len({c["file"] for c in m["configs"]}) == len(configs)
    used = set()
    pairs = set()
    for cell in m["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4)
        pairs.add((cell["config"], cell["traffic"]))
        used.add(cell["config"])
        entry = configs[cell["config"]]
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["file"].startswith("benchmark/") \
            and FILE.match(entry["file"])
        with open(os.path.join(REPO, entry["file"])) as f:
            config = json.load(f)
        assert os.path.isfile(os.path.join(REPO, config["model_file"]))
        assert config["reduced"] == entry["reduced"]
        assert not config.get("rehearsal")
        with open(os.path.join(REPO, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["why"] and traffic["who"]
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "drivers", traffic["driver"] + ".py"))
    assert used == set(configs), "every configuration keeps a cell"
    assert len(pairs) == len(m["workloads"])
    four = sum(1 for c in m["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)


def test_metrics_have_readers_and_move_what_the_cell_reports(m):
    cells = [c["name"] for c in m["workloads"]]

    def reported(entry):
        return set(entry.get("workloads", cells))

    e2e = {e["name"]: reported(e) for e in m["end_to_end"]}
    assert e2e["setup_s"] == set(cells)
    for e in m["end_to_end"]:
        assert reported(e) <= set(cells)
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "end_to_end", e["name"] + ".py"))
    layers = {}
    for e in m["per_layer"]:
        reader = e["name"].split(".")[0]
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", reader + ".py")), e["name"]
        assert e["moves"] in e2e, e
        # reported only where the metric it moves is
        assert reported(e) <= e2e[e["moves"]], e
        layers.setdefault(reader, set()).add(e["layer"])
    for reader, named in layers.items():
        assert len(named) == 1, (reader, named)
    for cell in cells:
        assert sum(cell in r for n, r in e2e.items() if n != "setup_s") >= 1
        assert any(cell in reported(e) for e in m["per_layer"])
    # no dead reader: each is named by the manifest, or by the rehearsal
    # manifest of the tests (the serve cell's, which is still to come:
    # PERF.md, section 7)
    with open(os.path.join(REPO, "tests", "benchmark", "data",
                           "rehearsal_manifest.json")) as f:
        rehearsal = json.load(f)
    for directory, kind in (("end_to_end", "end_to_end"),
                            ("layer_metrics", "per_layer")):
        have = {f[:-3] for f in os.listdir(os.path.join(
            REPO, "benchmark", directory)) if f.endswith(".py")}
        named = {e["name"].split(".")[0] for e in m[kind]}
        rehearsed = {e["name"].split(".")[0] for e in rehearsal[kind]}
        assert named <= have and have - named <= rehearsed, have - named


def test_files_under_paths_are_named_from_allowed_characters(m):
    for base in m["paths"]:
        for where, dirs, files in os.walk(os.path.join(REPO, base)):
            dirs[:] = [d for d in dirs
                       if d not in (".cache", "__pycache__", ".pytest_cache")]
            for f in files:
                rel = os.path.relpath(os.path.join(where, f), REPO)
                assert FILE.match(rel), rel
