"""The nine per-layer metrics that split ``setup_s`` (ISSUE 37): their
manifest entries, their readers over ``harness/setup_spans.py``, and a
rehearsal through ``run.py --trace 1`` in a process of its own (the
timeline is a process's: in the test process it would hold the tests')."""
import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

from bench_helpers import (DATA, REPO, last_line, manifest, rehearsal_root,
                           write_manifest)
from benchmark import run as bench_run

NINE = {
    "setup_import_s": ("import", "Process start"),
    "setup_initialize_s": ("initialize", "Gluon front end"),
    "setup_eager_forward_s": ("eager_forward", "Gluon front end"),
    "setup_trace_s": ("trace", "Program registry + compile cache"),
    "setup_lower_s": ("lower", "Program registry + compile cache"),
    "setup_cache_load_s": ("cache_load", "Program registry + compile cache"),
    "setup_cold_compile_s": ("cold_compile",
                             "Program registry + compile cache"),
    "setup_step_host_s": ("step_host", "Step compiler"),
    "setup_unspanned_s": ("unspanned", "Outside the program's spans"),
}


def _reader(name):
    path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_loaded_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _stub_run(setup_s=10.0):
    notes = []
    run = types.SimpleNamespace(
        facts={} if setup_s is None else {"setup_s": setup_s},
        t_process=100.0, note=lambda **facts: notes.append(facts))
    return run, notes


def _setup_manifest(root):
    with open(os.path.join(DATA, "rehearsal_manifest_setup.json")) as f:
        m = json.load(f)
    write_manifest(root, m)
    return m


# -- the manifest --------------------------------------------------------------

@pytest.mark.parametrize("name", list(NINE))
def test_the_entry_is_well_formed_and_lists_no_cells(name):
    entry, = [e for e in manifest()["per_layer"] if e["name"] == name]
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": NINE[name][1],
                     "moves": "setup_s"}
    assert hasattr(_reader(name), "read")


def test_the_nine_are_appended_and_the_file_stays_small():
    m = manifest()
    assert [e["name"] for e in m["per_layer"]][-9:] == list(NINE)
    assert len(m["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # the accepted metrics that move setup_s stand as they were
    for name in ("build_s", "compile_s", "cache_hits"):
        entry, = [e for e in m["per_layer"] if e["name"] == name]
        assert entry["moves"] == "setup_s"


# -- the readers ---------------------------------------------------------------

@pytest.mark.parametrize("name", list(NINE))
def test_a_reader_gives_its_kind_of_the_set_ups_window(name, monkeypatch):
    from mxnet_tpu import telemetry
    asked = []

    def breakdown(t0, t1):
        asked.append((t0, t1))
        found = {kind: float(i) for i, kind
                 in enumerate(telemetry.STARTUP_KINDS)}
        found.update(by_program={"mx_step_step": {"trace": 3.0,
                                                  "total": 3.0}},
                     gaps=[{"start": 0.0, "end": 1.23456, "before": None,
                            "after": "import"}], dropped=0)
        return found

    monkeypatch.setattr(telemetry, "startup_breakdown", breakdown)
    run, notes = _stub_run()
    reader = _reader(name)
    want = telemetry.STARTUP_KINDS.index(NINE[name][0])
    assert reader.read(run) == want
    assert reader.read(run) == want
    assert asked == [(100.0, 110.0)]        # reduced once, over set-up
    line, = notes                           # and printed once
    assert line["setup_spans"]["kinds_s"][NINE[name][0]] == want
    assert line["setup_spans"]["programs_s"] == [
        {"program": "mx_step_step", "trace": 3.0, "total": 3.0}]
    assert line["setup_spans"]["gaps"][0]["end"] == 1.235


@pytest.mark.parametrize("name", list(NINE))
def test_a_reader_reads_nothing_from_a_program_without_the_timeline(
        name, monkeypatch):
    """The driver lays these files over the parent's checkout."""
    from mxnet_tpu import telemetry
    monkeypatch.delattr(telemetry, "startup_breakdown")
    run, notes = _stub_run()
    assert _reader(name).read(run) is None
    assert notes == []
    run, _ = _stub_run(setup_s=None)        # a driver that took no setup_s
    monkeypatch.undo()
    assert _reader(name).read(run) is None


# -- the rehearsal -------------------------------------------------------------

def test_a_traced_rehearsal_prints_all_nine_and_they_sum_to_setup_s(tmp_path):
    root, _ = rehearsal_root(tmp_path)
    _setup_manifest(root)
    env = dict(os.environ, MX_FORCE_CPU="1", JAX_PLATFORMS="cpu",
               MX_TELEMETRY="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("MX_TELEMETRY_TRACE", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--root", root, "--workload", "tiny-bert-train",
         "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = last_line(done.stdout)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert set(NINE) <= set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0
    values = {name: metrics[name]["value"] for name in NINE}
    assert all(m["unit"] == "s" for n, m in metrics.items() if n in NINE)
    assert all(v >= 0 for v in values.values())
    notes = [json.loads(l[len("benchmark: "):])
             for l in done.stdout.splitlines() if l.startswith("benchmark: {")]
    setup_s = next(n["setup_s"] for n in notes if "window_s" in n)
    assert sum(values.values()) == pytest.approx(setup_s, rel=0.01)
    assert values["setup_trace_s"] > 0 and values["setup_lower_s"] > 0
    assert values["setup_cold_compile_s"] > 0
    assert values["setup_import_s"] > 0 and values["setup_initialize_s"] > 0
    assert values["setup_eager_forward_s"] > 0
    assert values["setup_step_host_s"] > 0
    assert values["setup_cache_load_s"] == 0    # no cache under MX_FORCE_CPU=1
    spans, = [n["setup_spans"] for n in notes if "setup_spans" in n]
    assert spans["setup_s"] == setup_s and spans["dropped"] == 0
    assert len(spans["programs_s"]) == 10
    totals = [p["total"] for p in spans["programs_s"]]
    assert totals == sorted(totals, reverse=True)
    assert "mx_step_step" in {p["program"] for p in spans["programs_s"]}
    for gap in spans["gaps"]:
        assert gap["end"] - gap["start"] > 0.5
        assert set(gap) == {"start", "end", "before", "after"}


def test_with_the_timeline_taken_away_the_run_exits_0_and_prints_none(
        tmp_path, capsys, monkeypatch):
    from mxnet_tpu import telemetry
    monkeypatch.delattr(telemetry, "startup_breakdown")
    root, _ = rehearsal_root(tmp_path)
    _setup_manifest(root)
    rc = bench_run.main(["--root", root, "--workload", "tiny-resnet-train",
                         "--seed", "5", "--seconds", "1", "--trace", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    line = last_line(out)
    assert line["correct"] is True
    assert not set(NINE) & set(line["metrics"])
    assert "build_s" in line["metrics"]
    assert "setup_spans" not in out
