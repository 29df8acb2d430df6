"""The reader of `moe_exact_buffer_share` (benchmark/layer_metrics/): the
share of the expert layers' calls that took the exact no-drop buffer,
from the counters `moe_exact_buffer_calls{layer}` and
`moe_layer_calls{layer}` - and nothing, without raising, on a program
that has no such counters."""
import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["glm47flash-train-s4096-ep8share",
         "nemotron3super-train-s8192-ep64tp8share"]


def _reader():
    spec = importlib.util.spec_from_file_location(
        "_moe_exact_buffer_share", os.path.join(
            REPO, "benchmark", "layer_metrics", "moe_exact_buffer_share.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("exact,calls,want", [
    ({}, {}, None),                                 # the parent: no counters
    ({"0": 0.0, "mtp": 0.0}, {"0": 66.0, "mtp": 66.0}, 0.0),
    ({"0": 3.0, "mtp": 0.0}, {"0": 60.0, "mtp": 60.0}, 2.5),
    ({"0": 0.0}, {"0": 0.0}, None),                 # registered, never run
])
def test_the_share_is_exact_calls_over_calls(monkeypatch, exact, calls, want):
    from mxnet_tpu import telemetry
    registry = telemetry.Registry()         # other tests' layers stay out
    monkeypatch.setattr(telemetry, "registry", registry)
    for layer, value in exact.items():
        registry.read_counter("moe_exact_buffer_calls", lambda v=value: v,
                              labels={"layer": layer})
    for layer, value in calls.items():
        registry.read_counter("moe_layer_calls", lambda v=value: v,
                              labels={"layer": layer})
    registry.read_counter("moe_assignments_elsewhere", lambda: 7.0,
                          labels={"layer": "0"})
    got = _reader().read(None)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_entry_names_both_expert_cells():
    """Found by its name: a later PR's entries come after it."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [e for e in manifest["per_layer"]
              if e["name"] == "moe_exact_buffer_share"]
    assert entry == {
        "name": "moe_exact_buffer_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Kernels",
        "moves": "train_tokens_per_s", "workloads": CELLS}
    assert {w["name"] for w in manifest["workloads"]} >= set(CELLS)
