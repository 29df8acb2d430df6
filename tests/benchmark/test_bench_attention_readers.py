"""layer_metrics/attention_kernel_share.py and attention_roofline.py: on
the recorded v5e sample (the jnp composition: share 0, roofline from its
attention_ms), on a hand-made trace with a custom call under
attention_core, and in the manifest."""
import importlib.util
import json
import os

import pytest

from bench_helpers import DATA, REPO, manifest
from benchmark.harness import peaks, program_trace as pt

DEVICE = "/device:TPU:0"
CELLS = ["bert-base-train-s512", "bert-base-train-s512-fsdp4"]
PATH = "jit(mx_step_step)/%s/BERTModel/encoder/0/attention/" \
    "jit(mx_op_multi_head_attention)/attention_core/%s"


def _load(*relative):
    path = os.path.join(REPO, "benchmark", *relative)
    spec = importlib.util.spec_from_file_location(
        "_loaded_" + relative[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reader(name):
    return _load("layer_metrics", name + ".py")


share, roofline = _reader("attention_kernel_share"), \
    _reader("attention_roofline")


@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(DATA, "trace_v5e_bert_scopes.json")) as f:
        return json.load(f)


def _where(top, scope):
    return {"scope": scope, "top": top, "tops": [top], "mixed": False}


@pytest.fixture()
def handmade():
    """Two runs of a step on one device: a forward kernel (400 ns), a
    backward kernel (900 ns), the backward's delta as an XLA fusion under
    attention_core (100 ns), a matmul fusion outside it (1000 ns) and a
    custom call that is no attention (50 ns)."""
    ops, modules = [], []
    for start in (0, 10000):
        modules.append(["jit_mx_step_step", start, 5000])
        at = start
        for name, group, dur in (
                ("custom-call.1", "custom-call:tpu_custom_call", 400),
                ("fusion.7", "fusion:kOutput bf16[32,512,768]", 1000),
                ("fusion.9", "fusion:kLoop f32[32,12,1,512]", 100),
                ("custom-call.2", "custom-call:tpu_custom_call", 900),
                ("custom-call.3", "custom-call:Sharding", 50)):
            ops.append([name, group, at, dur])
            at += dur
    scopes = {"module": "jit_mx_step_step", "instructions": {
        "custom-call.1": _where("forward", PATH % ("jvp(forward)",
                                                   "pallas_call")),
        "fusion.7": _where("forward", "jit(mx_step_step)/jvp(forward)/"
                           "BERTModel/encoder/0/ffn/dot_general"),
        "fusion.9": _where("backward", PATH % ("transpose(jvp(forward))",
                                               "reduce_sum")),
        "custom-call.2": _where("backward",
                                PATH % ("transpose(jvp(forward))",
                                        "pallas_call")),
        "custom-call.3": _where("forward",
                                "jit(mx_step_step)/jvp(forward)/copy"),
    }}
    return {"devices": {DEVICE: {"ops": ops, "async": [],
                                 "modules": modules}},
            "host": []}, scopes


def test_share_on_a_handmade_trace(handmade):
    trace, scopes = handmade
    whole = pt.reduce(trace, scopes)["scopes"]["attention_ms"]
    assert whole == pytest.approx(1400e-6)        # 400 + 100 + 900 ns a step
    assert share.kernels_ms(trace, scopes) == pytest.approx(1300e-6)
    # the composition: no custom call under a scope -> 0, not None
    plain = dict(scopes, instructions={
        k: v for k, v in scopes["instructions"].items()
        if not k.startswith("custom-call")})
    assert share.kernels_ms(trace, plain) == 0.0
    # nothing to read: no scopes, or no device op
    assert share.kernels_ms(trace, None) is None
    assert share.kernels_ms({"devices": {}, "host": []}, scopes) is None


def test_share_of_the_recorded_sample_is_zero(sample):
    """PR 25's step ran the jnp composition: its trace holds no custom
    call, so the kernels' time is 0 of the 1.9 ms under attention_core."""
    assert pt.reduce(sample, sample["scopes"])["scopes"]["attention_ms"] \
        == pytest.approx(3829050 / 2e6)
    assert share.kernels_ms(sample, sample["scopes"]) == 0.0


class _Run:
    """What the readers ask of run.py's Run."""

    def __init__(self, reduced, facts, chips=1, layers=12):
        self.facts = dict(facts, program_trace=reduced)
        self.config = {"num_hidden_layers": layers}
        self.cell = {"name": "bert-base-train-s512", "chips": chips}
        self.cache_dir = "/nonexistent"
        self.trace = True
        self.notes = []

    def note(self, **facts):
        self.notes.append(facts)


def _bert_ops(batch):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "bert_base.json")) as f:
        config = json.load(f)
    return _load("models", "bert_base.py").ops_and_bytes(
        config, {"batch": batch, "seq": 512})


def test_roofline_counts_required_products_only(sample):
    """12 layers x 3 x (scores + values) of 32 rows of 512 at width 768:
    0.928 TFLOP, 4.71 ms at the v5e's 197 TFLOP/s - ISSUE 27's number;
    the parent's 52.4 ms read 9.0 %."""
    ops = _bert_ops(32)
    peak = peaks.peaks_for("TPU v5 lite")
    least = roofline.least_ms(ops, 12, peak, 1)
    assert least == pytest.approx(12 * 3 * 2 * 2 * 32 * 512 * 512 * 768
                                  / 197e12 * 1e3)
    assert least == pytest.approx(4.71, abs=0.005)
    # four chips, four times the rows: the same time a chip
    assert roofline.least_ms(_bert_ops(128), 12, peak, 4) == \
        pytest.approx(least)
    facts = {"ops": ops, "device": {"kind": "TPU v5 lite"}}
    reduced = {"scopes": {"attention_ms": 52.4}, "spans_ms": {}}
    run = _Run(reduced, facts)
    assert roofline.read(run) == pytest.approx(100 * least / 52.4)
    assert roofline.read(run) == pytest.approx(9.0, abs=0.05)
    # the recorded sample's own attention_ms (a fragment of a step)
    got = pt.reduce(sample, sample["scopes"])
    run = _Run(got, facts)
    assert roofline.read(run) == pytest.approx(
        100 * least / (3829050 / 2e6))


@pytest.mark.parametrize("reduced, facts", [
    (None, {}),                                           # not traced
    ({"scopes": None, "spans_ms": {}}, {}),               # no scopes
    ({"scopes": {"attention_ms": 0.0}, "spans_ms": {}},
     {"ops": {"detail": {}}, "device": {"kind": "TPU v5 lite"}}),
    ({"scopes": {"attention_ms": 5.0}, "spans_ms": {}},   # no attention
     {"ops": {"detail": {"per_layer_forward": {}}},
      "device": {"kind": "TPU v5 lite"}}),
])
def test_readers_return_nothing_where_there_is_nothing(reduced, facts):
    run = _Run(reduced, facts)
    assert roofline.read(run) is None
    assert share.read(run) is None     # the last: no trace file to open


@pytest.mark.parametrize("name", ["attention_kernel_share",
                                  "attention_roofline"])
def test_manifest_lists_both_with_their_cells(name):
    entries = [e for e in manifest()["per_layer"] if e["name"] == name]
    assert len(entries) == 1
    e = entries[0]
    assert e["workloads"] == CELLS and e["moves"] == "train_tokens_per_s"
    assert e["unit"] == "%" and e["better"] == "higher"
    assert e["layer"] == "Kernels" and e["source"] == "device_trace"
    # additions only: they follow attention_ms, the last entry of PR 25
    names = [x["name"] for x in manifest()["per_layer"]]
    assert names.index(name) > names.index("attention_ms")
