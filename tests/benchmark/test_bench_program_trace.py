"""harness/program_trace.py: the traced window read from inside the
program - against a recorded v5e sample with sums worked out apart from
it, the guard for a program without scopes, and a rehearsal run on the
host that prints the span metrics and leaves the device ones out."""
import json
import os

import pytest

from bench_helpers import (DATA, REPO, last_line, manifest, rehearsal_root,
                           write_manifest)
from benchmark import run as bench_run
from benchmark.harness import program_trace as pt

DEVICE = "/device:TPU:0"
READERS = ("forward_ms", "backward_ms", "optimizer_ms", "scope_unattributed",
           "other_programs_ms", "step_prepare_ms", "step_dispatch_ms",
           "step_write_back_ms", "attention_ms")


@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(DATA, "trace_v5e_bert_scopes.json")) as f:
        return json.load(f)


def test_recorded_sample_is_what_the_note_says(sample):
    ops = sample["devices"][DEVICE]["ops"]
    assert len(ops) == 400 and sum(o[3] for o in ops) == 19968844
    ordered = sorted(ops, key=lambda o: o[2])
    assert all(a[2] + a[3] <= b[2] for a, b in zip(ordered, ordered[1:]))
    assert [m[0] for m in sample["devices"][DEVICE]["modules"]] == [
        "jit_mx_step_step", "jit_reshape", "jit_convert_element_type",
        "jit_mx_random_fold_in", "jit_mx_step_step"]
    assert sample["scopes"]["module"] == "jit_mx_step_step"
    assert len(sample["scopes"]["instructions"]) == 395


def test_recorded_sample_by_scope(sample):
    """The end of one BERT-base step, the programs between and the start
    of the next on a TPU v5 lite (PR 25): two step events, so every sum
    is halved.  No two of the 400 ops overlap, so self time is duration;
    the sums (ns) were added up apart from program_trace, by walking the
    ops and looking each one's instruction up in the sample's scopes:
    forward 8,716,182 (45 ops), backward 4,403,525 (23), optimizer
    701,507 (21), two mixed fusions 5,989,106 - fusion.2195, the MLM
    head's weight gradient with its optimizer update, alone 5,646,768 -
    and 304 ops with no top-level scope (async copies and slices) 157,047;
    7 unmixed ops under attention_core 3,829,050."""
    out = pt.reduce(sample, sample["scopes"])
    assert out["steps"] == 2 and out["step_module"] == "jit_mx_step_step"
    assert out["why"] is None
    s = out["scopes"]
    assert s["forward_ms"] == pytest.approx(8716182 / 2e6)
    assert s["backward_ms"] == pytest.approx(4403525 / 2e6)
    assert s["optimizer_ms"] == pytest.approx(701507 / 2e6)
    assert s["exchange_ms"] == 0 and s["metric_ms"] == 0
    assert s["attention_ms"] == pytest.approx(3829050 / 2e6)
    assert s["unattributed_ms"] == pytest.approx((5989106 + 157047) / 2e6)
    assert out["step_ms"] == pytest.approx(19967367 / 2e6)
    assert s["scope_unattributed"] == pytest.approx(
        100.0 * (5989106 + 157047) / 19967367)
    # the parts add up to the step
    assert s["forward_ms"] + s["backward_ms"] + s["optimizer_ms"] \
        + s["unattributed_ms"] == pytest.approx(out["step_ms"])
    assert s["unattributed_by"]["backward+forward+optimizer"] == \
        pytest.approx(5989106 / 2e6)
    assert sum(s["unattributed_by"].values()) == \
        pytest.approx(s["unattributed_ms"])
    # by block path: a Sequential's child number is written *
    assert sum(s["blocks_ms"].values()) == pytest.approx(
        s["forward_ms"] + s["backward_ms"] + s["optimizer_ms"])
    assert s["blocks_ms"]["optimizer"] == pytest.approx(701507 / 2e6)
    assert next(iter(s["blocks_ms"])) == "forward/SoftmaxCrossEntropyLoss"
    assert "backward/BERTModel/encoder/*/attention" in s["blocks_ms"]
    assert "forward/BERTModel/encoder/*/ffn/ffn_1" in s["blocks_ms"]
    # by operator (summed apart, by a regular expression over each op's
    # scope): the loss's log_softmax 4,471,437, the attention's backward
    # 2,685,205, its forward 1,239,878
    assert sum(s["operators_ms"].values()) == pytest.approx(
        sum(s["blocks_ms"].values()))
    assert list(s["operators_ms"])[:2] == ["forward/log_softmax",
                                           "backward/multi_head_attention"]
    assert s["operators_ms"]["forward/log_softmax"] == \
        pytest.approx(4471437 / 2e6)
    assert s["operators_ms"]["backward/multi_head_attention"] == \
        pytest.approx(2685205 / 2e6)
    assert s["operators_ms"]["forward/multi_head_attention"] == \
        pytest.approx(1239878 / 2e6)
    assert s["operators_ms"]["optimizer"] == pytest.approx(701507 / 2e6)


def test_recorded_sample_other_programs_and_spans(sample):
    out = pt.reduce(sample, sample["scopes"])
    # jit_reshape (the loss's reshape in CompiledStep.step) 1 op of 539 ns,
    # the rng's fold_in 4 ops of 938 ns; jit_convert_element_type has no
    # op on the "XLA Ops" line
    assert out["other_programs"] == {
        "jit_mx_random_fold_in": pytest.approx(938 / 2e6),
        "jit_reshape": pytest.approx(539 / 2e6)}
    assert out["other_programs_ms"] == pytest.approx(1477 / 2e6)
    # one whole mx.step in the window (the second runs past its end), two
    # data waits: nearest-rank median is the first
    assert out["spans_ms"] == {
        "mx.data_wait": pytest.approx(0.042491),
        "mx.step": pytest.approx(10.009025),
        "mx.step.dispatch": pytest.approx(3.329361),
        "mx.step.prepare": pytest.approx(3.044391),
        "mx.step.write_back": pytest.approx(2.404201)}


def test_recorded_sample_idle_gaps_by_innermost_span(sample):
    """30,042,402 ns of window less 19,968,844 busy: 10,073,558 idle.
    The 7 ms between the two runs of the step are the host's first step
    after a sync: named by the part of mx.step that covers each gap, not
    by mx.step itself."""
    gaps = pt.idle_gaps(sample)
    assert sum(gaps.values()) == pytest.approx(0.010073558)
    assert gaps["mx.step.prepare"] == pytest.approx(0.004923311)
    assert gaps["mx.step.dispatch"] == pytest.approx(0.002082621)
    assert gaps["host.untraced"] == pytest.approx(0.00200797)
    assert gaps["device.between_ops"] == pytest.approx(5.9656e-05)
    assert gaps["mx.step"] == pytest.approx(0.001)      # the window's tail


@pytest.mark.parametrize("scopes, why", [
    (None, "0 instructions known"),
    ({"module": "jit_mx_step_step", "instructions": {
        "fusion.2195": {"scope": "", "top": None, "tops": [],
                        "mixed": False}}}, "1 instructions known"),
])
def test_guard_scopes_absent(sample, scopes, why):
    """A program from before the scopes (or an executable a cache handed
    over without them): every scope number is None, one reason is given,
    and what needs no scope is still read."""
    out = pt.reduce(sample, scopes)
    assert out["scopes"] is None
    assert "forward" in out["why"] and why in out["why"]
    assert out["steps"] == 2            # by name, else by most device time
    assert out["other_programs_ms"] == pytest.approx(1477 / 2e6)
    assert out["spans_ms"]["mx.step.prepare"] == pytest.approx(3.044391)


def test_guard_no_device_and_no_step_event(sample):
    host_only = {"devices": {}, "host": sample["host"]}
    out = pt.reduce(host_only, sample["scopes"])
    assert out["scopes"] is None and "no device op" in out["why"]
    assert out["spans_ms"]["mx.step.dispatch"] == pytest.approx(3.329361)
    assert pt.idle_gaps(host_only) is None
    other = dict(sample["scopes"], module="jit_mx_step_window")
    out = pt.reduce(sample, other)
    assert out["scopes"] is None and "jit_mx_step_window" in out["why"]
    assert out["other_programs_ms"] is None


@pytest.mark.parametrize("scope, path", [
    ("jit(mx_step_step)/jvp(forward)/BERTModel/encoder/3/attention/proj/"
     "jit(mx_op_FullyConnected)/dot_general",
     "BERTModel/encoder/*/attention/proj"),
    ("jit(mx_step_step)/transpose(jvp(forward))/BERTModel/encoder/11/"
     "attention/jit(mx_op_multi_head_attention)/attention_core/exp",
     "BERTModel/encoder/*/attention"),
    ("jit(mx_step_step)/jit(main)/optimizer/sub", ""),
    ("jit(mx_step_step)/jvp(forward)/SoftmaxCrossEntropyLoss/"
     "jit(mx_op_log_softmax)/jit(log_softmax)/reduce_sum",
     "SoftmaxCrossEntropyLoss"),
    ("jit(mx_step_step)/forward/Net/head/add", "Net/head"),
    ("", ""),
])
def test_block_path(scope, path):
    assert pt.block_path(scope) == path


@pytest.mark.parametrize("scope, operator", [
    ("jit(mx_step_step)/jvp(forward)/ResNetV1/features/1/"
     "jit(mx_op_BatchNorm)/reduce_sum", "BatchNorm"),
    ("jit(mx_step_step)/transpose(jvp(forward))/BERTModel/encoder/11/"
     "attention/jit(mx_op_multi_head_attention)/attention_core/exp",
     "multi_head_attention"),
    ("jit(mx_step_step)/jvp(forward)/SoftmaxCrossEntropyLoss/"
     "jit(mx_op_log_softmax)/jit(log_softmax)/reduce_sum", "log_softmax"),
    ("jit(mx_step_step)/jit(main)/optimizer/sub", ""),
    ("", ""),
])
def test_operator_of(scope, operator):
    assert pt.operator_of(scope) == operator


def test_innermost_cuts_nested_and_overlapping_spans():
    spans = [["mx.step", 0, 100], ["mx.step.prepare", 10, 30],
             ["mx.step.retrace", 15, 10], ["mx.step.dispatch", 40, 20],
             ["mx.data_wait", 90, 30]]          # another thread: overlaps
    assert pt.innermost(spans) == [
        ["mx.step", 0, 10], ["mx.step.prepare", 10, 5],
        ["mx.step.retrace", 15, 10], ["mx.step.prepare", 25, 15],
        ["mx.step.dispatch", 40, 20], ["mx.step", 60, 30],
        ["mx.data_wait", 90, 30]]
    assert pt.innermost([]) == []


@pytest.mark.parametrize("reader", READERS)
def test_every_new_entry_has_its_reader_and_its_cells(reader):
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "layer_metrics", reader + ".py"))
    entries = {e["name"]: e for e in manifest()["per_layer"]}
    plain = entries[reader]
    assert plain["moves"] == "train_tokens_per_s"
    assert plain["workloads"] == ["bert-base-train-s512",
                                  "bert-base-train-s512-fsdp4"]
    assert plain["better"] == "lower"
    assert plain["source"] == ("program_span" if reader.startswith("step_")
                               else "device_trace")
    if reader == "attention_ms":
        assert plain["layer"] == "Kernels"
        assert reader + ".images" not in entries
    else:
        assert plain["layer"] == "Step compiler"
        twin = entries[reader + ".images"]
        assert twin["moves"] == "train_images_per_s"
        assert twin["workloads"] == ["resnet50-train-b256"]
        assert {k: twin[k] for k in ("unit", "source", "layer")} == \
            {k: plain[k] for k in ("unit", "source", "layer")}


def test_rehearsal_traced_run_prints_spans_and_leaves_scopes_out(
        tmp_path, capsys):
    """On the host the trace has no device plane: the three program_span
    metrics print from the mx.* spans, the device_trace ones are left
    out, and one line says the scopes are absent."""
    root, m = rehearsal_root(tmp_path)
    for e in manifest()["per_layer"]:
        if e["name"] in READERS:
            m["per_layer"].append(dict(e, workloads=["tiny-bert-train"]))
    write_manifest(root, m)
    rc = bench_run.main(["--root", root, "--workload", "tiny-bert-train",
                         "--seed", "3", "--seconds", "1", "--trace", "1"])
    assert rc == 0
    text = capsys.readouterr().out
    line = last_line(text)
    assert line["correct"] is True, line.get("reasons")
    got = line["metrics"]
    spans = {"step_prepare_ms", "step_dispatch_ms", "step_write_back_ms"}
    assert spans <= set(got)
    assert not (set(READERS) - spans) & set(got)
    for name in spans:
        assert got[name]["unit"] == "ms" and got[name]["value"] > 0
    # the parts are the enqueue: no more than the call the driver times
    assert sum(got[n]["value"] for n in spans) \
        <= 1.5 * got["step_call_ms"]["value"]
    notes = [json.loads(l[len("benchmark: "):]) for l in text.splitlines()
             if l.startswith("benchmark: {")]
    absent = [n for n in notes if n.get("scopes") == "absent"]
    assert len(absent) == 1 and "no device op" in absent[0]["why"]
