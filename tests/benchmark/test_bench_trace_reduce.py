"""trace_reduce against a hand-made trace whose every number is worked out
in the comments, and against a small trace recorded on the chip."""
import json
import os

import pytest

from bench_helpers import DATA
from benchmark.harness import trace_reduce as tr

MS = 1_000_000      # ns


def hand_trace():
    """Two devices, a 100 ms window.  Times in ms:

    device A   ops:   matmul   [10, 30)      'fusion:kOutput f32[8]'
                      gather   [30, 45)      all-gather (done: the core waits)
                      matmul   [50, 70)
                      while    [70, 90)  containing  body [72, 80) 'fusion:kLoop f32[8]'
               async: gather   [20, 45)      the transfer in flight
               modules: step   [10, 90)
    device B   ops:   matmul   [10, 40)
               modules: step   [10, 40)
    host:      bench.trace_window [0, 100), bench.step_call [0, 8),
               bench.sync [8, 100)
    """
    a = {"ops": [["fusion.1", "fusion:kOutput f32[8]", 10 * MS, 20 * MS],
                 ["all-gather-done.1", "all-gather", 30 * MS, 15 * MS],
                 ["fusion.2", "fusion:kOutput f32[8]", 50 * MS, 20 * MS],
                 ["while.1", "while f32[8]", 70 * MS, 20 * MS],
                 ["fusion.3", "fusion:kLoop f32[8]", 72 * MS, 8 * MS]],
         "async": [["all-gather-start.1", "all-gather", 20 * MS, 25 * MS]],
         "modules": [["step", 10 * MS, 80 * MS]]}
    b = {"ops": [["fusion.1", "fusion:kOutput f32[8]", 10 * MS, 30 * MS]],
         "async": [], "modules": [["step", 10 * MS, 30 * MS]]}
    host = [["bench.trace_window", 0, 100 * MS],
            ["bench.step_call", 0, 8 * MS], ["bench.sync", 8 * MS, 92 * MS]]
    return {"devices": {"/device:TPU:0": a, "/device:TPU:1": b},
            "host": host}


def test_hand_made_trace():
    r = tr.reduce(hand_trace())
    assert r["devices"] == 2 and r["window_s"] == pytest.approx(0.100)
    # busy: A = [10,45) + [50,90) = 75 ms (the while's child adds nothing);
    # B = 30 ms; mean 52.5 ms.  Idle: A 25 %, B 70 %; mean 47.5 %
    assert r["busy_s"] == pytest.approx(0.0525)
    assert r["idle_share"] == pytest.approx(0.475)
    # groups are SELF times, mean over the two devices:
    #   fusion:kOutput  A 20+20, B 30           -> 35
    #   all-gather      A 15 (ops line)         -> 7.5
    #   while           A 20 - 8 (its child)    -> 6
    #   fusion:kLoop    A 8                     -> 4
    assert r["groups"] == pytest.approx({
        "fusion:kOutput f32[8]": 0.035, "all-gather": 0.0075,
        "while f32[8]": 0.006, "fusion:kLoop f32[8]": 0.004})
    assert list(r["groups"])[0] == "fusion:kOutput f32[8]"   # most first
    assert r["kinds"] == pytest.approx({
        "fusion:kOutput": 0.035, "all-gather": 0.0075, "while": 0.006,
        "fusion:kLoop": 0.004})
    # collectives: A's union of [30,45) and the transfer [20,45) = 25 ms,
    # of which [20,30) ran under the first matmul: 15 ms exposed.  Means:
    assert r["collective_s"] == pytest.approx(0.0125)
    assert r["collective_exposed_s"] == pytest.approx(0.0075)
    # gaps of A: [0,10) -> step_call covers 8 of it; [45,50) inside the
    # step's module; [90,100) -> sync.  Of B: [0,10) step_call; [40,100)
    # sync.  Means over two devices:
    assert r["idle_gaps"] == pytest.approx({
        "bench.sync": (0.010 + 0.060) / 2, "bench.step_call": 0.010,
        "device.between_ops": 0.0025})
    # the step program: module "step"; median of its events' durations
    assert r["step_module"] == "step" and r["step_events"] == 1
    assert r["step_busy_s"] in (0.080, 0.030)
    b = tr.breakdown(r, top=2)
    assert b["device_ops"] == [["fusion:kOutput f32[8]", pytest.approx(0.035)],
                               ["all-gather", pytest.approx(0.0075)]]
    assert b["idle_gaps"][0] == ["bench.sync", pytest.approx(0.035)]


def test_a_gap_no_span_covers_takes_the_default_name():
    t = hand_trace()
    t["host"] = [h for h in t["host"] if h[0] != "bench.sync"]
    r = tr.reduce(t, host_default="replica.host")
    assert r["idle_gaps"]["replica.host"] == pytest.approx(0.035)
    assert tr.reduce({"devices": {}, "host": []}) is None


def test_interval_arithmetic():
    assert tr.union([[5, 7], [1, 3], [2, 4], [7, 8], [9, 9]]) \
        == [[1, 4], [5, 8]]
    assert tr.total([[1, 4], [5, 8]]) == 6
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tr.clip([[0, 10], [20, 30]], 5, 25) == [[5, 10], [20, 25]]
    ops = [["w", "g", 0, 100], ["a", "g", 10, 20], ["b", "g", 12, 5],
           ["c", "g", 200, 10]]
    assert tr.self_times(ops) == [80, 15, 5, 10]


def test_names_from_the_hlo_text_a_tpu_trace_carries():
    text = ("%fusion.543 = (bf16[32,512,3072]{2,1,0:T(8,128)(2,1)}, "
            "bf16[32,512,3072]{2,1,0:T(8,128)(2,1)}) fusion(bf16[3072]{0:"
            "T(1024)(128)(2,1)S(1)} %copy-done.963), kind=kOutput, "
            "calls=%fused_computation")
    assert tr.parse_hlo(text)[:2] == ("fusion.543", "fusion")
    assert tr.group_of(text, {}) \
        == "fusion:kOutput (bf16[32,512,3072], bf16[32,512,3072])"
    assert tr.group_of(text, {"hlo_category": "convolution"}) == "convolution"
    start = ("%all-gather-start.12 = (bf16[8,768]{1,0}, bf16[32,768]{1,0}) "
             "all-gather-start(bf16[8,768]{1,0} %p), dimensions={0}")
    assert tr.collective_kind(start) == "all-gather"
    assert tr.group_of(start, {"hlo_category": "x"}) == "all-gather"
    assert tr.collective_kind("reduce-scatter.7") == "reduce-scatter"
    assert tr.collective_kind(text) is None
    assert tr.group_of("fusion.22", {}) == "fusion"


def test_recorded_v5e_trace():
    """400 ops of one BERT-base step on a TPU v5 lite (PR 23); what was cut
    and how is in the file's own note.  The expected numbers were worked
    out apart from trace_reduce, by a sweep over sorted event boundaries:
    no two of the 400 ops overlap, so busy is the plain sum of their
    durations, 11,096,043 ns of the 14 ms window."""
    with open(os.path.join(DATA, "trace_v5e_bert_base.json")) as f:
        trace = json.load(f)
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    assert len(ops) == 400 and sum(o[3] for o in ops) == 11096043
    r = tr.reduce(trace)
    assert r["window_s"] == pytest.approx(0.014)
    assert r["busy_s"] == pytest.approx(0.011096043)
    assert r["idle_share"] == pytest.approx(1 - 11096043 / 14e6)
    assert sum(r["groups"].values()) == pytest.approx(0.011096043)
    # the widest group of a BERT layer's forward: the FFN's first product
    # with its GELU, output (32, 512, 3072) twice
    top = next(iter(r["groups"]))
    assert top == "fusion:kOutput (bf16[32,512,3072], bf16[32,512,3072])"
    assert r["groups"][top] == pytest.approx(0.002757145)
    # 315 gaps, 2,903,957 ns.  Three lie outside any running program -
    # before the first op (290,503), between jit_fold_in and the step
    # (2,243,026 less fold_in's ops... in all 2,843,223) - and the host was
    # inside step.step() for all of them; the rest are the pauses between
    # the ops of the running step
    assert sum(r["idle_gaps"].values()) == pytest.approx(0.002903957)
    assert r["idle_gaps"]["bench.step_call"] == pytest.approx(0.002843223)
    assert r["idle_gaps"]["device.between_ops"] == pytest.approx(6.0734e-05)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    assert r["step_module"] == "jit__traced_step_window"
