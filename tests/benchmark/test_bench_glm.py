"""The GLM-4.7-Flash cell: its configuration against the published one,
its manifest entries, the rehearsal through run.py, and the readers of its
per-layer metrics on a hand-made trace and on counters."""
import importlib.util
import json
import os
import types

import pytest

from bench_helpers import (DATA, REPO, last_line, manifest, rehearsal_root,
                           write_manifest)
from benchmark import run as bench_run
from benchmark.harness import peaks, scope_time

CELL = "glm47flash-train-s4096-ep8share"
DEVICE = "/device:TPU:0"

# the catalog row's `config` (model-configs guide, GLM-4.7-Flash; source:
# https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json)
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def _load(*relative):
    path = os.path.join(REPO, "benchmark", *relative)
    spec = importlib.util.spec_from_file_location(
        "_loaded_" + relative[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*relative):
    with open(os.path.join(REPO, "benchmark", *relative)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _json("configs", "glm_4_7_flash.json")


# -- the configuration file ---------------------------------------------------

def test_every_published_key_is_kept_or_listed_as_reduced(config):
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        assert key in config, key
        if key in reduced:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key


def test_the_cut_is_the_guides_floor_and_states_its_deployment(config):
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] == len(config["experts_held"]) == 8
    assert config["n_routed_experts_published"] == 64
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "8 chips share each layer" in config["deployment"]
    for key in ("rotary_pair_layout", "router_correction", "mtp_loss_weight",
                "optimizer", "initializer", "document_mask", "dropout",
                "token_ids"):
        assert config["assumed"][key], key
    assert config["optimizer"]["name"] == "adamw"
    assert config["optimizer"]["multi_precision"] is True
    assert 0 < config["check_tolerance"] <= 0.1
    assert config["check_tolerance_why"] and config["check_routing_gap"] > 0


# the accepted tests of tests/benchmark pin the cell lists of these ten
# (test_bench_program_trace.py, test_bench_attention_readers.py), and a PR
# may not edit them: the cell reports them through `<reader>.clm` entries
PINNED = ("forward_ms", "backward_ms", "optimizer_ms", "scope_unattributed",
          "other_programs_ms", "step_prepare_ms", "step_dispatch_ms",
          "step_write_back_ms", "attention_ms", "attention_kernel_share")


@pytest.mark.parametrize("name,listed", [
    (n, True) for n in (
        "train_tokens_per_s", "data_wait_ms", "step_call_ms",
        "compiles_in_window", "mfu", "step_roofline", "device_idle",
        "peak_hbm_gb", "mla_ms", "mla_core_roofline", "moe_ms",
        "moe_experts_roofline", "moe_overhead_share",
        "expert_load_max_over_mean", "expert_assignments_here", "mtp_ms",
        "recompute_ms") + tuple(n + ".clm" for n in PINNED)
] + [(n, False) for n in PINNED + ("attention_roofline",
                                   "train_images_per_s", "collective_ms",
                                   "mfu.images")])
def test_the_cell_is_on_the_lists_the_issue_names(name, listed):
    m = manifest()
    entry = next(e for e in m["end_to_end"] + m["per_layer"]
                 if e["name"] == name)
    assert (CELL in entry["workloads"]) == listed
    if listed and name not in ("train_tokens_per_s",):
        assert entry["moves"] == "train_tokens_per_s"
    if name.endswith(".clm"):
        twin = next(e for e in m["per_layer"]
                    if e["name"] == name[:-len(".clm")])
        assert entry["workloads"] == [CELL]
        assert {k: entry[k] for k in ("unit", "better", "source", "layer",
                                      "moves")} == \
            {k: twin[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}


def test_the_new_entries_stand_at_the_end_and_name_the_cell_alone():
    m = manifest()
    assert m["configs"][-1]["name"] == "glm_4_7_flash"
    assert m["configs"][-1]["reduced"] == ["num_hidden_layers",
                                           "n_routed_experts", "vocab_size"]
    cell = m["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="glm_4_7_flash",
                        traffic="clm-s4096-b2", chips=1)
    assert [e["name"] for e in m["per_layer"][-19:]] == [
        "mla_ms", "mla_core_roofline", "moe_ms", "moe_experts_roofline",
        "moe_overhead_share", "expert_load_max_over_mean",
        "expert_assignments_here", "mtp_ms", "recompute_ms"] \
        + [n + ".clm" for n in PINNED]
    for e in m["per_layer"][-19:]:
        assert e["workloads"] == [CELL]
    traffic = _json("traffic", "clm-s4096-b2.json")
    assert (traffic["batch"], traffic["seq"], traffic["pool"],
            traffic["sync_every"], traffic["trace_seconds"],
            traffic["mesh"], traffic["driver"]) == (
        2, 4096, 8, 5, 4, None, "train_steps")


# -- the rehearsal through run.py ---------------------------------------------

def test_rehearsal_trains_the_new_model_on_the_host(tmp_path, capsys):
    """`rehearsal_root` copies `tiny_glm.json` and `tiny-clm.json` beside
    the others; the cell's manifest is a file of its own (the accepted
    rehearsal manifest is not this PR's to edit)."""
    root, _ = rehearsal_root(tmp_path)
    with open(os.path.join(DATA, "rehearsal_manifest_glm.json")) as f:
        write_manifest(root, json.load(f))
    rc = bench_run.main(["--root", root, "--workload", "tiny-glm-train",
                         "--seed", str(2 ** 31 + 7), "--seconds", "1",
                         "--trace", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    line = last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10 and line["attempted"] % 5 == 0
    metrics = line["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    # 2 of 8 experts held, top-2: a quarter of the assignments at most
    assert 0 < metrics["expert_assignments_here"]["value"] < 60
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
    notes = [json.loads(l[len("benchmark: "):]) for l in out.splitlines()
             if l.startswith("benchmark: {")]
    routing = next(n for n in notes if "routing_choices" in n)
    assert routing["routing_choices_differ_share"] == 0.0      # float32
    assert routing["routing_choices"] == 3 * 2 * 32
    check = next(n for n in notes if "reference_err" in n)
    assert check["reference_err"] < 1e-5


# -- scope_time on a hand-made trace ------------------------------------------

FWD = "jit(mx_step_step)/jvp(forward)/GLMMoeLite/blocks/1/%s"
BWD = "jit(mx_step_step)/transpose(jvp(forward))/GLMMoeLite/blocks/1/" \
    "jvp(forward)/GLMMoeLite/blocks/1/checkpoint/%s"


def _where(top, scope, mixed=False):
    return {"scope": scope, "top": top, "tops": [top], "mixed": mixed}


@pytest.fixture()
def handmade():
    """Two runs of a step on one device; durations in ns."""
    rows = [
        ("fusion.1", "forward", FWD % "mla/q_a_proj/jit(mx_op_FullyConnected)/dot_general", 1000),
        ("custom-call.1", "forward", FWD % "mla/jit(mx_op_multi_head_attention)/attention_core/pallas_call", 400),
        ("fusion.2", "forward", FWD % "moe/jit(mx_op_moe_token_choice)/route/top_k", 200),
        ("fusion.3", "forward", FWD % "moe/jit(mx_op_moe_token_choice)/dispatch/gather", 300),
        ("fusion.4", "forward", FWD % "moe/jit(mx_op_moe_token_choice)/experts/ragged_dot", 500),
        ("fusion.5", "forward", FWD % "moe/jit(mx_op_moe_token_choice)/combine/gather", 100),
        ("fusion.6", "forward", FWD % "moe/shared/down_proj/jit(mx_op_FullyConnected)/dot_general", 600),
        ("fusion.7", "backward", BWD % "rematted_computation/mla/q_a_proj/jit(mx_op_FullyConnected)/dot_general", 1000),
        ("custom-call.2", "backward", BWD % "rematted_computation/mla/jit(mx_op_multi_head_attention)/attention_core/pallas_call", 400),
        ("custom-call.3", "backward", BWD % "mla/jit(mx_op_multi_head_attention)/attention_core/pallas_call", 900),
        ("fusion.8", "backward", BWD % "moe/jit(mx_op_moe_token_choice)/experts/ragged_dot", 1100),
        ("fusion.9", "forward", "jit(mx_step_step)/jvp(forward)/GLMMoeLite/mtp/eh_proj/jit(mx_op_FullyConnected)/dot_general", 250),
        ("fusion.10", "forward", "jit(mx_step_step)/jvp(forward)/GLMMoeLite/mtp/block/moe/jit(mx_op_moe_token_choice)/experts/ragged_dot", 150),
        ("fusion.11", "forward", "jit(mx_step_step)/jvp(forward)/GLMMoeLite/lm_head/proj/jit(mx_op_FullyConnected)/dot_general", 700),
        # a weight gradient fused with its optimizer update: mixed, left out
        ("fusion.12", "optimizer", BWD % "moe/shared/down_proj/jit(mx_op_FullyConnected)/dot_general", 5000),
        ("fusion.13", "optimizer", "jit(mx_step_step)/optimizer/mul", 50),
        # XLA:TPU's expansion of lax.ragged_dot: no scope left, no top
        ("ragged-dot-none.1", None, "ragged-dot-none", 2000),
        ("ragged-dot-metadata", None, "ragged-dot-metadata", 10),
        ("copy.1", None, "", 70),
    ]
    ops, modules, instructions = [], [], {}
    for start in (0, 100000):
        modules.append(["jit_mx_step_step", start, 50000])
        at = start
        for name, top, scope, dur in rows:
            ops.append([name, "fusion", at, dur])
            at += dur
            instructions[name] = _where(top, scope, mixed=name == "fusion.12")
            if name == "fusion.12":
                instructions[name]["tops"] = ["backward", "optimizer"]
    trace = {"devices": {DEVICE: {"ops": ops, "async": [],
                                  "modules": modules}}, "host": []}
    return trace, {"module": "jit_mx_step_step",
                   "instructions": instructions}


def test_under_matches_whole_components_in_order():
    path = BWD % "rematted_computation/moe/jit(mx_op_x)/route/top_k"
    assert scope_time.under(path, ("moe",))
    assert scope_time.under(path, ("moe", "route"))
    assert scope_time.under(path, ("rematted_computation",))
    assert not scope_time.under(path, ("route", "moe"))
    assert not scope_time.under(path, ("mo",))
    assert not scope_time.under(FWD % "moe_x/route", ("moe",))


def test_per_step_sums_by_scope_and_leaves_mixed_fusions_out(handmade):
    trace, scopes = handmade
    got = scope_time.per_step(trace, scopes)
    ms = {k: None if v is None else round(v * 1e6) for k, v in got.items()
          if k != "mixed"}                          # back to ns a step
    assert ms["mla"] == 1000 + 400 + 1000 + 400 + 900
    assert ms["attention_core"] == 400 + 400 + 900
    assert ms["moe"] == 200 + 300 + 500 + 100 + 600 + 1100 + 150 + 2010
    assert ms["moe/route"] == 200 and ms["moe/dispatch"] == 300
    assert ms["moe/experts"] == 500 + 1100 + 150 + 2010    # ragged-dot-*
    assert ms["moe/combine"] == 100 and ms["moe/shared"] == 600
    assert ms["mtp"] == 250 + 150
    assert ms["lm_head"] == 700
    assert ms["recompute"] == 1000 + 400
    assert round(got["mixed"]["moe"] * 1e6) == 5000
    assert round(got["mixed"]["moe/shared"] * 1e6) == 5000
    assert "mla" not in got["mixed"]


def test_a_program_without_the_scopes_reads_nothing(handmade):
    trace, scopes = handmade
    bert = {"module": scopes["module"], "instructions": {
        name: _where(w["top"], "jit(mx_step_step)/jvp(forward)/BERTModel/x")
        for name, w in scopes["instructions"].items()}}
    got = scope_time.per_step(trace, bert)
    assert all(got[k] is None for k in scope_time.SCOPES)
    assert scope_time.per_step(trace, None) is None
    assert scope_time.per_step({"devices": {}}, scopes) is None
    run = types.SimpleNamespace(trace=False, facts={}, cell={"name": CELL},
                                cache_dir="/nonexistent")
    assert scope_time.ms(run, "mla") is None


# -- the readers ----------------------------------------------------------------

def _run(config, times):
    model = _load("models", "glm_4_7_flash.py")
    traffic = _json("traffic", "clm-s4096-b2.json")
    notes = []
    run = types.SimpleNamespace(
        trace=True, cell={"name": CELL, "chips": 1}, config=config,
        facts={"scope_time": times, "device": {"kind": "TPU v5 lite"},
               "ops": model.ops_and_bytes(config, traffic)},
        note=lambda **kw: notes.append(kw))
    return run, notes


def test_scope_readers_read_their_scope(config):
    times = {"mla": 180.0, "moe": 60.0, "mtp": 50.0, "recompute": 70.0,
             "moe/route": 9.0, "moe/dispatch": 12.0, "moe/combine": 9.0}
    run, _ = _run(config, times)
    for name, want in (("mla_ms", 180.0), ("moe_ms", 60.0),
                       ("mtp_ms", 50.0), ("recompute_ms", 70.0),
                       ("moe_overhead_share", 50.0)):
        assert _load("layer_metrics", name + ".py").read(run) == want
    run, _ = _run(config, None)                      # not traced
    for name in ("mla_ms", "moe_ms", "mtp_ms", "recompute_ms",
                 "moe_overhead_share", "mla_core_roofline",
                 "moe_experts_roofline"):
        assert _load("layer_metrics", name + ".py").read(run) is None


def test_rooflines_count_required_work_over_the_scopes_time(config):
    peak = peaks.peaks_for("TPU v5 lite")
    run, notes = _run(config, {"attention_core": 100.0, "moe/experts": 20.0})
    forward = run.facts["ops"]["detail"]["forward"]
    core = _load("layer_metrics", "mla_core_roofline.py").read(run)
    # 6 blocks x 2 rows x 20 heads x (256 + 256) lanes x 4096^2 / 2, x 3
    assert forward["mla_core"] == 6 * 2 * 20 * 512 * 4096 * 4096
    least = 1e3 * 3 * forward["mla_core"] / peak["bf16_flops_per_s"]
    assert core == pytest.approx(100.0 * least / 100.0)
    assert 25 < core < 40                           # 31.4 ms of products
    experts = _load("layer_metrics", "moe_experts_roofline.py").read(run)
    compute = forward["moe_routed"] / peak["bf16_flops_per_s"]
    memory = run.facts["ops"]["detail"]["held_expert_weight_bytes"] \
        / peak["hbm_bytes_per_s"]
    assert compute > memory                          # 512 tokens an expert
    assert experts == pytest.approx(100.0 * 1e3 * 3 * compute / 20.0)
    assert notes[-1]["moe_experts_bound"] == "compute"
    assert experts < 100 and core < 100


def test_counter_readers_read_the_registry(config):
    from mxnet_tpu import telemetry
    values = {"0": [600.0, 500.0, 400.0, 548.0], "mtp": [512.0] * 4}
    for layer, counts in values.items():
        for expert, count in enumerate(counts):
            telemetry.registry.read_counter(
                "moe_assignments", lambda c=count: c,
                labels={"layer": "t" + layer, "expert": str(expert)})
        telemetry.registry.read_counter(
            "moe_assignments_elsewhere", lambda c=counts: 7.0 * sum(c),
            labels={"layer": "t" + layer})
    counters = _load("harness", "expert_counters.py")
    here, away = counters.assignments()
    assert here["t0"] == {"0": 600.0, "1": 500.0, "2": 400.0, "3": 548.0}
    assert away["tmtp"] == 7 * 2048.0
    run, notes = _run(config, None)
    ratio = _load("layer_metrics", "expert_load_max_over_mean.py").read(run)
    share = _load("layer_metrics", "expert_assignments_here.py").read(run)
    # other tests' layers may be registered too: check these two by hand
    assert here["t0"] and max(here["t0"].values()) / 512.0 \
        == pytest.approx(600 / 512)
    assert ratio >= 1.0 and 0 < share <= 100
    only = {k: v for k, v in here.items() if k in ("t0", "tmtp")}
    held = sum(sum(c.values()) for c in only.values())
    assert 100.0 * held / (held + away["t0"] + away["tmtp"]) == 12.5
