"""The Nemotron-3-Super cell: its configuration against the published one,
its manifest entries (found by NAME, never by position), the rehearsal
through run.py, and the readers of its per-layer metrics on a hand-made
trace."""
import importlib.util
import json
import os
import types

import pytest

from bench_helpers import (DATA, REPO, last_line, manifest, rehearsal_root,
                           write_manifest)
from benchmark import run as bench_run
from benchmark.harness import peaks, scope_time, scope_time_ssm

CELL = "nemotron3super-train-s8192-ep64tp8share"
GLM_CELL = "glm47flash-train-s4096-ep8share"
DEVICE = "/device:TPU:0"

# the catalog row's `config` (model-configs guide,
# NVIDIA-Nemotron-3-Super-120B-A12B-BF16; source:
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json)
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
    "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "mamba_num_heads", "n_groups", "num_attention_heads",
           "num_key_value_heads"]
# what may never be listed as reduced: the widths
WIDTHS = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
          "chunk_size", "conv_kernel", "expand", "intermediate_size",
          "moe_intermediate_size", "moe_latent_size",
          "moe_shared_expert_intermediate_size", "num_experts_per_tok")


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
    return _json("configs", "nemotron_3_super.json")


@pytest.fixture(scope="module")
def traffic():
    cell = next(c for c in manifest()["workloads"] if c["name"] == CELL)
    return _json("traffic", cell["traffic"] + ".json")


# -- the configuration file ---------------------------------------------------

def test_every_published_key_is_kept_or_listed_as_reduced(config):
    assert config["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        assert key in config, key
        if key in REDUCED:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    for key in WIDTHS:
        assert key not in REDUCED and config[key] == PUBLISHED[key], key


def test_the_cut_is_one_chips_share_of_64_and_states_its_deployment(config):
    tp = config["tensor_parallel"]
    assert tp == 8 and config["tensor_parallel_rank"] == 0
    assert config["n_routed_experts"] == len(config["experts_held"]) == 8
    assert config["n_routed_experts_published"] == 512 == 64 * 8
    for key in ("mamba_num_heads", "n_groups", "num_attention_heads",
                "vocab_size"):
        assert config[key] * tp == PUBLISHED[key], key
    assert config["num_key_value_heads"] == 1       # 2 heads over 8 ranks
    assert config["shared_expert_columns_held"] * tp \
        == PUBLISHED["moe_shared_expert_intermediate_size"]
    # one whole period of the published pattern, every kind of layer in it
    first, last = config["layers_held"]
    assert PATTERN[first:last + 1] == config["pattern_held"] \
        == "MEMEMEMEM*E"
    assert len(config["pattern_held"]) == config["num_hidden_layers"] == 11
    assert PATTERN.count("M") == PATTERN.count("E") == 40
    assert "64 chips share each layer" in config["deployment"]
    assert "EP 64 x TP 8 x DP 8" in config["deployment"]
    for key in ("position_signal", "sequence_length", "router_correction",
                "mtp_loss_weight", "scan_precision", "mamba_initializer",
                "optimizer", "initializer", "document_mask", "dropout",
                "token_ids", "recomputation"):
        assert config["assumed"][key], key
    assert "607.0 M parameters" in config["why"]
    assert config["optimizer"]["name"] == "adamw"
    assert config["optimizer"]["multi_precision"] is True
    assert 0 < config["check_tolerance"] <= 0.1
    assert config["check_tolerance_why"] and config["check_routing_gap"] > 0


def test_the_model_file_counts_the_configurations_parameters(config,
                                                             traffic):
    model = _load("models", "nemotron_3_super.py")
    ops = model.ops_and_bytes(config, traffic)
    assert round(ops["n_params"] / 1e6, 1) == 607.0
    assert round(ops["bytes"] / 2 / 1e9, 2) == 8.50
    tokens = traffic["batch"] * traffic["seq"]
    assert ops["detail"]["expected_assignments_per_expert"] \
        == tokens * 22 / 512
    assert model.MTP_WEIGHT == 0.1 and "0.1" in \
        config["assumed"]["mtp_loss_weight"]


# -- the manifest: by name, never by position ---------------------------------

TWINS = ("forward_ms", "backward_ms", "optimizer_ms", "scope_unattributed",
         "other_programs_ms", "step_prepare_ms", "step_dispatch_ms",
         "step_write_back_ms", "attention_ms", "attention_kernel_share",
         "moe_ms", "moe_experts_roofline", "moe_overhead_share",
         "expert_load_max_over_mean", "expert_assignments_here", "mtp_ms",
         "recompute_ms")
OWN = ("ssm_ms", "ssm_scan_ms", "ssm_scan_roofline", "ssm_scan_kernel_share",
       "moe_latent_ms")
SHARED = ("train_tokens_per_s", "data_wait_ms", "step_call_ms",
          "compiles_in_window", "mfu", "step_roofline", "device_idle",
          "peak_hbm_gb")


def _entry(name):
    m = manifest()
    return next(e for e in m["end_to_end"] + m["per_layer"]
                if e["name"] == name)


@pytest.mark.parametrize("name", SHARED)
def test_the_shared_lists_gain_the_cell_and_lose_none(name):
    cells = _entry(name)["workloads"]
    assert cells[-1] == CELL and cells.count(CELL) == 1
    assert cells[:-1] == ["bert-base-train-s512",
                          "bert-base-train-s512-fsdp4", GLM_CELL]


@pytest.mark.parametrize("name", TWINS)
def test_an_accepted_reader_serves_the_cell_through_its_ssm_entry(name):
    entry, twin = _entry(name + ".ssm"), _entry(name)
    assert entry["workloads"] == [CELL]
    assert CELL not in twin["workloads"]            # pinned lists stay
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == twin[key], key
    assert entry["moves"] == "train_tokens_per_s"
    assert os.path.isfile(os.path.join(REPO, "benchmark", "layer_metrics",
                                       name + ".py"))


@pytest.mark.parametrize("name", OWN)
def test_the_new_metrics_name_the_cell_alone_and_have_a_reader(name):
    entry = _entry(name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["layer"] == "Kernels" and entry["source"] == "device_trace"
    assert (entry["unit"] == "%") == name.endswith(("roofline", "share"))
    assert hasattr(_load("layer_metrics", name + ".py"), "read")


def test_the_configuration_and_the_cell_are_listed_once(traffic):
    m = manifest()
    entry, = [c for c in m["configs"] if c["name"] == "nemotron_3_super"]
    assert entry["file"] == "benchmark/configs/nemotron_3_super.json"
    assert entry["source"] == ("https://huggingface.co/nvidia/NVIDIA-"
                               "Nemotron-3-Super-120B-A12B-BF16/blob/main/"
                               "config.json")
    assert entry["reduced"] == REDUCED
    cell, = [c for c in m["workloads"] if c["name"] == CELL]
    assert cell == dict(cell, config="nemotron_3_super", chips=1)
    assert cell["traffic"] == "clm-s8192-b1"        # 2 rows do not fit
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert (traffic["seq"], traffic["pool"], traffic["sync_every"],
            traffic["trace_seconds"], traffic["mesh"], traffic["driver"]) \
        == (8192, 8, 5, 6, None, "train_steps")
    assert traffic["batch"] == 1
    # every metric that moves the cell's throughput and lists its cells
    # names the cell itself or has a twin that does
    for e in m["per_layer"]:
        if e["moves"] != "train_tokens_per_s" or CELL in e["workloads"]:
            continue
        base = e["name"].split(".")[0]
        named = [o for o in m["per_layer"] if o["name"].split(".")[0] == base
                 and CELL in o["workloads"]]
        only_elsewhere = base in ("collective_ms", "collective_exposed_ms",
                                  "state_share_per_chip",
                                  "attention_roofline", "mla_ms",
                                  "mla_core_roofline")
        assert named or only_elsewhere, e["name"]


# -- the rehearsal through run.py ---------------------------------------------

def test_rehearsal_trains_the_new_model_on_the_host(tmp_path, capsys):
    """`rehearsal_root` copies `tiny_nemotron.json` and `tiny-clm.json`
    beside the others; the cell's manifest is a file of its own."""
    root, _ = rehearsal_root(tmp_path)
    with open(os.path.join(DATA, "rehearsal_manifest_nemotron.json")) as f:
        write_manifest(root, json.load(f))
    rc = bench_run.main(["--root", root, "--workload", "tiny-nemotron-train",
                         "--seed", str(2 ** 31 + 7), "--seconds", "2",
                         "--trace", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    line = last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10 and line["attempted"] % 5 == 0
    metrics = line["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    # 3 of 16 experts held, top-5: 3/16 of the assignments at an even load
    assert 0 < metrics["expert_assignments_here"]["value"] < 60
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
    notes = [json.loads(l[len("benchmark: "):]) for l in out.splitlines()
             if l.startswith("benchmark: {")]
    # the trace opens at the first sync past seconds - trace_seconds; on a
    # loaded host that sync can be the one that ends the window, so the
    # span is asked for only where a trace was read
    if any("spans_ms" in n for n in notes):
        assert metrics["step_prepare_ms.ssm"]["value"] > 0
    # XLA:CPU's trace has no device plane: the scope readers read nothing
    for name in OWN + ("moe_ms.ssm",):
        assert name not in metrics
    routing = next(n for n in notes if "routing_choices" in n)
    assert routing["routing_choices_differ_share"] == 0.0      # float32
    assert routing["routing_choices"] == 3 * 2 * 32
    check = next(n for n in notes if "reference_err" in n)
    assert check["reference_err"] < 1e-5


def test_a_manifest_without_the_cell_refuses_its_name_at_once(tmp_path):
    """What a checkout from before this cell does with its name: run.py
    exits on the manifest alone, before it loads a driver or jax."""
    m = manifest()
    m["workloads"] = [c for c in m["workloads"] if c["name"] != CELL]
    root = str(tmp_path / "root")
    os.makedirs(root)
    write_manifest(root, m)
    with pytest.raises(SystemExit) as refused:
        bench_run.main(["--root", root, "--workload", CELL, "--seed", "1"])
    assert "no cell" in str(refused.value) and CELL in str(refused.value)


# -- scope_time_ssm on a hand-made trace ---------------------------------------

FWD = "jit(mx_step_step)/jvp(forward)/NemotronH/blocks/0/%s"
BWD = "jit(mx_step_step)/transpose(jvp(forward))/NemotronH/blocks/0/" \
    "jvp(forward)/NemotronH/blocks/0/checkpoint/%s"
MOE = "jit(mx_step_step)/jvp(forward)/NemotronH/blocks/1/moe/%s"


def _where(top, scope, mixed=False):
    return {"scope": scope, "top": top, "tops": [top], "mixed": mixed}


@pytest.fixture()
def handmade():
    """Two runs of a step on one device; durations in ns."""
    rows = [
        ("fusion.1", "forward", FWD % "ssm/in_proj/jit(mx_op_FullyConnected)/dot_general", 1000, "fusion"),
        ("fusion.2", "forward", FWD % "ssm/jit(mx_op_causal_conv1d)/conv/add", 100, "fusion"),
        ("fusion.3", "forward", FWD % "ssm/jit(mx_op_ssm_scan)/scan/dot_general", 700, "fusion"),
        ("custom-call.1", "forward", FWD % "ssm/jit(mx_op_ssm_scan)/scan/pallas_call", 300, "custom-call:tpu_custom_call"),
        ("fusion.4", "backward", BWD % "ssm/jit(mx_op_ssm_scan)/scan/dot_general", 1500, "fusion"),
        ("fusion.5", "backward", BWD % "rematted_computation/ssm/in_proj/jit(mx_op_FullyConnected)/dot_general", 900, "fusion"),
        ("fusion.6", "forward", MOE % "latent/latent_down/jit(mx_op_FullyConnected)/dot_general", 400, "fusion"),
        ("fusion.7", "forward", MOE % "latent/latent_up/jit(mx_op_FullyConnected)/dot_general", 450, "fusion"),
        ("fusion.8", "forward", MOE % "jit(mx_op_moe_token_choice)/experts/ragged_dot", 500, "fusion"),
        ("custom-call.2", "forward", "jit(mx_step_step)/jvp(forward)/NemotronH/blocks/9/attention/jit(mx_op_multi_head_attention)/attention_core/pallas_call", 600, "custom-call:tpu_custom_call"),
        # a weight gradient fused with its optimizer update: mixed, left out
        ("fusion.9", "optimizer", BWD % "ssm/out_proj/jit(mx_op_FullyConnected)/dot_general", 5000, "fusion"),
    ]
    ops, modules, instructions = [], [], {}
    for start in (0, 100000):
        modules.append(["jit_mx_step_step", start, 50000])
        at = start
        for name, top, scope, dur, group in rows:
            ops.append([name, group, at, dur])
            at += dur
            instructions[name] = _where(top, scope, mixed=name == "fusion.9")
    trace = {"devices": {DEVICE: {"ops": ops, "async": [],
                                  "modules": modules}}, "host": []}
    return trace, {"module": "jit_mx_step_step",
                   "instructions": instructions}


def test_the_new_scopes_are_read_beside_the_accepted_ones(handmade):
    assert not set(scope_time_ssm.SCOPES) & set(scope_time.SCOPES)
    trace, scopes = handmade
    got = scope_time_ssm.reduce(trace, scopes)
    ns = {k: None if v is None else round(v * 1e6) for k, v in got.items()
          if k != "mixed"}
    assert ns["ssm"] == 1000 + 100 + 700 + 300 + 1500 + 900
    assert ns["ssm/conv"] == 100
    assert ns["ssm/scan"] == 700 + 300 + 1500
    assert ns[scope_time_ssm.KERNELS] == 300        # not the attention's
    assert ns["moe/latent"] == 400 + 450
    assert round(got["mixed"]["ssm"] * 1e6) == 5000
    # the accepted reader on the same trace still reads its own scopes
    old = scope_time.per_step(trace, scopes)
    assert round(old["moe"] * 1e6) == 400 + 450 + 500
    assert round(old["attention_core"] * 1e6) == 600


def test_a_composed_scan_has_no_kernel_share_and_a_parent_reads_nothing(
        handmade):
    trace, scopes = handmade
    composed = dict(scopes, instructions={
        k: v for k, v in scopes["instructions"].items()
        if k != "custom-call.1"})
    got = scope_time_ssm.reduce(trace, composed)
    assert got[scope_time_ssm.KERNELS] == 0.0 and got["ssm/scan"] > 0
    glm = {"module": scopes["module"], "instructions": {
        name: _where(w["top"], "jit(mx_step_step)/jvp(forward)/GLMMoeLite/x")
        for name, w in scopes["instructions"].items()}}
    got = scope_time_ssm.reduce(trace, glm)
    assert all(got[k] is None for k in scope_time_ssm.SCOPES)
    assert got[scope_time_ssm.KERNELS] is None
    assert scope_time_ssm.reduce(trace, None) is None
    assert scope_time_ssm.reduce({"devices": {}}, scopes) is None
    run = types.SimpleNamespace(trace=False, facts={}, cell={"name": CELL},
                                cache_dir="/nonexistent")
    for name in OWN:
        assert _load("layer_metrics", name + ".py").read(run) is None


# -- the readers ----------------------------------------------------------------

def _run(config, traffic, times):
    model = _load("models", "nemotron_3_super.py")
    notes = []
    run = types.SimpleNamespace(
        trace=True, cell={"name": CELL, "chips": 1}, config=config,
        facts={"scope_time_ssm": times, "scope_time": times,
               "device": {"kind": "TPU v5 lite"},
               "ops": model.ops_and_bytes(config, traffic)},
        note=lambda **kw: notes.append(kw))
    return run, notes


def test_scope_readers_read_their_scope(config, traffic):
    times = {"ssm": 150.0, "ssm/scan": 60.0, "ssm/conv": 5.0,
             "moe/latent": 30.0, scope_time_ssm.KERNELS: 0.0}
    run, _ = _run(config, traffic, times)
    for name, want in (("ssm_ms", 150.0), ("ssm_scan_ms", 60.0),
                       ("moe_latent_ms", 30.0),
                       ("ssm_scan_kernel_share", 0.0)):
        assert _load("layer_metrics", name + ".py").read(run) == want
    run, _ = _run(config, traffic, dict(times,
                                        **{scope_time_ssm.KERNELS: 45.0}))
    assert _load("layer_metrics", "ssm_scan_kernel_share.py").read(run) \
        == 75.0


def test_the_scans_roofline_counts_required_work_over_the_scopes_time(
        config, traffic):
    peak = peaks.peaks_for("TPU v5 lite")
    run, notes = _run(config, traffic, {"ssm/scan": 40.0,
                                        "moe/experts": 20.0})
    detail = run.facts["ops"]["detail"]
    tokens = traffic["batch"] * traffic["seq"]
    assert detail["forward"]["ssm_scan"] == 5 * tokens * 2 * (
        128 * 128 + 128 * 64 * 16 + 2 * 128 * 64 * 16)
    assert detail["ssm_scan_bytes"] == 5 * tokens * 2 * (3 * 1024 + 256 + 16)
    compute = detail["forward"]["ssm_scan"] / peak["bf16_flops_per_s"]
    memory = detail["ssm_scan_bytes"] / peak["hbm_bytes_per_s"]
    assert memory > compute             # the scan is bound by its bytes
    got = _load("layer_metrics", "ssm_scan_roofline.py").read(run)
    assert got == pytest.approx(100.0 * 1e3 * 3 * memory / 40.0)
    assert notes[-1]["ssm_scan_bound"] == "memory"
    assert 0 < got < 100
    experts = _load("layer_metrics", "moe_experts_roofline.py").read(run)
    assert 0 < experts < 100
