"""The Laguna-XS.2 cell: its configuration against the published one, its
manifest entries (found by NAME, never by position), the rehearsal through
run.py, and the readers of its per-layer metrics on a hand-made trace."""
import importlib.util
import json
import os
import types

import numpy as np
import pytest

from bench_helpers import (DATA, REPO, last_line, manifest, rehearsal_root,
                           write_manifest)
from benchmark import run as bench_run
from benchmark.harness import peaks, scope_time, scope_time_swa

CELL = "lagunaxs2-train-s8192-ep8share"
GLM_CELL = "glm47flash-train-s4096-ep8share"
NEMOTRON_CELL = "nemotron3super-train-s8192-ep64tp8share"
DEVICE = "/device:TPU:0"
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"

# the catalog row's `config` (model-configs guide, Laguna-XS.2)
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# what may never be listed as reduced: the widths
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "num_experts_per_tok", "sliding_window", "num_key_value_heads",
          "num_attention_heads_per_layer")


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
    return _json("configs", "laguna_xs_2.json")


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
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    assert row["config"] == PUBLISHED and row["source_url"] == SOURCE


def test_the_cut_is_one_chips_share_of_8_and_states_its_deployment(config):
    assert config["num_experts"] == len(config["experts_held"]) == 32
    assert config["experts_held"] == list(range(32))
    assert config["num_experts_published"] == 256 == 8 * 32
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the leading dense layer and one whole period after it, 3 : 1
    held = config["layers_held"]
    assert held == list(range(config["num_hidden_layers"])) == [0, 1, 2, 3, 4]
    assert [config["layer_types"][l] for l in held] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert [config["mlp_layer_types"][l] for l in held] \
        == ["dense"] + ["sparse"] * 4
    assert [config["num_attention_heads_per_layer"][l] for l in held] \
        == [48, 64, 64, 64, 48]
    assert "8 chips share each layer" in config["deployment"]
    assert "expert parallel 8 x vocabulary parallel 8" in config["deployment"]
    for key in ("gating", "router", "attention_extras", "rotary_pair_layout",
                "sequence_length", "router_correction", "optimizer",
                "initializer", "document_mask", "dropout", "token_ids",
                "recomputation"):
        assert config["assumed"][key], key
    assert "a QUERY HEAD" in config["assumed"]["gating"]
    assert "SHARED expert" in config["assumed"]["gating"]     # the other
    assert "noaux_tc" in config["assumed"]["router"]
    assert "no q/k norm" in config["assumed"]["attention_extras"]
    assert "691.6 M parameters" in config["why"]
    assert "9.68 GB" in config["why"]
    assert config["optimizer"]["name"] == "adamw"
    assert config["optimizer"]["multi_precision"] is True
    assert 0 < config["check_tolerance"] <= 0.1
    assert config["check_tolerance_why"] and config["check_routing_gap"] > 0
    for reading in ("(a)", "(b)", "(c)", "(d)"):
        assert reading in config["check_tolerance_why"], reading


def test_the_model_file_counts_the_configurations_parameters(config,
                                                             traffic):
    model = _load("models", "laguna_xs_2.py")
    ops = model.ops_and_bytes(config, traffic)
    assert round(ops["n_params"] / 1e6, 1) == 691.6
    assert round(ops["bytes"] / 2 / 1e9, 2) == 9.68
    tokens = traffic["batch"] * traffic["seq"]
    assert ops["detail"]["expected_assignments_per_expert"] \
        == tokens * 8 / 256 == 256
    # the window cores: the pairs inside the band, by brute count
    t, w = traffic["seq"], config["sliding_window"]
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    assert model.band_pairs(t, w) == int(((j <= i) & (j > i - w)).sum())
    assert ops["detail"]["forward"]["attention_core_window"] \
        == 3 * 64 * 4 * 128 * model.band_pairs(t, w)


# -- the manifest: by name, never by position ---------------------------------

# accepted tests pin the cell lists of these readers' older entries (the
# Nemotron cell has to stand LAST in the eight it shares with the BERT
# cells), so the cell is served by entries of its own: `<reader>.swa`
TWINS = ("data_wait_ms", "step_call_ms", "compiles_in_window", "mfu",
         "step_roofline", "device_idle", "peak_hbm_gb",
         "moe_exact_buffer_share",
         "forward_ms", "backward_ms", "optimizer_ms", "scope_unattributed",
         "other_programs_ms", "step_prepare_ms", "step_dispatch_ms",
         "step_write_back_ms", "attention_ms", "attention_kernel_share",
         "moe_ms", "moe_experts_roofline", "moe_overhead_share",
         "expert_load_max_over_mean", "expert_assignments_here",
         "recompute_ms")
OWN = ("window_attention_ms", "full_attention_ms",
       "window_attention_roofline", "window_visited_over_needed")


def _entry(name):
    m = manifest()
    return next(e for e in m["end_to_end"] + m["per_layer"]
                if e["name"] == name)


def test_the_end_to_end_list_gains_the_cell_and_loses_none():
    """An end-to-end metric can have no twin: the cell's name is appended
    to `train_tokens_per_s`, the one list of an existing entry that
    changes; `setup_s` lists no cells."""
    cells = _entry("train_tokens_per_s")["workloads"]
    assert cells.count(CELL) == 1
    assert cells[:cells.index(CELL)] == [
        "bert-base-train-s512", "bert-base-train-s512-fsdp4", GLM_CELL,
        NEMOTRON_CELL]
    assert "workloads" not in _entry("setup_s")
    m = manifest()
    for e in m["per_layer"]:
        assert (CELL in e.get("workloads", ())) == (
            e["name"].endswith(".swa") or e["name"] in OWN), e["name"]


@pytest.mark.parametrize("name", TWINS)
def test_an_accepted_reader_serves_the_cell_through_its_swa_entry(name):
    entry, twin = _entry(name + ".swa"), _entry(name)
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
    assert entry["layer"] == "Kernels"
    assert entry["source"] == ("program_counter" if "visited" in name
                               else "device_trace")
    assert (entry["unit"] == "%") == name.endswith("roofline")
    assert entry["better"] == ("higher" if name.endswith("roofline")
                               else "lower")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert hasattr(_load("layer_metrics", name + ".py"), "read")


def test_the_configuration_and_the_cell_are_listed_once(traffic):
    m = manifest()
    entry, = [c for c in m["configs"] if c["name"] == "laguna_xs_2"]
    assert entry["file"] == "benchmark/configs/laguna_xs_2.json"
    assert entry["source"].startswith(SOURCE + " laguna, 40 layers")
    assert entry["reduced"] == REDUCED
    cell, = [c for c in m["workloads"] if c["name"] == CELL]
    assert cell == dict(cell, config="laguna_xs_2", chips=1)
    assert cell["traffic"] == "clm-s8192-b1-ep8"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(entry["source"]) <= 200
    assert (traffic["batch"], traffic["seq"], traffic["pool"],
            traffic["sync_every"], traffic["trace_seconds"], traffic["mesh"],
            traffic["driver"]) == (1, 8192, 8, 5, 6, None, "train_steps")
    assert traffic["why"] and traffic["who"]
    # one cell on four chips, as before; six cells, five configurations
    assert sum(c["chips"] == 4 for c in m["workloads"]) == 1
    assert len({c["name"] for c in m["workloads"]}) == len(m["workloads"])
    assert len({e["name"] for e in m["per_layer"]}) == len(m["per_layer"])
    # every metric that moves the cell's throughput and lists its cells
    # names the cell itself or has a twin that does
    for e in m["per_layer"]:
        if e["moves"] != "train_tokens_per_s" or CELL in e["workloads"]:
            continue
        base = e["name"].split(".")[0]
        named = [o for o in m["per_layer"] if o["name"].split(".")[0] == base
                 and CELL in o["workloads"]]
        only_elsewhere = base in (
            "collective_ms", "collective_exposed_ms", "state_share_per_chip",
            "attention_roofline", "mla_ms", "mla_core_roofline", "mtp_ms",
            "ssm_ms", "ssm_scan_ms", "ssm_scan_roofline",
            "ssm_scan_kernel_share", "moe_latent_ms")
        assert named or only_elsewhere, e["name"]


# -- the rehearsal through run.py ---------------------------------------------

def test_rehearsal_trains_the_new_model_on_the_host(tmp_path, capsys):
    """`rehearsal_root` copies `tiny_laguna.json` and `tiny-clm.json`
    beside the others; the cell's manifest is a file of its own."""
    root, _ = rehearsal_root(tmp_path)
    with open(os.path.join(DATA, "rehearsal_manifest_laguna.json")) as f:
        write_manifest(root, json.load(f))
    rc = bench_run.main(["--root", root, "--workload", "tiny-laguna-train",
                         "--seed", str(2 ** 31 + 7), "--seconds", "2",
                         "--trace", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    line = last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10 and line["attempted"] % 5 == 0
    metrics = line["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["moe_exact_buffer_share"]["value"] == 0
    # 4 of 16 experts held, top-4: 1/4 of the assignments at an even load
    assert 0 < metrics["expert_assignments_here"]["value"] < 60
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
    notes = [json.loads(l[len("benchmark: "):]) for l in out.splitlines()
             if l.startswith("benchmark: {")]
    if any("spans_ms" in n for n in notes):
        assert metrics["step_prepare_ms.swa"]["value"] > 0
    # XLA:CPU's trace has no device plane: the scope readers read nothing,
    # and heads of 16 lanes take the composition: no pair is counted
    for name in OWN + ("moe_ms.swa",):
        assert name not in metrics
    routing = next(n for n in notes if "routing_choices" in n)
    assert routing["routing_choices_differ_share"] == 0.0      # float32
    assert routing["routing_choices"] == 4 * 2 * 32
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


# -- scope_time_swa on a hand-made trace ----------------------------------------

WIN = "jit(mx_step_step)/jvp(forward)/Laguna/blocks/1/attention_window/%s"
FULL = "jit(mx_step_step)/jvp(forward)/Laguna/blocks/4/attention_full/%s"
BWD = "jit(mx_step_step)/transpose(jvp(forward))/Laguna/blocks/1/" \
    "jvp(forward)/Laguna/blocks/1/checkpoint/%s"
CORE = "jit(mx_op_multi_head_attention)/attention_core/pallas_call"


def _where(top, scope, mixed=False):
    return {"scope": scope, "top": top, "tops": [top], "mixed": mixed}


@pytest.fixture()
def handmade():
    """Two runs of a step on one device; durations in ns."""
    rows = [
        ("fusion.1", "forward", WIN % "q_proj/jit(mx_op_FullyConnected)/dot_general", 1000, "fusion"),
        ("fusion.2", "forward", WIN % "rotary/jit(mx_op_rotary_embedding)/mul", 100, "fusion"),
        ("custom-call.1", "forward", WIN % CORE, 300, "custom-call:tpu_custom_call"),
        ("fusion.3", "forward", WIN % "head_gate/g_proj/jit(mx_op_FullyConnected)/dot_general", 50, "fusion"),
        ("custom-call.2", "backward", BWD % ("attention_window/" + CORE), 700, "custom-call:tpu_custom_call"),
        ("fusion.4", "backward", BWD % "rematted_computation/attention_window/q_proj/jit(mx_op_FullyConnected)/dot_general", 900, "fusion"),
        ("fusion.5", "forward", FULL % "rotary/jit(mx_op_rotary_embedding)/mul", 120, "fusion"),
        ("custom-call.3", "forward", FULL % CORE, 2000, "custom-call:tpu_custom_call"),
        ("fusion.6", "forward", FULL % "head_gate/mul", 60, "fusion"),
        ("fusion.7", "forward", "jit(mx_step_step)/jvp(forward)/Laguna/blocks/1/moe/jit(mx_op_moe_token_choice)/experts/ragged_dot", 500, "fusion"),
        # a weight gradient fused with its optimizer update: mixed, left out
        ("fusion.8", "optimizer", BWD % "attention_window/o_proj/jit(mx_op_FullyConnected)/dot_general", 5000, "fusion"),
    ]
    ops, modules, instructions = [], [], {}
    for start in (0, 100000):
        modules.append(["jit_mx_step_step", start, 50000])
        at = start
        for name, top, scope, dur, group in rows:
            ops.append([name, group, at, dur])
            at += dur
            instructions[name] = _where(top, scope, mixed=name == "fusion.8")
    trace = {"devices": {DEVICE: {"ops": ops, "async": [],
                                  "modules": modules}}, "host": []}
    return trace, {"module": "jit_mx_step_step",
                   "instructions": instructions}


def test_the_new_scopes_are_read_beside_the_accepted_ones(handmade):
    assert not set(scope_time_swa.SCOPES) & set(scope_time.SCOPES)
    trace, scopes = handmade
    got = scope_time_swa.reduce(trace, scopes)
    ns = {k: None if v is None else round(v * 1e6) for k, v in got.items()
          if k != "mixed"}
    assert ns["attention_window"] == 1000 + 100 + 300 + 50 + 700 + 900
    assert ns["attention_full"] == 120 + 2000 + 60
    assert ns["attention_window/attention_core"] == 300 + 700
    assert ns["attention_full/attention_core"] == 2000
    assert ns["rotary"] == 100 + 120
    assert ns["head_gate"] == 50 + 60
    assert round(got["mixed"]["attention_window"] * 1e6) == 5000
    # the accepted reader on the same trace still reads its own scopes
    old = scope_time.per_step(trace, scopes)
    assert round(old["attention_core"] * 1e6) == 300 + 700 + 2000
    assert round(old["moe"] * 1e6) == 500


def test_a_parent_program_reads_nothing(handmade):
    trace, scopes = handmade
    nemotron = {"module": scopes["module"], "instructions": {
        name: _where(w["top"], "jit(mx_step_step)/jvp(forward)/NemotronH/"
                     "blocks/9/attention/attention_core/pallas_call")
        for name, w in scopes["instructions"].items()}}
    got = scope_time_swa.reduce(trace, nemotron)
    assert all(got[k] is None for k in scope_time_swa.SCOPES)
    assert scope_time_swa.reduce(trace, None) is None
    assert scope_time_swa.reduce({"devices": {}}, scopes) is None
    run = types.SimpleNamespace(trace=False, facts={}, cell={"name": CELL},
                                cache_dir="/nonexistent", note=lambda **k: 0)
    for name in OWN[:3]:
        assert _load("layer_metrics", name + ".py").read(run) is None


# -- the readers ----------------------------------------------------------------

def _run(config, traffic, times):
    model = _load("models", "laguna_xs_2.py")
    notes = []
    run = types.SimpleNamespace(
        trace=True, cell={"name": CELL, "chips": 1}, config=config,
        facts={"scope_time_swa": times, "scope_time": times,
               "device": {"kind": "TPU v5 lite"},
               "ops": model.ops_and_bytes(config, traffic)},
        note=lambda **kw: notes.append(kw))
    return run, notes


def test_scope_readers_read_their_scope(config, traffic):
    times = {"attention_window": 90.0, "attention_full": 70.0,
             "attention_window/attention_core": 25.0}
    run, _ = _run(config, traffic, times)
    for name, want in (("window_attention_ms", 90.0),
                       ("full_attention_ms", 70.0)):
        assert _load("layer_metrics", name + ".py").read(run) == want


def test_the_windows_roofline_counts_the_band_over_the_cores_time(
        config, traffic):
    peak = peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    run, notes = _run(config, traffic,
                      {"attention_window/attention_core": 25.0,
                       "moe/experts": 20.0})
    core = run.facts["ops"]["detail"]["forward"]["attention_core_window"]
    got = _load("layer_metrics", "window_attention_roofline.py").read(run)
    assert got == pytest.approx(100.0 * 1e3 * 3 * core / peak / 25.0)
    assert notes[-1]["window_core_ms"] == 25.0
    assert 0 < got < 100
    # the whole causal triangle in its place would read 8 x as much
    triangle = 3 * 64 * 4 * 128 * (8192 * 8193 // 2)
    assert 8 < triangle / core < 8.5
    run, _ = _run(config, traffic, {"attention_window/attention_core": None})
    assert _load("layer_metrics",
                 "window_attention_roofline.py").read(run) is None
    run, _ = _run(config, traffic, {"moe/experts": 20.0})
    experts = _load("layer_metrics", "moe_experts_roofline.py").read(run)
    assert 0 < experts < 100


def test_visited_over_needed_reads_the_windows_counters(config, traffic):
    from mxnet_tpu import telemetry
    reader = _load("layer_metrics", "window_visited_over_needed.py")
    kind = {"kind": "window"}
    needed = telemetry.registry.counter("attention_pairs_needed", "", kind)
    visited = telemetry.registry.counter("attention_pairs_visited", "", kind)
    was = needed.value, visited.value
    run, notes = _run(config, traffic, {})
    try:
        needed.set(0)
        visited.set(0)
        assert reader.read(run) is None         # no windowed call traced
        needed.set(4063488)
        visited.set(2 * 4063488)
        assert reader.read(run) == 2.0
        assert notes[-1]["attention_pairs"]["window"]["needed"] == 4063488
    finally:
        needed.set(was[0])
        visited.set(was[1])
