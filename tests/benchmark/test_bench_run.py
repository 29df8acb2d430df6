"""run.py end to end on the host: a rehearsal configuration through
train_steps, a configuration, a traffic mix and a per-layer metric added
as NEW files only, and the refusals."""
import hashlib
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import REPO, last_line, rehearsal_root
from benchmark import run as bench_run


def _hashes(root):
    out = {}
    for where, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
        for f in files:
            path = os.path.join(where, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


def test_rehearsal_train_prints_the_contracts_last_line(tmp_path, capsys):
    root, _ = rehearsal_root(tmp_path)
    rc = bench_run.main(["--root", root, "--workload", "tiny-bert-train",
                         "--seed", "3", "--seconds", "1", "--trace", "0"])
    assert rc == 0
    line = last_line(capsys.readouterr().out)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 20 and line["attempted"] % 10 == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert line["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]


def test_config_traffic_and_metric_added_as_new_files_only(tmp_path, capsys):
    """What a later PR does: new files, new manifest entries, no edit."""
    root, m = rehearsal_root(tmp_path)
    before = _hashes(os.path.join(root, "benchmark"))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "tiny_bert.json")) as f:
        config = json.load(f)
    config.update(name="added_bert", num_hidden_layers=1, hidden_size=16,
                  intermediate_size=64, num_attention_heads=2)
    config["optimizer"]["learning_rate"] = 0.05
    with open(os.path.join(bench, "configs", "added_bert.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "tiny-mlm.json")) as f:
        traffic = json.load(f)
    traffic.update(batch=4, seq=8, sync_every=5)
    with open(os.path.join(bench, "traffic", "added-mlm.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "layer_metrics", "steps_per_sync.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return run.traffic['sync_every']\n")
    with open(os.path.join(bench, "layer_metrics", "never_there.py"),
              "w") as f:
        f.write("def read(run):\n    return None\n")
    m["configs"].append({"name": "added_bert", "source": "toy", "reduced": [],
                         "file": "benchmark/configs/added_bert.json",
                         "why": "added by files"})
    m["workloads"].append({"name": "added-cell", "config": "added_bert",
                           "traffic": "added-mlm", "chips": 1, "why": "x"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "tiny-bert-train" in e.get("workloads", ()):
            e["workloads"].append("added-cell")
    for name in ("steps_per_sync", "never_there", "steps_per_sync.again"):
        m["per_layer"].append({
            "name": name, "unit": "steps", "better": "higher",
            "source": "host_clock", "layer": "Step compiler",
            "moves": "train_tokens_per_s", "workloads": ["added-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    rc = bench_run.main(["--root", root, "--workload", "added-cell",
                         "--seed", "1", "--seconds", "1", "--trace", "1"])
    assert rc == 0
    line = last_line(capsys.readouterr().out)
    assert line["correct"] is True, line.get("reasons")
    got = line["metrics"]
    assert got["steps_per_sync"] == {"value": 5.0, "unit": "steps"}
    assert got["steps_per_sync.again"]["value"] == 5.0   # <reader>.<variant>
    assert "never_there" not in got          # nothing to read: left out
    assert {"build_s", "step_call_ms", "data_wait_ms",
            "compiles_in_window"} <= set(got)
    assert got["compiles_in_window"]["value"] == 0
    assert "train_tokens_per_s" not in got   # traced: per-layer only
    assert line["attempted"] % 5 == 0
    after = _hashes(os.path.join(root, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/added_bert.json", "traffic/added-mlm.json",
        "layer_metrics/steps_per_sync.py", "layer_metrics/never_there.py"}


def _run_py(args, env_extra, cwd=REPO, timeout=120):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py")] + args,
        env=env, cwd=cwd, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell", ["bert-base-train-s512",
                                  "resnet50-train-b256"])
def test_a_real_configuration_without_a_chip_exits_nonzero(cell):
    # under the harness's pin a real configuration is refused outright
    out = _run_py(["--workload", cell, "--seed", "0", "--seconds", "1"],
                  {"MX_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout
    assert "runs on the chip only" in out.stderr


def test_no_accelerator_exits_nonzero_and_prints_no_result(tmp_path):
    # no pin, and jax finds only the host: the device check refuses before
    # anything is built
    out = _run_py(["--workload", "bert-base-train-s512", "--seed", "0",
                   "--seconds", "1"],
                  {"MX_FORCE_CPU": "", "JAX_PLATFORMS": "cpu",
                   "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla")})
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_unknown_cell_and_too_few_chips(tmp_path):
    out = _run_py(["--workload", "no-such-cell"], {"MX_FORCE_CPU": "1"})
    assert out.returncode != 0 and "no cell" in out.stderr
    root, m = rehearsal_root(tmp_path)
    m["workloads"][0]["chips"] = 4
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    flags = "--xla_force_host_platform_device_count=2"
    out = _run_py(["--root", root, "--workload", "tiny-bert-train",
                   "--seconds", "1"],
                  {"MX_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                   "XLA_FLAGS": flags})
    assert out.returncode != 0 and '"metrics"' not in out.stdout
    assert "asks for 4 chip(s), jax found 2" in out.stderr


def test_unknown_device_kind_is_refused(monkeypatch):
    from benchmark.harness import device

    class Dev:
        platform = "tpu"
        device_kind = "TPU v9"

    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    monkeypatch.delenv("MX_FORCE_CPU")
    with pytest.raises(SystemExit, match="TPU v9"):
        device.require(1)


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: the system under test is missing, so the run ends non-zero
    and prints no result."""
    root, _ = rehearsal_root(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "tiny-bert-train", "--seconds", "1"],
        env=dict(env, MX_FORCE_CPU="1", JAX_PLATFORMS="cpu"), cwd=root,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout
    assert "mxnet_tpu" in out.stderr
