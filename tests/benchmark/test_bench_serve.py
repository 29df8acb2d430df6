"""The serve cell's path on the host: a real replica child (through
harness/replica_main.py), the open-loop driver, the counters it reads."""
import os
import subprocess
import sys

from bench_helpers import REPO, last_line, rehearsal_root

LIMIT_S = 120       # the child replica's own limit; a run takes ~15 s


def test_rehearsal_predict_cell_through_a_child_replica(tmp_path):
    root, _ = rehearsal_root(tmp_path)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--root", root, "--workload", "tiny-resnet-predict", "--seed", "2",
         "--seconds", "2", "--trace", "1"],
        env=dict(os.environ, PYTHONPATH=REPO), cwd=root,
        capture_output=True, text=True, timeout=LIMIT_S)
    assert out.returncode == 0, out.stderr[-2000:]
    line = last_line(out.stdout)
    # a loaded test machine makes the generator late; that sets `correct`
    # false by design and is the only reason accepted here
    if not line["correct"]:
        assert all("load generator ran late" in r for r in line["reasons"])
    assert line["failed"] == 0 and line["attempted"] > 50
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": 0}
    got = line["metrics"]
    assert {"build_s", "rejected_share", "front_residual_ms",
            "queue_wait_ms", "batch_rows_mean", "pad_share",
            "compiles_in_window.predict"} <= set(got)
    assert got["rejected_share"]["value"] == 0
    assert got["compiles_in_window.predict"]["value"] == 0
    assert 1.0 <= got["batch_rows_mean"]["value"] <= 16.0
    assert 0.0 <= got["pad_share"]["value"] < 100.0
    assert "serve: stopped" in out.stderr        # the replica exited on STOP
    # the artifact and its reference answers are kept by config and seed
    kept = os.path.join(root, "benchmark", ".cache", "artifacts",
                        "tiny_resnet-seed2")
    assert sorted(os.listdir(kept)) == ["model-0000.params",
                                        "model-symbol.json", "reference.npy"]


def test_tail_readers_need_a_thousand_requests():
    """predict_p99_ms and loadgen_late_ms are listed for no rehearsal cell
    (a 2 s window holds ~100 requests): read here from made-up facts."""
    import json
    import pytest
    from benchmark.run import Run
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    run = Run(REPO, m, m["workloads"][0], 0, 0, False)
    p99 = run.load("benchmark/end_to_end/predict_p99_ms.py")
    p50 = run.load("benchmark/end_to_end/predict_p50_ms.py")
    late = run.load("benchmark/layer_metrics/loadgen_late_ms.py")
    assert p99.read(run) is None and late.read(run) is None
    run.facts["latency_s"] = [i / 1e6 for i in range(1, 2001)]   # 1..2000 us
    run.facts["late_s"] = [0.0] * 1990 + [0.002] * 10
    assert p50.read(run) == pytest.approx(1.0)          # ms
    assert p99.read(run) == pytest.approx(1.98)
    assert late.read(run) == pytest.approx(0.0)          # 10 of 2000 = 0.5 %
    run.facts["latency_s"] = run.facts["latency_s"][:500]
    with pytest.raises(ValueError, match="ten are needed"):
        p99.read(run)
