"""The four-chip cell's path on four of the host's forced devices: the
mesh from the traffic file, batches put with the layout's sharding by the
prefetcher, the state spread and the collectives that decide `correct`."""
from bench_helpers import last_line, rehearsal_root
from benchmark import run as bench_run


def test_rehearsal_fsdp4_cell(tmp_path, capsys):
    root, _ = rehearsal_root(tmp_path)
    rc = bench_run.main(["--root", root, "--workload",
                         "tiny-bert-train-fsdp4", "--seed", "3",
                         "--seconds", "1", "--trace", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    line = last_line(out)
    assert line["correct"] is True, line.get("reasons")
    assert line["device"]["count"] == 4 and line["failed"] == 0
    got = line["metrics"]
    # every array of this toy divides by four: a quarter each, and a little
    # for what is replicated
    assert 25.0 <= got["state_share_per_chip"]["value"] < 30.0
    assert got["compiles_in_window"]["value"] == 0
    # XLA:CPU's trace has no device plane: the trace metrics are left out
    assert "collective_ms" not in got and "device_idle" not in got
