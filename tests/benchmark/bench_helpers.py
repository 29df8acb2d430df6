"""Shared by the benchmark's tests: where things are, and a temporary root
that holds a copy of ``benchmark/`` plus the rehearsal files."""
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearsal_root(tmp_path):
    """A root as ``run.py --root`` wants it: a copy of ``benchmark/``, the
    tiny configurations and traffic mixes of ``tests/benchmark/data/`` and
    their manifest.  Returns the root and its manifest."""
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    for name in os.listdir(DATA):
        if name.startswith("tiny_"):
            shutil.copy(os.path.join(DATA, name),
                        os.path.join(root, "benchmark", "configs", name))
        elif name.startswith("tiny-"):
            shutil.copy(os.path.join(DATA, name),
                        os.path.join(root, "benchmark", "traffic", name))
    with open(os.path.join(DATA, "rehearsal_manifest.json")) as f:
        m = json.load(f)
    write_manifest(root, m)
    return root, m


def write_manifest(root, m):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)


def last_line(text):
    return json.loads(text.strip().splitlines()[-1])
