"""The one compile cache: jax's persistent compilation cache, armed and
counted by mxnet_tpu/compile_cache.py.

* a second process sharing ``JAX_COMPILATION_CACHE_DIR`` finds the first
  one's XLA compiles (``compile_cache.xla_hits`` > 0) for a CompiledStep
  and for a Servable's bucket table, and continues the exact trajectory
* ``tools/launch.py --compile-cache DIR`` exports that variable,
  absolute and created, to every rank
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import compile_cache, gluon, nd, programs

def step():
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"))
    net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    cstep = tr.make_compiled_step(net, gluon.loss.SoftmaxCrossEntropyLoss())
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 8).astype(np.float32))
    y = nd.array(rng.randint(0, 4, 8).astype(np.float32))
    out = [cstep.step(x, y).mean().asnumpy().item().hex() for _ in range(4)]
    assert cstep.compiled, cstep.fallback_reason
    return out

def servable():
    from mxnet_tpu.serve.demo import demo_block, demo_example
    from mxnet_tpu.serve.servable import BucketTable, Servable
    sv = Servable(demo_block(), name="demo-mlp", version=1,
                  buckets=BucketTable([1, 2, 4]))
    sv.warm(demo_example())
    x = np.random.RandomState(3).randn(2, 16).astype(np.float32)
    return np.asarray(sv.dispatch(2, [x])[0]).tobytes().hex()

result = {"step": step, "servable": servable}[sys.argv[1]]()
print(json.dumps({"result": result, "stats": compile_cache.stats(),
                  "summary": programs.program_summary()}))
"""


def _child(case, cache_dir):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _CHILD, case], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["step", "servable"])
def test_second_process_hits_jax_cache_and_matches(case, tmp_path):
    cache_dir = str(tmp_path / "xla")
    cold = _child(case, cache_dir)
    warm = _child(case, cache_dir)
    assert cold["stats"]["dir"] == warm["stats"]["dir"] == cache_dir
    assert cold["stats"]["xla_misses"] > 0      # it compiled, and wrote
    assert warm["stats"]["xla_hits"] > 0
    assert warm["stats"]["xla_hits"] >= cold["stats"]["xla_misses"]
    for key in ("cache_hits", "deserialize_seconds",
                "deserialize_seconds_total"):
        assert key not in warm["summary"]
    # a warm process still traces: the census counts the same builds
    assert warm["summary"]["compiles"] == cold["summary"]["compiles"]
    assert warm["result"] == cold["result"]     # to the last digit


def test_launch_compile_cache_exports_jax_dir_to_every_rank(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", "--compile-cache", "cc", "--",
         sys.executable, "-c",
         "import os; print('RANK_DIR', os.environ['MX_PROCESS_ID'], "
         "os.environ['JAX_COMPILATION_CACHE_DIR'], flush=True)"],
        capture_output=True, text=True, timeout=120,
        cwd=str(tmp_path))      # the flag takes a relative path too
    assert r.returncode == 0, (r.stdout, r.stderr)
    want = os.path.join(os.path.realpath(str(tmp_path)), "cc")
    assert os.path.isdir(want)
    seen = sorted(line.split()[1:] for line in r.stdout.splitlines()
                  if line.startswith("RANK_DIR"))
    assert seen == [["0", want], ["1", want]]
