"""Whole train steps compiled ahead of time for one chip of a described
TPU v5e:2x2, without the chip (benchmark/tools/aot_check.py): the cells
whose state fills most of the chip.  Each
cell's step is built and compiled once a run of this file, and every
test of that cell reads the one compile; nothing runs.  A file of its
own so that these compiles, the longest of tier-1, run beside the kernel
cases of test_aot_compile.py and not after them."""
import contextlib
import importlib.util
import io
import json
import os
import re

import pytest

import jax

from described_v5e import topo                    # noqa: F401 (fixture)
from mxnet_tpu.ops import attention as att

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEMOTRON = "nemotron3super-train-s8192-ep64tp8share"
LAGUNA = "lagunaxs2-train-s8192-ep8share"


@pytest.fixture(scope="module")
def step(topo):                                   # noqa: F811
    """step(cell) -> (what the tool printed, the step's compiled text,
    the `rotary_calls` counters by path before the compile and after it):
    the cell's step compiled for the described chip, once a module."""
    from benchmark.run import Run
    from mxnet_tpu import telemetry
    spec = importlib.util.spec_from_file_location(
        "_aot_check", os.path.join(REPO, "benchmark", "tools",
                                   "aot_check.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    load = Run.model

    def model(run):
        """The cell's model file without its set-up passes over the net
        (`balance_routers` moves parameter VALUES; here is no device to
        run a forward on, and the program does not depend on them)."""
        module = load(run)
        module.balance_routers = lambda *args: None
        return module

    texts = []
    as_text = jax.stages.Compiled.as_text

    def noted(self, *args, **kwargs):
        texts.append(as_text(self, *args, **kwargs))
        return texts[-1]

    def calls():
        return {path: telemetry.registry.value("rotary_calls",
                                               {"path": path})
                for path in ("kernel", "composition")}

    compiled = {}

    def of(cell):
        if cell not in compiled:
            before, printed = calls(), io.StringIO()
            with contextlib.redirect_stdout(printed):
                assert tool.main(["--workload", cell]) == 0
            printed = printed.getvalue()
            compiled[cell] = (json.loads(printed[printed.index("{"):]),
                              texts[-1], before, calls())
        return compiled[cell]

    with pytest.MonkeyPatch.context() as patch:
        # the code asks jax.default_backend(), sees the CPU here and would
        # take interpret mode: steer it in the test, not through an option
        patch.setattr(att, "_on_tpu", lambda: True)
        patch.setattr(Run, "model", model)
        patch.setattr(jax.stages.Compiled, "as_text", noted)
        yield of


def test_the_nemotron_step_fits_the_described_chip(step):
    """The whole train step of the cell
    `nemotron3super-train-s8192-ep64tp8share` - 607.0 M parameters built
    on the host, the step's program lowered from shapes - compiles for one
    chip of the described v5e:2x2: its arguments (weights, masters,
    AdamW's moments) and its temporaries together stay under the 15.0 GB
    allowed of the chip's 16, and both attention layers run the
    grouped-KV flash kernels (forward + one backward kernel each, kept
    across the recomputation)."""
    got, _, _, _ = step(NEMOTRON)
    assert 8.4 < got["argument_gb"] < 8.6
    assert got["arguments_plus_temp_gb"] < 15.0
    assert got["tpu_custom_calls"] > 0
    # twice as many rows do not fit: what the cell's batch of 1 is for
    assert got["batch"] == 1


def test_the_laguna_step_fits_the_described_chip(step):
    """The whole train step of the cell `lagunaxs2-train-s8192-ep8share` -
    691.6 M parameters built on the host, the step's program lowered from
    shapes - compiles for one chip of the described v5e:2x2: arguments
    (weights, masters, AdamW's moments) 9.68 GB and temporaries together
    under the 15.0 GB allowed of the chip's 16 (read: 12.77), all
    five attention layers in the flash kernels - three of them windowed -
    kept across the recomputation."""
    got, _, _, _ = step(LAGUNA)
    assert 9.6 < got["argument_gb"] < 9.8
    assert got["arguments_plus_temp_gb"] < 15.0
    # 5 attention layers x (forward + one backward kernel) among them
    assert got["tpu_custom_calls"] >= 10
    assert got["batch"] == 1


def test_the_laguna_step_turns_q_and_k_on_the_packed_layout(step):
    """The same step's program text (the same compile): q and k of the
    five attention layers go through ops/rotary.py's kernel in the
    forward, in the recomputed forward and in the backward (30 custom
    calls), each under its layer's `rotary` scope - which is how
    benchmark/harness/scope_time_swa.py's note finds them - and none under
    `attention_core`, whose time `attention_kernel_share.swa` and
    `window_attention_roofline` read; no instruction under `rotary` is a
    float32 tensor of four axes, and no float32 tensor of a token's heads
    is copied into another layout (the composition's `f32[1,8192,64,128]`
    result and its relayout copy: PERF.md section 6.  The head
    gate's backward still multiplies at that shape inside its fusion:
    ROADMAP S17)."""
    _, text, before, calls = step(LAGUNA)
    # q and k of each kind of layer, traced once a shape in a process
    # (another test may have traced them): none by the composition
    assert calls["kernel"] >= 4
    assert calls["composition"] == before["composition"]
    lines = [line for line in text.splitlines() if " = " in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in lines if "tpu_custom_call" in line]
    turns = [name for name in names if "rotary_turn" in name]
    assert len(turns) == 5 * 2 * 3
    assert all("rotary" in name.split("/")
               and "attention_core" not in name.split("/")
               and ("attention_window" in name.split("/")
                    or "attention_full" in name.split("/"))
               for name in turns), turns
    assert sum("rematted_computation" in name for name in turns) == 10
    wide = re.compile(r"= f32\[1,8192,\d+,128\]")
    assert not [line for line in lines if wide.search(line)
                and "/rotary/" in line]
    assert not [line for line in lines if wide.search(line)
                and " copy(" in line]
