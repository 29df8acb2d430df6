"""Ahead-of-time compiles for a described TPU v5e, without the chip.

The TPU's compiler is installed with jax and compiles for a device that is
described (`v5e:2x2`) and not attached, so the kernels of the main path
are held to what Mosaic and XLA:TPU accept at real widths in tier-1:
what interpret mode on the CPU cannot show (tiling, fast-memory limits).
Nothing runs — results and times come only from a chip run.  Whole-model
compiles (the ResNet-50 and BERT-base steps) take a quarter of a minute
and more and stay out of tier-1.  Also here: chip_smoke.py's phase
functions at tiny sizes on the CPU, and its refusal to pass without a TPU.

The file name sorts first on purpose: tier-1 is cut by the clock.
"""
import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402
from jax.sharding import SingleDeviceSharding     # noqa: E402

from mxnet_tpu import tpu_kernel                  # noqa: E402
from mxnet_tpu.ops import attention as att        # noqa: E402
from mxnet_tpu.serve.decode import DecodeConfig   # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture(scope="module")
def chip():
    """Sharding onto one chip of a described v5e:2x2; the persistent
    compile cache is off around these compiles (an entry written for a
    described device cannot be read back without one, and the retry
    warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here: nothing to compile with
        pytest.skip("cannot describe a v5e topology: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash(direction, shape, causal):
    def fwd(q, k, v):
        return att.attention_core(q, k, v, causal=causal)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return (fwd if direction == "fwd" else bwd), \
        [(shape, jnp.bfloat16)] * 3


def _decode_op(name):
    """The decode engine's attention ops at its default geometry."""
    cfg = DecodeConfig()
    B, H, D, T = cfg.slots, cfg.heads, cfg.head_dim, cfg.spec_k + 1
    f32, i32 = jnp.float32, jnp.int32
    flat = ((B, cfg.max_len, H, D), f32)
    heap = ((cfg.kv_pages, cfg.kv_page_len, H, D), f32)
    table = ((B, cfg.pages_per_slot), i32)
    return {
        "cached_attention": (
            att.cached_attention,
            [((B, H, D), f32), flat, flat, ((B,), i32)]),
        "cached_attention_multi": (
            att.cached_attention_multi,
            [((B, T, H, D), f32), flat, flat, ((B, T), i32)]),
        "paged_attention": (
            att.paged_attention,
            [((B, H, D), f32), heap, heap, table, ((B,), i32)]),
        "paged_attention_multi": (
            att.paged_attention_multi,
            [((B, T, H, D), f32), heap, heap, table, ((B, T), i32)]),
    }[name]


def _user_kernel():
    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    kernel = tpu_kernel.Kernel(body, name="axpb", interpret=False)
    return (lambda x: kernel._call_jax((256, 256), x)[0]), \
        [((256, 256), jnp.float32)]


# (id, builder of (fn, [(shape, dtype), ...]), tpu_custom_calls expected)
CASES = [
    ("flash-%s-%s-%s" % (d, "x".join(map(str, s)), "causal" if c else "full"),
     lambda d=d, s=s, c=c: _flash(d, s, c), n)
    for s in ((8, 8, 512, 128), (2, 8, 2048, 128))
    for c in (True, False)
    for d, n in (("fwd", 1), ("bwd", 3))
] + [
    # BERT-base's own shape: head dim 64 is not a multiple of 128, so the
    # dispatch rule gives it the jnp composition TODAY.  A change of the
    # rule must show here.
    ("bert-base-8x12x512x64-gets-xla",
     lambda: _flash("fwd", (8, 12, 512, 64), False), 0),
    ("user-kernel", _user_kernel, 1),
] + [
    (name, lambda name=name: _decode_op(name), 0)
    for name in ("cached_attention", "cached_attention_multi",
                 "paged_attention", "paged_attention_multi")
]


@pytest.mark.parametrize("build,custom_calls",
                         [pytest.param(b, n, id=i) for i, b, n in CASES])
def test_compiles_for_v5e(chip, monkeypatch, build, custom_calls):
    # the code asks jax.default_backend(), sees the CPU here and would
    # take interpret mode: steer it in the test, not through an option
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    fn, args = build()
    abstract = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                for shape, dtype in args]
    compiled = jax.jit(fn).lower(*abstract).compile()
    assert compiled.as_text().count("tpu_custom_call") == custom_calls


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

SMOKE_TINY = {
    "train_bert_base": dict(layers=1, units=32, heads=2, vocab=64, batch=4,
                            seq=16, lr=0.5),
    "eager": dict(rows=32, cols=8, hidden=16),
    "pallas": dict(shape=(1, 2, 256, 128)),
}


@pytest.mark.parametrize("phase", sorted(SMOKE_TINY))
def test_chip_smoke_phase_tiny_on_cpu(phase):
    """The phase functions run end to end at tiny sizes on the CPU; the
    platform they check for is steered from here."""
    import chip_smoke
    out = getattr(chip_smoke, phase)(platform="cpu", **SMOKE_TINY[phase])
    json.dumps(out)                       # each phase prints one JSON line
    if phase == "train_bert_base":
        assert out["compiles_in_steps"] == 0
        assert out["losses"][-1] < out["losses"][0]
        assert out["attention_impl"] == "xla"
    if phase == "pallas":
        assert out["tpu_custom_calls"] == {"forward": 0, "backward": 0}


def test_chip_smoke_refuses_cpu():
    """Without a TPU the script exits non-zero within seconds and prints
    no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MX_FORCE_CPU", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
