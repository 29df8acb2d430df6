"""Program-contract verifier (ISSUE 11): device-free donation/HBM/
trace-closure proofs.

Layers, bottom-up:

  * the SHIPPED manifest: every declared contract builds, lowers and
    compiles under JAX_PLATFORMS=cpu, >= 15 registered programs verify
    with ZERO findings (contract findings are never baselined), and
    every declared donation is accounted (aliased + pruned == expected);
  * reinjection — the acceptance criterion verbatim: a dropped donation
    (dtype-mismatched donated leaf), a budget overrun (1-byte budget),
    and an unbucketed shape (closure point outside the case set) each
    trip the right finding class, the closure miss rendered through the
    retrace-explainer diff.  (The unhandled-wire-verb reinjection lives
    in tests/test_mxlint.py with the other AST-rule fixtures.);
  * the CLI (`python -m tools.mxlint --contracts`): exit contract,
    --format json schema, --select narrowing, and the manifest
    round-trip that `python -m tools.mxlint --check-manifest`
    validates without importing jax.
"""
import json
import os
import subprocess
import sys
import uuid

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from mxnet_tpu import programs                          # noqa: E402
from tools.mxlint import contracts as lane              # noqa: E402


def _name(tag):
    return "test.%s.%s" % (tag, uuid.uuid4().hex[:8])


def _shipped_names():
    """The shipped contract set: everything the declaring modules
    register, minus any test-declared 'test.*' contracts this process
    accumulated."""
    return [c.name for c in lane.load_contracts()
            if not c.name.startswith("test.")]


@pytest.fixture(scope="module")
def shipped():
    """One full run of the lane over the shipped tree (module-scoped:
    every lowering is cached by jax afterwards, so the per-test cost is
    paid once)."""
    diags, results, verified = lane.verify(_shipped_names(), root=REPO)
    return diags, results, verified


# ---------------------------------------------------------------------------
# the shipped tree proves clean
# ---------------------------------------------------------------------------

def test_shipped_contracts_verify_15_programs_zero_findings(shipped):
    diags, results, verified = shipped
    assert diags == [], "\n".join(map(repr, diags))
    assert len(set(verified)) >= 15, sorted(verified)
    # the headline surfaces are all in the proven set
    assert {"step.step", "step.window", "optimizer.fused_adam",
            "kvstore.exchange_int8"} <= set(verified)
    assert any(p.startswith("serve.demo.b") for p in verified)


def test_shipped_donations_fully_accounted(shipped):
    _diags, results, _verified = shipped
    donating = [r for r in results if r.donated_expected]
    assert donating, "no donating contract cases found"
    for r in donating:
        assert r.aliased + r.pruned == r.donated_expected, vars(r)
        assert r.dropped == 0, vars(r)
    # the step programs donate all six state groups with nothing pruned
    step_rows = [r for r in results if r.program.startswith("step.")]
    assert step_rows and all(r.pruned == 0 and r.aliased ==
                             r.donated_expected for r in step_rows)


def test_shipped_budgets_hold_with_headroom(shipped):
    _diags, results, _verified = shipped
    for r in results:
        if r.budget is not None and r.temp_bytes is not None:
            assert r.temp_bytes <= r.budget, vars(r)


def test_pruned_donation_noted_not_flagged(shipped):
    """The mp Adam/AdamW weights are donated but value-unused (the new
    weights derive from the fp32 masters): jax prunes them, the lane
    NOTES the no-op donation in the pruned column without flagging."""
    _diags, results, _verified = shipped
    mp_rows = [r for r in results if r.label.endswith("_mp")]
    assert mp_rows and all(r.pruned == 3 for r in mp_rows), \
        [vars(r) for r in mp_rows]


def test_contract_schema_constants_agree():
    assert lane.CONTRACT_SCHEMA == programs.CONTRACT_SCHEMA


# ---------------------------------------------------------------------------
# reinjection: each check trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("body, witness", [
    # same element count, other byte size: jax (0.9) hands the donor to
    # XLA without a warning and XLA places it in no output
    (lambda w, g: (w - g).astype(jnp.bfloat16), "in no output"),
    # no output of that size at all: jax warns at lowering
    (lambda w, g: (w - g)[:32], "not usable"),
], ids=["other_dtype", "other_size"])
def test_reinjected_dropped_donation_trips(body, witness):
    """A donated f32 buffer that no output can take over: XLA cannot
    alias it and the lane must flag it, whichever of jax and XLA gave up
    on it — this is the exact failure that doubles HBM on TPU while CPU
    stays green."""
    name = _name("drop")
    sds = jax.ShapeDtypeStruct((64,), jnp.float32)
    programs.declare_contract(
        name,
        lambda: [programs.ContractCase(name, (sds, sds), fn=body,
                                       jit_kw={"donate_argnums": (0,)})],
        donate_argnums=(0,))
    diags, results, _ = lane.verify([name], root=REPO)
    assert [d.rule for d in diags] == [lane.RULE_DONATION]
    assert "donations dropped" in diags[0].message
    assert witness in diags[0].message      # who dropped it rides along
    (r,) = results
    assert r.donated_expected == 1 and r.aliased == 0 and r.dropped == 1


def test_reinjected_budget_overrun_trips():
    """A 1-byte temp budget against a kernel with real scratch: the
    static HBM-creep gate fires with both numbers in the message."""
    from mxnet_tpu.ops import quantization as q
    import functools
    name = _name("budget")
    sds = jax.ShapeDtypeStruct((4096,), jnp.float32)
    programs.declare_contract(
        name,
        lambda: [programs.ContractCase(
            name, (sds, sds),
            fn=functools.partial(q._quantize_int8_kernel, block=256),
            jit_kw={"donate_argnums": (1,)})],
        donate_argnums=(1,), temp_budget_bytes=1)
    diags, results, _ = lane.verify([name], root=REPO)
    assert [d.rule for d in diags] == [lane.RULE_BUDGET]
    assert "1-byte budget" in diags[0].message
    (r,) = results
    assert r.temp_bytes and r.temp_bytes > 1


def test_reinjected_unbucketed_shape_trips_with_explainer_diff():
    """A closure point resolving to a shape outside the declared case
    set: the zero-retrace proof fails and the finding carries the
    retrace explainer's structured diff naming the offending arg."""
    name = _name("closure")

    def body(x):
        return x.sum()

    def args_for(n):
        return (jax.ShapeDtypeStruct((n, 16), jnp.float32),)

    closure = programs.ContractClosure(
        points=[4, 5],                      # 5 pads to... nothing: leak
        resolve=lambda n: args_for(n))
    programs.declare_contract(
        name,
        lambda: [programs.ContractCase(name, args_for(4), label="b4",
                                       fn=body, jit_kw={})],
        closure=closure)
    diags, _results, _ = lane.verify([name], root=REPO)
    assert [d.rule for d in diags] == [lane.RULE_CLOSURE]
    msg = diags[0].message
    assert "point 5" in msg and "retrace" in msg
    # the explainer diff names the changed leaf and both shapes
    assert "shape" in msg and "(5, 16)" in msg and "(4, 16)" in msg


def test_reinjected_declaration_spec_mismatch_trips():
    """A contract declaring fewer donations than the jit site actually
    donates: the aliasing arithmetic cannot attribute aliases across
    the mismatch, so the lane flags the divergence itself."""
    name = _name("mismatch")
    prog = programs.register_program(name, lambda w, s: (w + 1, s + 1),
                                     donate_argnums=(0, 1))
    sds = jax.ShapeDtypeStruct((16,), jnp.float32)
    programs.declare_contract(
        name,
        lambda: [programs.ContractCase(name, (sds, sds), target=prog)],
        donate_argnums=(0,))
    diags, _r, _v = lane.verify([name], root=REPO)
    assert any(d.rule == lane.RULE_DONATION and
               "mismatched spec" in d.message for d in diags), \
        "\n".join(map(repr, diags))


def test_step_window_closure_covers_configured_scan(monkeypatch):
    """The step contract's closure proves the CONFIGURED window set: an
    MX_STEP_SCAN outside the contracted windows fails statically
    instead of retracing at runtime."""
    from mxnet_tpu import step as step_mod
    step_mod._step_contract_built.cache_clear()
    monkeypatch.setenv("MX_STEP_SCAN", "7")
    try:
        diags, _r, _v = lane.verify(["step.train"], root=REPO)
    finally:
        step_mod._step_contract_built.cache_clear()
    closure_hits = [d for d in diags if d.rule == lane.RULE_CLOSURE]
    assert closure_hits and "point 7" in closure_hits[0].message
    # and the explainer diff names the reshaped batch leaves
    assert "(7, 8, 16)" in closure_hits[0].message


def test_broken_builder_is_a_finding_not_a_crash():
    name = _name("broken")

    def build():
        raise RuntimeError("model zoo offline")

    programs.declare_contract(name, build)
    diags, results, verified = lane.verify([name], root=REPO)
    assert [d.rule for d in diags] == [lane.RULE_ERROR]
    assert "model zoo offline" in diags[0].message
    assert results == [] and verified == []


# ---------------------------------------------------------------------------
# manifest + CLI
# ---------------------------------------------------------------------------

def _check_manifest_cli(path):
    """`python -m tools.mxlint --check-manifest` in a process that must
    not import jax (nor the runtime): (exit code, stderr)."""
    code = ("import sys\n"
            "from tools.mxlint.__main__ import main\n"
            "rc = main(['--check-manifest', sys.argv[1]])\n"
            "assert 'jax' not in sys.modules, 'imported jax'\n"
            "assert 'mxnet_tpu' not in sys.modules, 'imported mxnet_tpu'\n"
            "sys.exit(rc)\n")
    r = subprocess.run([sys.executable, "-c", code, path], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    return r.returncode, r.stderr


def test_manifest_roundtrip_and_check_manifest_validation(tmp_path,
                                                          shipped):
    _diags, results, _verified = shipped
    doc = lane.manifest(results)
    assert doc["schema"] == programs.CONTRACT_SCHEMA
    assert len(doc["programs"]) >= 15
    # multi-case programs keep EVERY lowering (the mp adam row must not
    # shadow the plain one)
    adam = doc["programs"]["optimizer.fused_adam"]
    assert sorted(c["label"] for c in adam["cases"]) == \
        ["adam", "adam_mp"]
    p = tmp_path / "contracts.json"
    p.write_text(json.dumps(doc))
    assert lane.check_contract_manifest(str(p)) == 0
    # schema drift fails
    bad = dict(doc, schema=99)
    p.write_text(json.dumps(bad))
    assert lane.check_contract_manifest(str(p)) == 1
    rc, err = _check_manifest_cli(str(p))
    assert rc == 1 and "contract schema 99" in err, err
    # a case row missing a required field fails
    bad = json.loads(json.dumps(doc))
    next(iter(bad["programs"].values()))["cases"][0].pop("aliased")
    p.write_text(json.dumps(bad))
    assert lane.check_contract_manifest(str(p)) == 1
    rc, err = _check_manifest_cli(str(p))
    assert rc == 1 and "missing field 'aliased'" in err, err
    # absent manifest is fine (fresh checkout before the first run)
    assert lane.check_contract_manifest(
        str(tmp_path / "absent.json")) == 0


def test_checked_in_manifest_is_valid():
    assert os.path.isfile(lane.DEFAULT_MANIFEST), \
        "tools/mxlint/contracts.json missing — run " \
        "python -m tools.mxlint --contracts --write-manifest"
    assert lane.check_contract_manifest(lane.DEFAULT_MANIFEST) == 0
    # the CLI tools/lint.sh calls: same verdict, and no jax in its process
    rc, err = _check_manifest_cli(lane.DEFAULT_MANIFEST)
    assert rc == 0, err


def test_budget_table_renders_every_case(shipped):
    _diags, results, _verified = shipped
    table = lane.budget_table(results)
    lines = table.splitlines()
    assert lines[0].startswith("program")
    for r in results:
        assert any(r.program in ln and r.label in ln for ln in lines)


@pytest.mark.slow
def test_cli_contracts_json_and_select():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--contracts",
         "--select", "quant.gradient_wire", "--format", "json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["contract_schema"] == programs.CONTRACT_SCHEMA
    assert doc["violations"] == []
    assert set(doc["verified_programs"]) == \
        {"quant.q8_256", "quant.rt8_256", "quant.q2"}
    # a typo'd --select is a usage error (2), never "clean" (0)
    out = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--contracts",
         "--select", "no.such.contract"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "unknown contract" in out.stderr
    # --select + --write-manifest is refused: a partial write would
    # silently drop the unselected programs' snapshot rows
    out = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--contracts",
         "--select", "quant.gradient_wire", "--write-manifest"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "cannot be combined" in out.stderr
