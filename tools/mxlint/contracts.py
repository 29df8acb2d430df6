"""Program-contract verifier: device-free donation/HBM/retrace proofs
(ISSUE 11 tentpole).

The AST lanes prove what the *source* cannot do; this lane proves what
the *compiled executables* will do — without a TPU.  Every contracted
jit site (see ``mxnet_tpu.programs.declare_contract``; ``step.py``, the
serve bucket table, the fused optimizer kernels, the quantization wire
kernels and the kvstore exchange bodies all declare) is lowered with
abstract ``jax.ShapeDtypeStruct`` inputs under ``JAX_PLATFORMS=cpu``
via ``jit(fn).lower(*abstract).compile()`` and three theorems are
checked:

* **donation-aliasing** — every leaf the contract declares donated
  actually appears in the executable's input→output aliasing
  (``input_output_alias`` of the compiled module).  XLA silently DROPS a
  donation whose shape/dtype matches no output; CPU never exercises
  donation at runtime, so the first symptom used to be doubled HBM on
  TPU.  jax's "Some donated buffers were not usable" lowering warning,
  or the donor XLA left unplaced, is attached to the finding.
  Donated-but-*unused* args
  (jax prunes them; e.g. the bf16 weights of an mp Adam apply, whose
  new values derive from the fp32 masters) are counted separately and
  NOTED, not flagged — a pruned donation is a no-op, not a leak.
* **hbm-budget** — the compiled ``memory_analysis`` temp bytes fit the
  contract's declared ``temp_budget_bytes``: the static HBM-creep
  gate.  Budget bumps are reviewed like baseline entries —
  docs/TESTING.md §5.
* **trace-closure** — for contracts with a closure spec, every
  reachable workload point (each admissible serve batch size, each
  configured step window) resolves to a trace signature inside the
  declared case set; a miss is rendered through the PR-10 retrace
  explainer diff so the offending arg is named.  "Zero serve-time
  retraces" becomes a theorem instead of a bench observation.

Exit contract matches the AST lane: 0 clean, 1 findings, 2 internal
error.  ``--format json`` emits the machine schema
(``contract_schema``); ``--write-manifest`` refreshes the checked-in
``tools/mxlint/contracts.json`` (validated, without importing jax, by
``python -m tools.mxlint --check-manifest``).
"""
from __future__ import annotations

import json
import os
import re
import sys
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

from .core import Diagnostic

RULE_DONATION = "contract-donation-dropped"
RULE_BUDGET = "contract-hbm-budget"
RULE_CLOSURE = "contract-trace-closure"
RULE_ERROR = "contract-error"

DEFAULT_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "contracts.json")

# modules whose import declares the shipped tree's contracts (lazy
# builders; importing costs dict inserts, not traces)
DECLARING_MODULES = (
    "mxnet_tpu.step",
    "mxnet_tpu.serve.servable",
    "mxnet_tpu.ops.optimizer",
    "mxnet_tpu.ops.quantization",
    "mxnet_tpu.kvstore.kvstore",
)

# The witness is the COMPILED module's header, not the lowered text:
# jax (0.9) hands XLA every donation it cannot pair itself — sharded
# inputs, and any donated leaf with an output of the same element COUNT
# whatever its dtype — as `jax.buffer_donor`, without a warning.  What
# XLA then aliased is in the executable's `input_output_alias={...}`;
# a donor it found no output for stays behind in `buffer_donor={...}`.
_ALIASED_RE = re.compile(r"(?:may|must)-alias\)")
_LEFT_DONOR_RE = re.compile(r"\(\d+, \{[\d, ]*\}\)")
_DROP_WARNING = "donated buffers were not usable"


def _header_section(header: str, key: str) -> str:
    """The text of ``key={...}`` in an HloModule header line, up to the
    next attribute's ``=`` ('' when absent)."""
    return header.partition(key + "={")[2].partition("=")[0]


def _compiled_aliasing(compiled) -> Tuple[int, int]:
    """(donations XLA aliased into an output, donors it could not
    place) of one executable."""
    header = next(line for line in compiled.as_text().splitlines()
                  if line.startswith("HloModule"))
    return (len(_ALIASED_RE.findall(
                _header_section(header, "input_output_alias"))),
            len(_LEFT_DONOR_RE.findall(
                _header_section(header, "buffer_donor"))))


def _ensure_device_free():
    """The proofs must not depend on (or grab) an accelerator: force the
    CPU backend unless the operator explicitly chose a platform.  The
    sharded step contracts (ISSUE 14) lower over {dp, dp×fsdp,
    dp×fsdp×tp} meshes, so the CPU backend is faked out to 8 devices —
    the same flag tests/conftest.py sets — when the operator has not
    already pinned a device count."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()


def load_contracts(extra_modules: Tuple[str, ...] = ()):
    """Import the declaring modules and return the registered contracts."""
    _ensure_device_free()
    import importlib
    for mod in tuple(DECLARING_MODULES) + tuple(extra_modules):
        importlib.import_module(mod)
    from mxnet_tpu import programs
    return programs.contracts()


def _rel(path: Optional[str], root: str) -> str:
    if not path:
        return "<contracts>"
    try:
        rel = os.path.relpath(os.path.abspath(path), root)
    except ValueError:
        return path.replace(os.sep, "/")
    return rel.replace(os.sep, "/")


def _origin(contract, root: str) -> Tuple[str, int]:
    if contract.origin:
        return _rel(contract.origin[0], root), int(contract.origin[1])
    return "<contracts>", 1


class CaseResult:
    """One lowered case's measured facts (one row of the budget table)."""

    __slots__ = ("contract", "program", "label", "donated_expected",
                 "aliased", "pruned", "dropped", "temp_bytes", "memory",
                 "budget", "compile_seconds")

    def __init__(self, contract: str, program: str, label: str):
        self.contract = contract
        self.program = program
        self.label = label
        self.donated_expected = 0
        self.aliased = 0
        self.pruned = 0
        self.dropped = 0
        self.temp_bytes: Optional[int] = None
        self.memory: Optional[Dict[str, int]] = None
        self.budget: Optional[int] = None
        self.compile_seconds = 0.0

    def to_json(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}


def _donated_leaves(case, donate_argnums) -> int:
    import jax
    return sum(len(jax.tree_util.tree_leaves(case.args[i]))
               for i in donate_argnums if i < len(case.args))


def _verify_case(contract, case, root: str):
    """Lower+compile one case; returns (CaseResult, [Diagnostic])."""
    import jax
    path, line = _origin(contract, root)
    res = CaseResult(contract.name, case.program, case.label)
    res.budget = contract.temp_budget_bytes
    diags: List[Diagnostic] = []
    res.donated_expected = _donated_leaves(case, contract.donate_argnums)

    # declaration/spec cross-check: the alias/prune arithmetic below is
    # only sound when the jit site donates EXACTLY what the contract
    # declares — an undeclared jit donation could otherwise alias and
    # mask a pruned declared one.  Program wrappers expose their jit
    # kwargs; fn-cases carry theirs on the case.
    jit_kw = getattr(case.target, "jit_kw", None) \
        if case.target is not None else case.jit_kw
    if isinstance(jit_kw, dict):
        spec = tuple(sorted(int(i) for i in
                            (jit_kw.get("donate_argnums") or ())))
        if spec != contract.donate_argnums:
            diags.append(Diagnostic(
                RULE_DONATION, path, line, 0,
                "program %r (case %s): the jit site donates argnums %r "
                "but the contract declares %r — align them (the "
                "aliasing proof cannot attribute aliases across a "
                "mismatched spec)"
                % (case.program, case.label, spec,
                   contract.donate_argnums),
                snippet="contract %s" % contract.name))

    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        compiled = case.lower().compile()
    res.compile_seconds = time.perf_counter() - t0

    drop_msgs = [str(w.message) for w in rec
                 if _DROP_WARNING in str(w.message)]
    res.aliased, left_donors = _compiled_aliasing(compiled)
    missing = max(0, res.donated_expected - res.aliased)
    if drop_msgs or left_donors:
        # a LIVE donated buffer matched no output (jax warned at
        # lowering, or XLA left the donor unplaced): the TPU would
        # carry both generations of it.  Count the dropped buffers from
        # those witnesses, not from expected-aliased: an alias from a
        # jit-spec donation the contract does not declare could mask
        # the subtraction to zero while the drop is real.
        warned = sum(m.count("ShapedArray") for m in drop_msgs)
        res.dropped = max(warned + left_donors, 1)
        if not drop_msgs:
            drop_msgs = ["XLA placed %d donated buffer(s) in no output "
                         "(same element count, other byte size?)"
                         % left_donors]
        diags.append(Diagnostic(
            RULE_DONATION, path, line, 0,
            "program %r (case %s): %d of %d declared donations dropped "
            "— %s; on TPU the undonated buffer stays live "
            "next to its replacement (CPU hides this).  Make the donated "
            "leaf's shape+dtype match an output, or shrink the declared "
            "donate_argnums" % (case.program, case.label, res.dropped,
                                res.donated_expected,
                                "; ".join(drop_msgs)[:300]),
            snippet="contract %s" % contract.name))
    else:
        # no dropped donation: any shortfall is donated-but-unused args
        # jax pruned from the computation — a no-op donation, noted in
        # the table, not a finding
        res.pruned = missing

    mem = _memory_dict(compiled)
    if mem is not None:
        res.memory = mem
        res.temp_bytes = mem.get("temp_bytes")
    budget = contract.temp_budget_bytes
    if budget is not None and res.temp_bytes is not None and \
            res.temp_bytes > budget:
        diags.append(Diagnostic(
            RULE_BUDGET, path, line, 0,
            "program %r (case %s): compiled temp footprint %d bytes "
            "exceeds the contract's %d-byte budget — HBM creep; shrink "
            "the program or bump the budget WITH review (docs/TESTING.md "
            "§5 budget-bump policy)"
            % (case.program, case.label, res.temp_bytes, budget),
            snippet="contract %s" % contract.name))
    return res, diags


def _memory_dict(compiled) -> Optional[Dict[str, int]]:
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    except Exception:
        return None


def _verify_closure(contract, cases, root: str) -> List[Diagnostic]:
    """Prove the declared workload points' signatures all land in the
    compiled case set; render misses through the retrace explainer."""
    from mxnet_tpu import programs
    closure = contract.closure
    if callable(closure) and not isinstance(closure,
                                            programs.ContractClosure):
        closure = closure()
    if closure is None:
        return []
    path, line = _origin(contract, root)
    case_sigs = {}
    for case in cases:
        case_sigs[programs.signature_of(tuple(case.args),
                                        case.kwargs)] = case
    diags: List[Diagnostic] = []
    for point in closure.points:
        args = closure.resolve(point)
        if args is None:
            continue        # provably rejected before any jit
        sig = programs.signature_of(tuple(args), {})
        if sig in case_sigs:
            continue
        # nearest declared case (same tree structure first) for the
        # explainer diff, so the offending arg is NAMED
        near = None
        for csig, case in case_sigs.items():
            if csig[0] == sig[0]:
                near = (csig, case)
                break
        if near is None and case_sigs:
            near = next(iter(case_sigs.items()))
        detail = ""
        if near is not None:
            diff = programs.diff_signatures(near[0], sig)
            if diff is not None:
                detail = " vs case %s: %s" % (
                    near[1].label, programs._format_diff(diff))
        diags.append(Diagnostic(
            RULE_CLOSURE, path, line, 0,
            "contract %r: workload point %r dispatches a trace "
            "signature OUTSIDE the declared case set (a run-time "
            "retrace the zero-retrace proof does not cover)%s"
            % (contract.name, point, detail),
            snippet="contract %s" % contract.name))
    return diags


def verify(contract_names: Optional[List[str]] = None,
           root: Optional[str] = None):
    """Run the whole lane.  Returns (diags, results, verified_names)."""
    root = root or os.getcwd()
    contracts = load_contracts()
    if contract_names:
        wanted = set(contract_names)
        contracts = [c for c in contracts if c.name in wanted]
    diags: List[Diagnostic] = []
    results: List[CaseResult] = []
    verified: List[str] = []
    for contract in contracts:
        path, line = _origin(contract, root)
        try:
            cases = contract.build()
        except Exception as e:
            diags.append(Diagnostic(
                RULE_ERROR, path, line, 0,
                "contract %r failed to build its cases: %s: %s"
                % (contract.name, type(e).__name__, e),
                snippet="contract %s" % contract.name))
            continue
        built = []
        for case in cases:
            try:
                res, case_diags = _verify_case(contract, case, root)
            except Exception as e:
                diags.append(Diagnostic(
                    RULE_ERROR, path, line, 0,
                    "contract %r case %s failed to lower/compile: %s: %s"
                    % (contract.name, case.label, type(e).__name__, e),
                    snippet="contract %s" % contract.name))
                continue
            built.append(case)
            results.append(res)
            diags.extend(case_diags)
            if case.program not in verified:
                verified.append(case.program)
        try:
            diags.extend(_verify_closure(contract, built, root))
        except Exception as e:
            diags.append(Diagnostic(
                RULE_ERROR, path, line, 0,
                "contract %r closure check failed: %s: %s"
                % (contract.name, type(e).__name__, e),
                snippet="contract %s" % contract.name))
    return diags, results, verified


def _fmt_bytes(n: Optional[int]) -> str:
    if n is None:
        return "-"
    return "{:,}".format(n)


def budget_table(results: List[CaseResult]) -> str:
    """The per-program budget table tools/lint.sh prints."""
    header = ("program", "case", "donated", "aliased", "pruned",
              "temp_bytes", "budget", "compile_s")
    rows = [header]
    for r in results:
        rows.append((r.program, r.label,
                     str(r.donated_expected), str(r.aliased),
                     str(r.pruned), _fmt_bytes(r.temp_bytes),
                     _fmt_bytes(r.budget), "%.2f" % r.compile_seconds))
    widths = [max(len(row[i]) for row in rows)
              for i in range(len(header))]
    lines = []
    for i, row in enumerate(rows):
        lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     .rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def manifest(results: List[CaseResult]) -> Dict[str, Any]:
    """The contract-manifest document: declared contracts + this run's
    measured table.  ``schema`` is programs.CONTRACT_SCHEMA — what
    :func:`check_contract_manifest` validates.  Each program keeps EVERY
    measured case (optimizer.fused_adam has both the plain and the mp
    lowering) — a flat {program: row} map would silently drop all but
    the last."""
    from mxnet_tpu import programs
    doc = programs.contract_manifest()
    rows: Dict[str, Any] = {}
    for r in results:
        slot = rows.setdefault(r.program, {"program": r.program,
                                           "contract": r.contract,
                                           "cases": []})
        slot["cases"].append(r.to_json())
    doc["programs"] = rows
    return doc


def run_cli(fmt: str = "text",
            write_manifest: Optional[str] = None,
            contract_names: Optional[List[str]] = None) -> int:
    _ensure_device_free()
    root = os.getcwd()
    if write_manifest and contract_names:
        # a narrowed run sees only a slice of the programs; writing it
        # out would silently erase every other program's snapshot rows
        # (and still pass check_contract_manifest — it validates shape,
        # not coverage)
        print("mxlint --contracts: --write-manifest cannot be combined "
              "with --select (it would drop the unselected programs' "
              "rows)", file=sys.stderr)
        return 2
    try:
        if contract_names:
            known = {c.name for c in load_contracts()}
            unknown = set(contract_names) - known
            if unknown:
                # a typo'd --select must read as a usage error, never
                # as "0 contracts, clean"
                print("mxlint --contracts: unknown contract(s): %s "
                      "(have %s)" % (", ".join(sorted(unknown)),
                                     ", ".join(sorted(known))),
                      file=sys.stderr)
                return 2
        diags, results, verified = verify(contract_names, root=root)
    except Exception as e:    # import errors etc: internal, never "clean"
        print("mxlint --contracts: internal error: %s: %s"
              % (type(e).__name__, e), file=sys.stderr)
        return 2
    doc = manifest(results)
    if write_manifest:
        with open(write_manifest, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print("mxlint --contracts: wrote manifest (%d programs) to %s"
              % (len(doc["programs"]), write_manifest))
    if fmt == "json":
        print(json.dumps({
            "contract_schema": doc["schema"],
            "violations": [d.to_json() for d in diags],
            "verified_programs": verified,
            "programs": doc["programs"],
        }, indent=1, sort_keys=True))
    else:
        for d in diags:
            print("%s:%d:%d: %s: %s" % (d.path, d.line, d.col, d.rule,
                                        d.message))
        print(budget_table(results))
        print("mxlint --contracts: %d program%s verified device-free, "
              "%d finding%s"
              % (len(verified), "" if len(verified) == 1 else "s",
                 len(diags), "" if len(diags) == 1 else "s"),
              file=sys.stderr)
    return 1 if diags else 0


# ---------------------------------------------------------------------------
# the checked-in manifest's shape (jax-free: python -m tools.mxlint
# --check-manifest, tools/lint.sh)
# ---------------------------------------------------------------------------

# must track mxnet_tpu.programs.CONTRACT_SCHEMA; pinned here so the check
# imports no jax (tests/test_contracts.py asserts the two agree)
CONTRACT_SCHEMA = 1
CONTRACT_FIELDS = ("name", "donate_argnums", "temp_budget_bytes")
# each program row carries a `cases` list (one entry per lowering —
# e.g. fused_adam's plain AND mp cases); every case needs these
CONTRACT_PROGRAM_FIELDS = ("program", "cases")
CONTRACT_CASE_FIELDS = ("program", "label", "donated_expected",
                        "aliased", "temp_bytes", "budget")


def check_contract_manifest(path) -> int:
    """Validate the checked-in program-contract manifest (absent is OK —
    the contracts lane may not have been run on this checkout)."""
    if not os.path.exists(path):
        return 0
    try:
        with open(path) as f:
            doc = json.load(f)
    except ValueError as e:
        print("mxlint: %s: unparseable contract manifest: %s"
              % (path, e), file=sys.stderr)
        return 1
    if not isinstance(doc, dict):
        print("mxlint: %s: contract manifest is not an object"
              % path, file=sys.stderr)
        return 1
    bad = []
    progs = doc.get("programs")
    if progs is not None and not isinstance(progs, dict):
        bad.append("'programs' is not an object")
        doc = dict(doc, programs={})
    if doc.get("schema") != CONTRACT_SCHEMA:
        bad.append("contract schema %r != expected %d (regenerate with "
                   "python -m tools.mxlint --contracts --write-manifest, "
                   "or bump CONTRACT_SCHEMA in both places)"
                   % (doc.get("schema"), CONTRACT_SCHEMA))
    declared = doc.get("contracts", [])
    if not isinstance(declared, list):
        bad.append("'contracts' is not a list")
        declared = []
    for entry in declared:
        if not isinstance(entry, dict):
            # type corruption must be a finding, not a TypeError
            bad.append("contract entry %r is not an object" % (entry,))
            continue
        for field in CONTRACT_FIELDS:
            if field not in entry:
                bad.append("contract entry %r missing field %r"
                           % (entry.get("name", "?"), field))
    for pname, row in (doc.get("programs") or {}).items():
        if not isinstance(row, dict):
            # type corruption must be a finding, not a TypeError
            bad.append("program row %r is not an object" % pname)
            continue
        for field in CONTRACT_PROGRAM_FIELDS:
            if field not in row:
                bad.append("program row %r missing field %r"
                           % (pname, field))
        cases = row.get("cases") or []
        if not isinstance(cases, list):
            bad.append("program %r 'cases' is not a list" % pname)
            cases = []
        for case in cases:
            if not isinstance(case, dict):
                bad.append("program %r has a non-object case" % pname)
                continue
            for field in CONTRACT_CASE_FIELDS:
                if field not in case:
                    bad.append("program %r case %r missing field %r"
                               % (pname, case.get("label", "?"), field))
    if bad:
        for why in bad:
            print("mxlint: %s: %s" % (path, why), file=sys.stderr)
        return 1
    print("mxlint: contract manifest OK (%d contracts, %d programs, "
          "schema %d)"
          % (len(doc.get("contracts", [])),
             len(doc.get("programs") or {}), CONTRACT_SCHEMA))
    return 0
