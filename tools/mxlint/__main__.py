"""``python -m tools.mxlint`` — CLI front end.

Exit-code contract (what tools/lint.sh and the tier-1 test key on):
  0  clean (every diagnostic suppressed or baselined)
  1  new violations
  2  usage / internal error
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from .core import (RULES, apply_baseline, lint_paths, load_baseline,
                   load_baseline_whys, repo_root_of, write_baseline)
from . import rules as _rules  # noqa: F401  (registers the rule set)
from . import project as _project  # noqa: F401  (concurrency rules)

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")


def _default_paths():
    """mxnet_tpu plus the supervisor and the operator-facing tools —
    the launcher is part of the threaded runtime the concurrency rules
    certify, telemetry_dump.py processes trace files (ISSUE 8), and
    fleet_top.py emits the FLEET wire verb the exhaustiveness rule
    pins (ISSUE 12)."""
    out = ["mxnet_tpu"]
    for extra in ("launch.py", "telemetry_dump.py", "fleet_top.py"):
        if os.path.isfile(os.path.join("tools", extra)):
            out.append(os.path.join("tools", extra))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.mxlint",
        description="TPU-invariant static analyzer for this repo "
                    "(stdlib-ast; see tools/mxlint/__init__.py)")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/trees to lint (default: mxnet_tpu plus "
                    "tools/launch.py — the whole threaded runtime)")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="parse/lint files in N worker processes (the "
                    "whole-program pass itself stays in-process)")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="grandfathered-violations file (default: "
                    "tools/mxlint/baseline.json when it exists)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report grandfathered violations too")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline from the current findings "
                    "and exit 0")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--select", default=None, metavar="RULES",
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--contracts", action="store_true",
                    help="run the program-contract lane instead of the "
                    "AST rules: lower every contracted jit program "
                    "device-free (JAX_PLATFORMS=cpu) and prove donation "
                    "aliasing, temp-HBM budgets and trace closure "
                    "(ISSUE 11; see tools/mxlint/contracts.py)")
    ap.add_argument("--write-manifest", nargs="?", const="DEFAULT",
                    default=None, metavar="FILE",
                    help="with --contracts: write the contract manifest "
                    "JSON (default tools/mxlint/contracts.json)")
    ap.add_argument("--check-manifest", nargs="?", const="DEFAULT",
                    default=None, metavar="FILE",
                    help="validate the shape of the contract manifest "
                    "(default tools/mxlint/contracts.json) and exit; "
                    "imports no jax")
    ap.add_argument("--protocol", action="store_true",
                    help="run the wire-protocol verifier instead of the "
                    "AST rules: extract per-verb effect summaries from "
                    "every declare_verbs() machine and model-check the "
                    "exactly-once layer under exhaustive bounded fault "
                    "schedules (ISSUE 19; see tools/mxlint/protocol.py). "
                    "No baseline: findings are fix-or-suppress-with-why")
    args = ap.parse_args(argv)

    if args.check_manifest:
        from . import contracts as _contracts
        path = args.check_manifest
        if path == "DEFAULT":
            path = _contracts.DEFAULT_MANIFEST
        return _contracts.check_contract_manifest(path)

    if args.protocol:
        # pure-stdlib like the AST lanes, but its own pipeline: verb
        # machines + deterministic model checker, never baselined
        from . import protocol as _protocol
        sel = None
        if args.select:
            sel = {r.strip() for r in args.select.split(",") if r.strip()}
            unknown = sel - set(RULES)
            if unknown:
                print("mxlint: unknown rule(s): %s"
                      % ", ".join(sorted(unknown)), file=sys.stderr)
                return 2
        ppaths = list(args.paths) if args.paths else _default_paths()
        for p in ppaths:
            if not os.path.exists(p):
                print("mxlint: no such path: %s" % p, file=sys.stderr)
                return 2
        return _protocol.run_cli(ppaths, fmt=args.format, select=sel)

    if args.contracts:
        # the contract lane imports the runtime (jax + mxnet_tpu) —
        # deliberately isolated from the pure-stdlib AST lanes above
        from . import contracts as _contracts
        out = args.write_manifest
        if out == "DEFAULT":
            out = _contracts.DEFAULT_MANIFEST
        names = None
        if args.select:
            names = [r.strip() for r in args.select.split(",")
                     if r.strip()]
        return _contracts.run_cli(fmt=args.format, write_manifest=out,
                                  contract_names=names)

    if args.list_rules:
        for rid, rule in sorted(RULES.items()):
            print("%-26s %s" % (rid, rule.description))
        return 0

    paths = list(args.paths) if args.paths else _default_paths()
    for p in paths:
        if not os.path.exists(p):
            print("mxlint: no such path: %s" % p, file=sys.stderr)
            return 2
    select = None
    if args.select:
        select = {r.strip() for r in args.select.split(",") if r.strip()}
        unknown = select - set(RULES)
        if unknown:
            print("mxlint: unknown rule(s): %s" % ", ".join(sorted(unknown)),
                  file=sys.stderr)
            return 2

    root = repo_root_of(paths[0]) or os.getcwd()
    # only the json output needs the ProjectIndex back (for the lock
    # graph); a --select run narrowed to file rules then skips the
    # whole-program indexing entirely
    want_graph = args.format == "json"
    try:
        result = lint_paths(paths, root=root, select=select,
                            jobs=args.jobs, return_project=want_graph)
        diags, project = result if want_graph else (result, None)
    except Exception as e:  # internal error must not look like "clean"
        print("mxlint: internal error: %s: %s" % (type(e).__name__, e),
              file=sys.stderr)
        return 2

    baseline_path = args.baseline or (
        DEFAULT_BASELINE if os.path.isfile(DEFAULT_BASELINE) else None)

    if args.write_baseline:
        if select is not None:
            # a rule-narrowed scan sees only a slice of the findings;
            # writing it out would silently drop every other rule's
            # grandfathered entries
            print("mxlint: --write-baseline cannot be combined with "
                  "--select (it would erase the unselected rules' "
                  "entries)", file=sys.stderr)
            return 2
        out = args.baseline or DEFAULT_BASELINE
        # merge: entries for files OUTSIDE the scanned paths are not in
        # `diags` only because they were not looked at — preserve them,
        # and re-attach every surviving entry's `why` justification
        kept = []
        whys = {}
        if os.path.isfile(out):
            rel_scanned = [os.path.relpath(os.path.abspath(p),
                                           root).replace(os.sep, "/")
                           for p in paths]
            prefixes = [r + "/" if os.path.isdir(p) else r
                        for p, r in zip(paths, rel_scanned)]

            def scanned(entry_path):
                return any(entry_path == pre.rstrip("/") or
                           entry_path.startswith(pre) for pre in prefixes)

            try:
                whys = load_baseline_whys(out)
                for key, count in load_baseline(out).items():
                    if not scanned(key[0]):
                        kept.append((key, count))
            except (OSError, ValueError, KeyError) as e:
                print("mxlint: cannot read existing baseline %s: %s"
                      % (out, e), file=sys.stderr)
                return 2
        write_baseline(out, diags, extra_counts=dict(kept), whys=whys)
        n = len(diags) + sum(c for _, c in kept)
        print("mxlint: wrote %d grandfathered entr%s to %s%s"
              % (n, "y" if n == 1 else "ies", out,
                 " (%d preserved from unscanned paths)" % len(kept)
                 if kept else ""))
        return 0

    baseline = {}
    if baseline_path and not args.no_baseline:
        try:
            baseline = load_baseline(baseline_path)
        except (OSError, ValueError, KeyError) as e:
            # a typo'd --baseline must read as a usage error (2), never as
            # "new violations" (1) — scripts key on the exit code
            print("mxlint: cannot read baseline %s: %s"
                  % (baseline_path, e), file=sys.stderr)
            return 2
    new, old, stale = apply_baseline(diags, baseline)

    if args.format == "json":
        # stable machine schema (satellite of ISSUE 6): every finding
        # carries rule id, file:line, a drift-stable fingerprint and the
        # thread roots involved; the static lock graph rides along so CI
        # can assert it stays acyclic
        cycles = project.lock_cycles()
        print(json.dumps({
            "schema": 2,
            "violations": [d.to_json() for d in new],
            "baselined": [d.to_json() for d in old],
            "stale_baseline": ["%s:%s:%s" % k for k in stale],
            "lock_graph": {
                "edges": sorted("%s -> %s" % k
                                for k in project.lock_graph()),
                "acyclic": not cycles,
            },
        }, indent=1))
    else:
        for d in new:
            print("%s:%d:%d: %s: %s" % (d.path, d.line, d.col, d.rule,
                                        d.message))
        if stale:
            print("mxlint: note: %d stale baseline entr%s (fixed or "
                  "reworded) — run --write-baseline to shrink the file"
                  % (len(stale), "y" if len(stale) == 1 else "ies"),
                  file=sys.stderr)
        summary = "mxlint: %d new violation%s" % (
            len(new), "" if len(new) == 1 else "s")
        if old:
            summary += ", %d baselined" % len(old)
        print(summary, file=sys.stderr)
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
