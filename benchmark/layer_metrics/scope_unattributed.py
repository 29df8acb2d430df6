"""Step compiler: the share of the step program's device time in fusions
that mix top-level scopes (`mixed` in programs.program_scopes) or in ops
with no top-level scope: how far forward_ms, backward_ms and optimizer_ms
can be trusted (harness/program_trace.py; an earlier line says what
mixes with what)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.scope(run, "scope_unattributed")
