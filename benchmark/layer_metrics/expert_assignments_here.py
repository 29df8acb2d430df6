"""Kernels: the share of all (token, choice) assignments that landed on the
experts held here, every expert layer together: `moe_assignments` over
`moe_assignments` + `moe_assignments_elsewhere`
(harness/expert_counters.py).  held / published experts x 100 is expected
of an even router: 12.5 for 8 of 64."""
from benchmark.harness import expert_counters


def read(run):
    here, away = expert_counters.assignments()
    held = sum(sum(c.values()) for c in here.values())
    total = held + sum(away.values())
    return 100.0 * held / total if total else None
