"""Kernels: how uneven the held experts' load was.  From the counters
`moe_assignments{layer, expert}` (harness/expert_counters.py): for each
expert layer the fullest held expert's assignments over the mean of the
held ones, mean over the layers.  1.0 is even."""
from benchmark.harness import expert_counters


def read(run):
    here, _ = expert_counters.assignments()
    ratios = [max(c.values()) / (sum(c.values()) / len(c))
              for c in here.values() if sum(c.values())]
    if not ratios:
        return None
    run.note(moe_assignments={layer: sorted(c.values(), reverse=True)
                              for layer, c in here.items()})
    return sum(ratios) / len(ratios)
