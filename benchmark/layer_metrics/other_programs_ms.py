"""Step compiler: device time a step inside "XLA Modules" events that are
not the step program's (the loss reshape after each step, the
prefetcher's placement programs); an earlier line lists them by module
name, which is the census name (harness/program_trace.py)."""
from benchmark.harness import program_trace


def read(run):
    got = program_trace.read(run)
    return None if not got else got.get("other_programs_ms")
