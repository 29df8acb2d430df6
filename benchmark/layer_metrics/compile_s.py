"""Program registry: compile seconds summed over the registered programs
(programs.program_summary()), at the end of set-up.  On a warm cache this
is trace + load, not XLA compilation."""


def read(run):
    return run.facts.get("compile_s")
