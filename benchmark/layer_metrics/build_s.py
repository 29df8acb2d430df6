"""Gluon front end: host clock around build + initialize + cast +
hybridize + the deferred-shape forward (+ export for a serve cell)."""


def read(run):
    return run.facts.get("build_s")
