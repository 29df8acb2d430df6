"""What the run's set-up was made of, by the program's own start-up
timeline: ``telemetry.startup_breakdown`` over ``[process start, process
start + setup_s]``, the very seconds ``setup_s`` counts.

The program holds the first spans of its life (``import``, ``initialize``,
the top-level eager ``forward``, every jit's ``compile.trace`` /
``compile.lower`` / ``compile.cache_load`` / ``compile.backend`` by jax's
function name, the step's phases); the innermost span at each moment owns
it, so the nine kinds add up to ``setup_s``.  ``read(run)`` makes the
reduction once, caches it on ``run.facts`` and prints one ``benchmark:``
line for a reader's eye: the kinds, the ten costliest programs and every
unspanned stretch over half a second with the spans on both sides.

None where the program has no ``telemetry.startup_breakdown`` (a checkout
from before it existed) or the driver took no ``setup_s``: the nine
readers then leave their metrics out of the line.
"""

PROGRAMS_SHOWN = 10


def read(run):
    """The breakdown of the run's set-up, or None."""
    facts = run.facts
    if "setup_spans" not in facts:
        facts["setup_spans"] = _reduce(run)
    return facts["setup_spans"]


def _reduce(run):
    from mxnet_tpu import telemetry
    breakdown = getattr(telemetry, "startup_breakdown", None)
    setup_s = run.facts.get("setup_s")
    if breakdown is None or setup_s is None:
        return None
    found = breakdown(run.t_process, run.t_process + setup_s)
    run.note(setup_spans={
        "setup_s": setup_s,
        "kinds_s": {k: round(found[k], 4) for k in telemetry.STARTUP_KINDS},
        "programs_s": [dict({k: round(v, 4) for k, v in row.items()},
                            program=name)
                       for name, row in list(found["by_program"].items())
                       [:PROGRAMS_SHOWN]],
        "programs": len(found["by_program"]),
        "gaps": [dict(g, start=round(g["start"], 3), end=round(g["end"], 3))
                 for g in found["gaps"]],
        "dropped": found["dropped"]})
    return found


def seconds(run, kind):
    """Seconds of set-up the timeline gives to `kind`, or None."""
    found = read(run)
    return None if found is None else found[kind]
