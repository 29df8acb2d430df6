"""Serve front, timed from outside: the client's median latency less the
mean time a batch spends in the replica's queue_wait + pad +
serve_dispatch + scatter phases - wire, envelope, payload decode, reply.
(The phases are histograms: they give means, not medians.)  Goes when the
program has request spans."""
from benchmark.harness import stats


def read(run):
    f = run.facts
    phases = f.get("phases")
    if not phases or not f.get("latency_s"):
        return None
    inside = sum(phases[p]["sum"] / phases[p]["count"]
                 for p in ("queue_wait", "pad", "serve_dispatch", "scatter")
                 if phases.get(p, {}).get("count"))
    return 1e3 * (stats.median(f["latency_s"]) - inside)
