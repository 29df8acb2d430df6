"""Look at a trace by hand (on-chip-measurement guide, section 6): print
the planes, lines, event counts, the stats the first events carry and what
``trace_reduce.reduce`` makes of it; with ``--sample N OUT.json`` write the
first N device ops of each device, the modules and host spans that
overlap them, in the plain form ``trace_reduce.reduce`` takes (this is how
the recorded sample under tests/benchmark/data/ was cut).

    python3 benchmark/tools/trace_dump.py <dir or .xplane.pb> [--sample N OUT.json]
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import trace_reduce  # noqa: E402


def main(argv):
    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in data.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  line %r: %d events" % (line.name, len(events)))
            for ev in events[:3]:
                print("    %r start_ns=%d dur_ns=%d stats=%r"
                      % (ev.name, ev.start_ns, ev.duration_ns,
                         {k: (v if not isinstance(v, (str, bytes))
                              else v[:60]) for k, v in ev.stats}))
    trace = trace_reduce.load_xplane(path)
    reduced = trace_reduce.reduce(trace)
    print(json.dumps(reduced, indent=1)[:6000])
    if "--sample" in argv:
        n = int(argv[argv.index("--sample") + 1])
        out = argv[argv.index("--sample") + 2]
        sample = {"devices": {}, "host": []}
        lo, hi = None, 0
        for name, dev in trace["devices"].items():
            ops = sorted(dev["ops"], key=lambda o: o[2])[:n]
            lo = ops[0][2] if lo is None else min(lo, ops[0][2])
            hi = max(hi, max(o[2] + o[3] for o in ops))
            sample["devices"][name] = {"ops": ops}
        for name, dev in trace["devices"].items():
            sample["devices"][name]["modules"] = [
                m for m in dev["modules"] if m[1] < hi and m[1] + m[2] > lo]
        sample["host"] = [s for s in trace["host"]
                          if s[1] < hi and s[1] + s[2] > lo]
        with open(out, "w") as f:
            json.dump(sample, f)
        print("sample:", out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1:])
