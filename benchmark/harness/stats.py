"""Order statistics of the readings inside one run."""
import math


def median(values):
    return percentile(values, 50.0)


def percentile(values, p):
    """The p-th percentile (nearest rank, no interpolation) of `values`.

    A tail percentile is refused unless at least ten samples lie beyond
    it: with fewer, the number is one or two outliers and does not repeat
    (choosing-metrics guide, section 1)."""
    n = len(values)
    if n == 0:
        raise ValueError("percentile of no samples")
    beyond = n * min(p, 100.0 - p) / 100.0
    if p != 50.0 and beyond < 10.0:
        raise ValueError("p%g of %d samples has %.1f beyond it; ten are "
                         "needed" % (p, n, beyond))
    ordered = sorted(values)
    rank = max(1, int(math.ceil(p / 100.0 * n)))
    return ordered[rank - 1]


def spread(values):
    """Distance between the quartiles over the median: the spread the
    bounds of BENCHMARK.json are set from."""
    ordered = sorted(values)
    n = len(ordered)
    if n < 3:
        raise ValueError("spread of fewer than 3 runs")

    def q(f):
        pos = f * (n - 1)
        lo = int(math.floor(pos))
        hi = min(n - 1, lo + 1)
        return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)

    return (q(0.75) - q(0.25)) / q(0.5)
