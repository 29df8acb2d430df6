"""The device a run is allowed to report, and how it is described.

Called only by the process that holds the chip (a train driver's own
process; the replica child of a serve cell).  There is no fallback: off a
TPU, on an unknown kind or with too few chips the run ends non-zero and
prints no result.  Under the harness's ``MX_FORCE_CPU=1`` a *rehearsal*
configuration runs on the host and says ``platform: "cpu"``.
"""
import os

from . import peaks


def rehearsing():
    return os.environ.get("MX_FORCE_CPU") == "1"


def require(chips):
    """Device facts for the last line, or SystemExit."""
    import jax
    devices = jax.devices()
    first = devices[0]
    facts = {"platform": first.platform, "kind": first.device_kind,
             "count": int(chips)}
    if len(devices) < chips:
        raise SystemExit("benchmark: the cell asks for %d chip(s), jax "
                         "found %d" % (chips, len(devices)))
    if rehearsing():
        if first.platform != "cpu":
            raise SystemExit("benchmark: MX_FORCE_CPU=1 but jax is on %r"
                             % first.platform)
        return facts
    if first.platform != "tpu":
        raise SystemExit("benchmark: needs a TPU, jax found %r"
                         % first.platform)
    try:
        peaks.peaks_for(first.device_kind)
    except KeyError as e:
        raise SystemExit(str(e))
    return facts


def memory_peak_bytes(chips):
    """Peak bytes held on the fullest of the first `chips` devices: the
    allocator's ``peak_bytes_in_use`` plus, where the backend keeps a
    running program's temporaries apart as the TPU's does
    (``peak_bytes_reserved``: 10.25 GB of BERT-base's step against 3.15 GB
    of buffers, my chip run, PR 23), that reservation.  0 where the
    backend reports nothing, as XLA:CPU."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats():
    """Device 0's whole ``memory_stats()``, for an earlier line."""
    import jax
    return {k: int(v) for k, v in
            (jax.devices()[0].memory_stats() or {}).items()}
