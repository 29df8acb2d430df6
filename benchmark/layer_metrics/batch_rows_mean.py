"""Batcher: serve.batch_occupancy sum over count - rows per dispatch."""


def read(run):
    occ = (run.facts.get("histograms") or {}).get("serve.batch_occupancy")
    return None if not occ or not occ["count"] else occ["sum"] / occ["count"]
