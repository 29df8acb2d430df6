"""Bytes of parameters + optimizer state in the addressable shards of the
fullest device over the total (25 % is even on four chips)."""


def read(run):
    share = run.facts.get("state_share")
    return None if share is None else 100.0 * share
