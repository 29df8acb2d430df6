"""Servable: serve.padding_rows over (serve.rows + serve.padding_rows) -
device work spent on rows nobody asked for."""


def read(run):
    c = run.facts.get("counters")
    if not c:
        return None
    rows, pad = c.get("serve.rows", 0), c.get("serve.padding_rows", 0)
    return None if not rows + pad else 100.0 * pad / (rows + pad)
