"""Host busy time of the named spans over the window, per block or
per transaction, in milliseconds.  Spans of the pipeline's threads
overlap, so this is a layer's busy time, never a share of the wall.

spec: {"spans": [names], "per": "block" | "tx"}
"""


def reduce(spec, window):
    present = [s for s in spec["spans"] if window.span_counts.get(s, 0) > 0]
    per = {"block": window.blocks, "tx": window.txs}[spec["per"]]
    if not present or per <= 0:
        return None
    return 1e3 * sum(window.span_secs[s] for s in present) / per
