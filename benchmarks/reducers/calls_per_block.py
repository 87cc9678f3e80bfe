"""Device calls per block: how many spans of the named kind (here
`device_enqueue`, one for each program the provider sends) the work on
one block opened, averaged over the blocks the run recorded whole.

A call is given to its block by its parents, not by the clock: it
belongs to the block whose number the nearest span above it carries,
among the spans named `under` (`device_dispatch` for the block's batch,
`mcs_verify` for the check of the block's signature).  The pipeline
runs two blocks ahead of the commit events that bound the window, so
the window's count of calls over the window's blocks loses that lead
whenever the backlog runs dry (3.805 where every block made 4 calls);
a count per block does not depend on where the window was cut, nor on
how long the backlog lasts.  A block is whole once each span of `under`
has been recorded for it: its calls ended before those did.  The
warm-up blocks count with the window's: the same traffic.

The spans are read from the program's own recorder, as `idle_under`
reads them.  None where no named span has a block above it (a verifier
that opens no such span), and where the recorder's ring is full, so
that calls may have been dropped from it before their parents.

spec: {"spans": [names of the calls], "under": [names of the spans
       that carry a `block` attribute and hold a block's calls]}
"""


def per_block(ring, spans, under) -> dict:
    """{block number: calls} of the blocks recorded whole."""
    by_id = {(sp["trace_id"], sp["span_id"]): sp for sp in ring}

    def holds_block(sp) -> bool:
        return sp["name"] in under and "block" in sp["attrs"]

    seen = {}
    for sp in ring:
        if holds_block(sp):
            seen.setdefault(sp["attrs"]["block"], set()).add(sp["name"])
    calls = {b: 0 for b, names in seen.items() if names == set(under)}
    for sp in ring:
        if sp["name"] not in spans:
            continue
        up = sp
        while up is not None and not holds_block(up):
            up = by_id.get((up["trace_id"], up["parent_id"]))
        if up is not None and up["attrs"]["block"] in calls:
            calls[up["attrs"]["block"]] += 1
    return calls


def reduce(spec, window):
    from fabric_mod_tpu.observability import tracing
    ring = tracing.recorder().recent_spans(limit=1 << 30)
    if len(ring) >= tracing.SPAN_RING:
        return None
    calls = per_block(ring, set(spec["spans"]), set(spec["under"]))
    if not any(calls.values()):
        return None
    return sum(calls.values()) / len(calls)
