"""The least time the chip could take for the real signatures of the
traced window's whole calls (`work.least_seconds`), over those calls'
device time, in percent.  Work and time come from the same calls
(`timeline.whole_calls`): each program that ran whole inside the
window, with the items of the `der_marshal` span of the dispatch that
sent it.  A program cut at either end of the window, and a call whose
items are not found, count for neither.  None where no such call lies
in the window.

spec: {"programs": [regular expressions on the jit name]}
"""
from benchmarks import timeline, work


def reduce(spec, window):
    if window.session is None:
        return None
    calls = [(secs, items) for _name, secs, items in timeline.whole_calls(
        window.session, window.ring, spec["programs"]) if items]
    device_s = sum(secs for secs, _items in calls)
    if device_s <= 0:
        return None
    least = work.least_seconds(sum(items for _secs, items in calls),
                               window.device_kind)
    return 100.0 * least["seconds"] / device_s
