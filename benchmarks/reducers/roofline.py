"""The least time the chip could take for the real signatures of the
traced blocks (`work.least_seconds`), over the device time of the
matching programs in the traced window, in percent.  The profiler
window spans whole blocks, commit event to commit event, so in steady
state the device work inside it is that of as many blocks.

spec: {"programs": [regular expressions on the jit name]}
"""
from benchmarks import work


def reduce(spec, window):
    if window.trace is None or window.traced_items <= 0:
        return None
    found = window.trace.modules_matching(spec["programs"])
    device_s = sum(v[0] for v in found.values())
    if device_s <= 0:
        return None
    least = work.least_seconds(window.traced_items, window.device_kind)
    return 100.0 * least["seconds"] / device_s
