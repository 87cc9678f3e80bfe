"""Device milliseconds per call of the compiled program that took most
device time in the traced window, among those whose jit name matches.

spec: {"programs": [regular expressions on the jit name]}
"""


def reduce(spec, window):
    if window.trace is None:
        return None
    found = window.trace.modules_matching(spec["programs"])
    if not found:
        return None
    secs, calls = max(found.values(), key=lambda v: v[0])
    if calls <= 0:
        return None
    return 1e3 * secs / calls
