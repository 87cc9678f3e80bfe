"""Real items over bucket lanes, summed over the window's device
dispatches, in percent: a count, so it repeats exactly for one seed.

spec: {} (reads the `items` and `bucket` attributes of the provider's
`der_marshal` span, one per dispatch)
"""


def reduce(spec, window):
    lanes = sum(bucket for _items, bucket in window.dispatches)
    if lanes <= 0:
        return None
    return 100.0 * sum(items for items, _b in window.dispatches) / lanes
