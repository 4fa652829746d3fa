"""Device idle share of the traced window, in %: 1 - busy union / window."""


def read(t, rec, peak):
    if t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
