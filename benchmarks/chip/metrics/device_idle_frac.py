"""Device: share of the traced window in which no operation ran on the
chip, 1 minus the union of the operations' intervals over the window."""


def read(rec):
    w = rec["trace_window_s"]
    if w <= 0:
        return None
    return 1.0 - rec["busy_s"] / w
