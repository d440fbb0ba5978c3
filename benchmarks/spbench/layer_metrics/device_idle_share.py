"""Share of the traced window in which the busiest device runs no op."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    busy = tr["per_device"][tr["busiest"]]["busy_s"]
    return 1.0 - busy / tr["window_s"]
