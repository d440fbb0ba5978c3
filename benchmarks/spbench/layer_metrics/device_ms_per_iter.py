"""Device milliseconds per iteration: the union of the op intervals on the
busiest device inside the traced window, over the window's iterations."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    busy = tr["per_device"][tr["busiest"]]["busy_s"]
    return busy / ctx["iterations"] * 1e3 if busy > 0 else None
