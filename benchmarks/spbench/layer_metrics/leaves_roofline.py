"""Share, in percent, of the roofline that the device time of one iteration
reaches: the least time of the statement's compulsory work (``work.py``,
bytes over HBM bandwidth or operations over peak, whichever is larger,
shared over the chips held) over the busiest device's time per iteration."""
import work


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None:
        return None
    busy = tr["per_device"][tr["busiest"]]["busy_s"]
    if busy <= 0:
        return None
    per_iter = busy / ctx["iterations"]
    return 100.0 * work.roofline_s(ctx["work"], peaks, ctx["chips"]) \
        / per_iter
