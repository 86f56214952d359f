"""Load generator: how late the client sent the turns due in the window
(send time minus due time), 95th percentile, in ms. A starved generator
shows here and not as a slow server."""
from generator import percentile


def read(run):
    lates = [(r.sent - r.due) * 1e3 for r in run.measured if r.sent]
    return percentile(lates, 95)
