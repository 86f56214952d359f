"""Pump loop (host between decode chunks): the mean time on the pump
thread from one decode chunk's host sync to the next chunk's dispatch,
less the first-token syncs in between, over the window's consecutive
chunks, in ms: the time the device waits on the loop."""


def read(run):
    a, b = run.stats["start"], run.stats["end"]
    if "host_gaps" not in b:
        return None
    n = b["host_gaps"] - a["host_gaps"]
    if n <= 0:
        return None
    return 1e3 * (b["host_gap_s"] - a["host_gap_s"]) / n
