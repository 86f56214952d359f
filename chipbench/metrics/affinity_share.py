"""Fleet router: share of the window's routed requests placed on a
replica that already held their prefix, in %. Reads nothing on a single
server."""


def read(run):
    if "routed_requests" not in run.stats["end"]:
        return None
    routed = run.delta("routed_requests")
    if routed <= 0:
        return None
    return 100.0 * run.delta("affinity_hits") / routed
