"""Scheduler: slots decoding per engine step over the window, from the
server's cumulative ``active_slots_per_step`` and ``engine_steps`` (summed
over the replicas of a fleet)."""


def _busy_and_steps(stats):
    per = stats.get("per_replica") or [stats]
    return (sum(p["active_slots_per_step"] * p["engine_steps"]
                for p in per if p),
            sum(p["engine_steps"] for p in per if p))


def read(run):
    b0, s0 = _busy_and_steps(run.stats["start"])
    b1, s1 = _busy_and_steps(run.stats["end"])
    if s1 <= s0:
        return None
    return (b1 - b0) / (s1 - s0)
