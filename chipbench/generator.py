"""The one traffic generator: turns a mix file and a cell's load into a plan.

A mix (``traffic/<name>.json``) holds only parameters: length
distributions, sharing, tool waits. The cell (``cells/<name>.json``) holds
the load: a fixed arrival rate (an open loop), or a fixed number of
clients that each run conversations back to back (a closed loop).
``plan()`` turns them into conversations: chains of turns, each turn due
when the one before it completed (plus its ``wait_s``), the first at the
conversation's arrival or, in a closed loop, when the client's previous
conversation ended.

Every seed gets the same plan. The sizes are stratified quantiles of each
distribution, dealt out by a fixed structure RNG, which also orders the
conversations and shuffles the arrival gaps, so the work offered does not
depend on the seed. The seed draws the weights and the token ids, so two
seeds differ in content, not in work.
"""
from __future__ import annotations

import dataclasses
import math
import random
import statistics
from typing import Dict, List, Optional, Sequence

import numpy as np

_NORMAL = statistics.NormalDist()
STRUCTURE_SEED = 0x5EED   # fixed: the sizes are the same for every run seed


@dataclasses.dataclass(frozen=True)
class Turn:
    """One request as the client plans it.

    ``role`` >= 0 makes it a turn of that role's session within its
    conversation: its prompt is the session's context so far (prompt and
    output of the role's last turn) plus ``suffix`` new tokens, and a
    role's first turn starts with shared prefix ``prefix``. ``role`` -1 is
    a request of its own: ``prefix`` (if any) and ``suffix`` tokens."""
    role: int
    suffix: int
    output: int
    prefix: int = -1          # index of a shared prefix, -1 for none
    wait_s: float = 0.0       # after the previous turn completes


@dataclasses.dataclass(frozen=True)
class Conversation:
    arrival_s: float          # offset from the window's start; < 0 = lead-in
    turns: tuple
    after: int = -1           # closed loop: the conversation it follows


@dataclasses.dataclass
class Plan:
    conversations: List[Conversation]
    prefix_tokens: List[int]  # length of each shared prefix


def quantile(spec: Dict, u: float) -> float:
    """Value of distribution ``spec`` at quantile ``u`` in (0, 1)."""
    dist = spec["dist"]
    if dist == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"] + 1)
        return min(math.floor(v), spec["max"])
    if dist == "lognormal":
        v = math.exp(math.log(spec["median"])
                     + spec["sigma"] * _NORMAL.inv_cdf(u))
        return min(max(round(v), spec["min"]), spec["max"])
    if dist == "exponential":
        return -spec["mean"] * math.log1p(-u)
    if dist == "choice":
        vals = spec["values"]
        return vals[min(int(u * len(vals)), len(vals) - 1)]
    raise ValueError(f"unknown distribution {dist!r}")


def stratified(spec: Dict, n: int, rng: random.Random) -> list:
    """``n`` draws of ``spec`` at the stratified quantiles (i + 1/2) / n,
    in an order shuffled by ``rng``."""
    vals = [quantile(spec, (i + 0.5) / n) for i in range(n)]
    rng.shuffle(vals)
    return vals


def _fits(ctx: int, suffix: int, output: int, engine: Dict) -> bool:
    """A turn fits the cache when its prompt, output and the sampled token
    stay inside capacity, and when its last prefill chunk, padded to a
    bucket, does too (the engine would otherwise clamp that chunk to a
    shape that was never warmed up)."""
    cap = engine["capacity"]
    prompt = ctx + suffix
    return (prompt + output + 1 <= cap
            and prompt + max(engine["prefill_buckets"]) <= cap)


def count(load: Dict, seconds: float) -> int:
    """How many conversations a mix lays out for ``seconds``: the open
    loop's arrivals, or the closed loop's clients times the conversations
    each may run."""
    if "clients" in load:
        return load["clients"] * load["conversations_per_client"]
    return max(1, round(load["rate_per_s"] * seconds))


def _agent(mix: Dict, load: Dict, seconds: float, engine: Dict, rng):
    n_conv = count(load, seconds)
    roles = len(mix["roles"])
    apps = stratified({"dist": "uniform", "min": 0, "max": mix["apps"] - 1},
                      n_conv, rng)
    reqs = stratified(mix["user_requests"], n_conv, rng)
    iters = stratified(mix["iterations"], sum(reqs), rng)
    n_turns = sum(iters) * roles
    suffix = stratified(mix["suffix_tokens"], n_turns, rng)
    output = stratified(mix["output_tokens"], n_turns, rng)
    tool_tok = stratified(mix["tool_output_tokens"], sum(iters), rng)
    tool_s = stratified(mix["tool_seconds"], sum(iters), rng)
    tool_role = mix["roles"].index(mix["tool_output_to"])
    convs, k, q, t = [], 0, 0, 0
    for c in range(n_conv):
        ctx = [mix["system_tokens"]] * roles
        turns, ok = [], True
        for _ in range(reqs[c]):
            n_iter = iters[q]
            q += 1
            for _ in range(n_iter):
                for role in range(roles):
                    s, o, wait = suffix[k], output[k], 0.0
                    if role == tool_role:
                        s += tool_tok[t]
                        wait = tool_s[t]
                    k += 1
                    if ok and not _fits(ctx[role], s, o, engine):
                        ok = False
                    if ok:
                        turns.append(Turn(role, s, o, apps[c] * roles + role,
                                          wait))
                        ctx[role] += s + o
                t += 1
        convs.append(turns)
    return convs, [mix["system_tokens"]] * (mix["apps"] * roles)


def _open(mix: Dict, load: Dict, seconds: float, engine: Dict, rng):
    n = count(load, seconds)
    convs = []
    for cls in mix["classes"]:
        m = round(n * cls["share"])
        prompts = stratified(cls["prompt_tokens"], m, rng)
        outputs = stratified(cls["output_tokens"], m, rng)
        for p, o in zip(prompts, outputs):
            if not _fits(0, p, o, engine):
                raise ValueError(f"mix request of {p}+{o} tokens does not "
                                 "fit the cell's cache")
            convs.append([Turn(-1, p, o)])
    rng.shuffle(convs)
    return convs, []


_KINDS = {"agent": _agent, "open": _open}


def shapes(kind: str):
    """The function that lays out a mix's conversations: one of the kinds
    above, or ``conversations`` of ``traffic/<kind>.py``, so that a mix of
    a new shape comes as files of its own. It takes (mix, load, seconds,
    engine, structure RNG) and returns (conversations as lists of
    ``Turn``, shared prefix lengths)."""
    if kind in _KINDS:
        return _KINDS[kind]
    import manifest
    path = manifest.HERE / "traffic" / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown traffic kind {kind!r}: no {path.name}")
    return manifest.module(path).conversations


def open_arrivals(n: int, span: float, load: Dict, rng) -> List[float]:
    """``n`` arrival offsets in [0, span), the first at 0: Poisson gaps at
    stratified quantiles, shuffled by ``rng`` and scaled so that the ``n``
    gaps (the last one closing the span) fill it exactly. With
    ``load["bursts"]`` (``on_s``, ``off_s``) the arrivals come only in the
    on-periods of an on/off cycle that starts on, at the rate that keeps
    the same ``n``."""
    if n == 0:
        return []
    gaps = stratified({"dist": "exponential", "mean": 1.0}, n, rng)
    burst = load.get("bursts")
    on, off = (burst["on_s"], burst["off_s"]) if burst else (span, 0.0)
    cycles = span / (on + off)
    on_span = on * (math.floor(cycles)
                    + min(1.0, (cycles - math.floor(cycles))
                          * (on + off) / on))
    t = np.cumsum([0.0] + gaps[:-1]) * (on_span / sum(gaps))
    # on-time to wall time: skip an off-period after every ``on`` seconds
    return list(t + np.floor(t / on) * off)


def plan(mix: Dict, load: Dict, engine: Dict, seconds: float) -> Plan:
    """The conversations of one run, the same for every seed: the sizes
    from the structure RNG, then the order of the conversations and the
    arrival gaps from the same RNG. The seed draws only the weights and
    each turn's tokens (``client.py``): a window holds a few turns of a
    few conversations (a closed loop) or a few tens of requests (an open
    one), so another order was other work.

    Arrivals cover the lead-in ``[-lead_in_s, 0)`` as well as the window
    ``[0, seconds)``: the window then opens on a system in its steady
    state, with conversations already under way, and not on an empty one.
    In a closed loop the clients start evenly spread over the lead-in, and
    conversation ``j`` goes to client ``j % clients``. Only turns due
    inside the window are measured."""
    lead = load.get("lead_in_s", 0.0)
    srng = random.Random(STRUCTURE_SEED)
    convs, prefixes = shapes(mix["kind"])(mix, load, seconds + lead,
                                          engine, srng)
    convs = [tuple(c) for c in convs if c]
    if "clients" in load:
        n = load["clients"]
        return Plan([Conversation(-lead + lead * j / n if j < n else 0.0, c,
                                  j - n if j >= n else -1)
                     for j, c in enumerate(convs)], prefixes)
    srng.shuffle(convs)
    # the lead-in and the window each get their share of the arrivals
    n_lead = round(len(convs) * lead / (lead + seconds))
    arrivals = ([a - lead for a in open_arrivals(n_lead, lead, load, srng)]
                + open_arrivals(len(convs) - n_lead, seconds, load, srng))
    return Plan([Conversation(float(a), c)
                 for a, c in zip(arrivals, convs)], prefixes)


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    """``n`` prompt token ids, clear of the tokenizer's three specials at
    the top of the vocabulary."""
    return rng.integers(0, vocab - 3, size=n, dtype=np.int64).astype(np.int32)


def offered(plan_: Plan, seconds: float) -> Dict[str, float]:
    """Totals of what the plan can send up to the window's close (turns
    after a conversation's first are sent only if it gets that far; a
    closed loop's later conversations only if its clients get to them)."""
    convs = [c for c in plan_.conversations
             if c.after < 0 and c.arrival_s < seconds]
    turns = [t for c in convs for t in c.turns]
    return {"conversations": len(convs), "turns_planned": len(turns),
            "output_tokens_planned": sum(t.output for t in turns)}


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100), interpolated between order
    statistics (``statistics.quantiles``, inclusive); None when empty."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    return float(statistics.quantiles(vals, n=100, method="inclusive")[
        int(q) - 1]) if q < 100 else float(vals[-1])
