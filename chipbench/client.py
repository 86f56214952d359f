"""The load client: plays a plan against a server from one client thread.

Turns go out at their due times through a small pool of submitter threads,
so a submit that waits for the server's pump to reach the end of an
engine step does not hold back the turns due behind it. The client
thread polls its outstanding handles; a turn that completes makes the next
turn of its conversation due (after the turn's tool wait), and in a closed
loop a conversation that ends makes its client's next one due.

Every turn due inside the window is waited for after the window closes;
turns due after the close are never sent. Everything is timed on
``time.perf_counter``, the clock the server stamps its requests with.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import heapq
import time
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from generator import Plan, token_ids

POLL_S = 0.002


@dataclasses.dataclass
class Record:
    """One sent turn, as the client saw it."""
    conv: int
    index: int                 # turn index within its conversation
    due: float                 # absolute perf_counter time it was due
    measured: bool             # due inside the window
    max_new: int
    prompt: np.ndarray         # the token ids sent
    sent: float = 0.0          # when the client called submit
    submitted: float = 0.0     # when the server enqueued it
    first: float = 0.0         # when the server sampled its first token
    done: float = 0.0          # when the client saw it finish
    status: str = "pending"
    output: Optional[np.ndarray] = None
    prefix_hit: int = 0
    admit_start: float = 0.0   # when its admission began on the server
    error: str = ""

    @property
    def ok(self) -> bool:
        return self.status == "completed"


class LoadClient:
    """Plays ``plan`` against ``server`` (an ``LLMServer`` or a
    ``FleetServer`` with a background pump) starting at ``t0``."""

    def __init__(self, server, plan: Plan, *, vocab: int, seed: int,
                 sampling, submitters: int = 4):
        self.server = server
        self.plan = plan
        self.vocab = vocab
        self.seed = seed % 2 ** 64
        self.sampling = sampling          # max_new_tokens -> SamplingParams
        self.pool = concurrent.futures.ThreadPoolExecutor(
            submitters, thread_name_prefix="bench-submit")
        self.records: List[Record] = []
        rng = np.random.default_rng([self.seed, 0])
        self.prefixes = [token_ids(rng, n, vocab) for n in plan.prefix_tokens]
        # one stream per conversation: a turn's tokens do not depend on the
        # order in which other conversations' turns happened to complete
        self.rngs = [np.random.default_rng([self.seed, 1, c])
                     for c in range(len(plan.conversations))]

    def _prompt(self, conv: int, turn, context: Dict) -> np.ndarray:
        new = token_ids(self.rngs[conv], turn.suffix, self.vocab)
        if turn.role >= 0 and turn.role in context:
            return np.concatenate([context[turn.role], new])
        if turn.prefix >= 0:
            return np.concatenate([self.prefixes[turn.prefix], new])
        return new

    def _submit(self, rec: Record, sessions: Dict, role: int):
        """Runs on a submitter thread: open the role's session on its
        first turn, then submit."""
        sid = None
        if role >= 0:
            if role not in sessions:
                with TraceAnnotation("client.open_session"):
                    sessions[role] = self.server.open_session()
            sid = sessions[role].sid
        ids = rec.prompt.tolist()
        rec.sent = time.perf_counter()
        with TraceAnnotation("client.submit"):
            return self.server.submit("", self.sampling(rec.max_new),
                                      session=sid, token_ids=ids)

    def run(self, t0: float, window_s: float, drain_s: float,
            on_tick=None) -> List[Record]:
        """Play the plan: arrivals relative to ``t0``, measured turns due in
        ``[t0, t0 + window_s)``. Returns once every sent turn has finished,
        or ``drain_s`` after the window closed (turns still out then are
        recorded as never finished). ``on_tick(now)`` runs on every poll."""
        end = t0 + window_s
        convs = self.plan.conversations
        due = [(t0 + c.arrival_s, i, 0) for i, c in enumerate(convs)
               if c.after < 0]
        heapq.heapify(due)
        follower = {c.after: i for i, c in enumerate(convs) if c.after >= 0}
        context: List[Dict] = [{} for _ in convs]
        sessions: List[Dict] = [{} for _ in convs]
        out: Dict[int, tuple] = {}       # conv -> (record, future or handle)
        closing = []

        def ended(c: int, when: float):
            """Conversation ``c`` is over: close its sessions, and in a
            closed loop make its client's next conversation due."""
            closing.append(self._close(sessions[c]))
            if c in follower and when < end:
                heapq.heappush(due, (when, follower[c], 0))

        while True:
            now = time.perf_counter()
            if on_tick is not None:
                on_tick(now)
            while due and due[0][0] <= now:
                when, c, k = heapq.heappop(due)
                if when >= end:
                    closing.append(self._close(sessions[c]))
                    continue
                turn = convs[c].turns[k]
                rec = Record(c, k, when, when >= t0, turn.output,
                             self._prompt(c, turn, context[c]))
                self.records.append(rec)
                out[c] = (rec, self.pool.submit(self._submit, rec,
                                                sessions[c], turn.role))
            for c, (rec, h) in list(out.items()):
                if isinstance(h, concurrent.futures.Future):
                    if not h.done():
                        continue
                    try:
                        h = h.result()
                    except Exception as e:            # refused at submit
                        self._finish(rec, now, "refused", error=repr(e))
                        del out[c]
                        ended(c, now)
                        continue
                    out[c] = (rec, h)
                if not h.done:
                    continue
                self._finish(rec, time.perf_counter(), h.status().value,
                             h.request)
                del out[c]
                conv = convs[c]
                if rec.ok and rec.index + 1 < len(conv.turns):
                    turn = conv.turns[rec.index]
                    if turn.role >= 0:
                        context[c][turn.role] = np.concatenate(
                            [rec.prompt, rec.output])
                    nxt = conv.turns[rec.index + 1]
                    heapq.heappush(due, (rec.done + nxt.wait_s, c,
                                         rec.index + 1))
                else:
                    ended(c, rec.done)
            if now >= end and (not out or now > end + drain_s):
                break
            nxt_due = due[0][0] if due else now + POLL_S
            time.sleep(max(0.0, min(POLL_S, nxt_due - time.perf_counter())))
        for _, c, _ in due:
            closing.append(self._close(sessions[c]))
        now = time.perf_counter()
        for rec, h in out.values():          # never finished
            req = None if isinstance(h, concurrent.futures.Future) \
                else h.request
            self._finish(rec, now, "unfinished", req)
        for f in closing:
            f.result()
        return self.records

    def _close(self, sessions: Dict):
        live = list(sessions.values())
        sessions.clear()

        def close():
            with TraceAnnotation("client.close_session"):
                for s in live:
                    s.close()
        return self.pool.submit(close)

    @staticmethod
    def _finish(rec: Record, now: float, status: str, req=None, error=""):
        rec.done = now
        rec.status = status
        rec.error = error
        if req is not None:
            rec.submitted = req._submit_t
            if req.first_token_s > 0:
                rec.first = req._submit_t + req.first_token_s
                rec.admit_start = rec.first - req.prefill_s
            rec.output = np.asarray(req.output_ids or [], np.int32)
            rec.prefix_hit = req.prefix_hit_tokens
            if req.error is not None:
                rec.error = repr(req.error)

    def close(self):
        self.pool.shutdown(wait=True)
