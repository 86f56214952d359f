"""What one run measured, and the end-to-end metrics taken from it.

``Run`` holds the client's records, the window, the server's counters at
the window's two ends and, in a traced run, the reduced trace. The
end-to-end metrics are computed here from the client's records on the
host clock; each per-layer metric has a reader of its own in
``metrics/<name>.py`` that takes a ``Run``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import manifest
from generator import percentile


@dataclasses.dataclass
class Run:
    records: List
    t0: float                 # window start (perf_counter)
    seconds: float
    setup_s: float
    spec: Dict
    chips: int
    stats: Dict               # server stats at "start" / "end" of window
    kind: str                 # device kind
    ref: object = None        # the configuration's reference module
    trace: Optional[object] = None
    span: Optional[tuple] = None   # traced span on the host clock

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    @property
    def measured(self) -> List:
        return [r for r in self.records if r.measured]

    @property
    def dims(self) -> Dict[str, int]:
        return self.ref.dims(self.spec)

    @property
    def tied(self) -> bool:
        return self.spec["tie_word_embeddings"]

    @property
    def peaks(self) -> Dict:
        return manifest.peaks(self.kind)

    @property
    def stopped(self) -> float:
        """When the client stopped waiting: its last record's end."""
        return max(r.done for r in self.records)

    def delta(self, key: str) -> float:
        return self.stats["end"][key] - self.stats["start"][key]

    def attach_trace(self, trace_dir, tracer):
        import trace_reduce
        if tracer.span is None:
            raise RuntimeError(f"no trace was taken: {tracer.error}")
        self.span = tracer.span
        self.trace = trace_reduce.reduce(trace_dir, self.span)

    @property
    def busy_s(self) -> float:
        return self.trace.busy_s

    @property
    def window_s(self) -> float:
        return self.trace.window_s

    def breakdown(self) -> Dict:
        return {"device_ops": self.trace.top_ops(10),
                "idle_gaps": self.trace.top_gaps(10)}

    # ---- the work inside the traced span, from the client's records ------
    def admissions_in_span(self):
        """(start, stop) prompt positions prefilled by each admission that
        began and ended inside the traced span: the prompt tokens past the
        prefix cache's hit. An admission that straddles an edge is left
        out, so the work counted never exceeds the work the span's device
        time covers."""
        a, b = self.span
        out = []
        for r in self.records:
            if r.first and r.admit_start >= a and r.first <= b:
                out.append((r.prefix_hit, len(r.prompt)))
        return out

    def decode_steps_in_span(self):
        """(steps, contexts): sequence-steps of decode whose emitted token
        falls inside the traced span, and the sum of their contexts. A
        turn's tokens after its first are taken as evenly spaced between
        its first token and its completion (they come in chunks that all
        slots share)."""
        a, b = self.span
        steps = contexts = 0
        for r in self.records:
            n = len(r.output) if r.output is not None else 0
            if not r.ok or n < 2 or r.done <= r.first:
                continue
            dt = (r.done - r.first) / (n - 1)
            # token i (2..n) comes at first + (i - 1) * dt and was decoded
            # over a context of P + i - 1 positions
            lo = max(2, int(-(-(a - r.first) // dt)) + 1)
            hi = min(n, int((b - r.first) // dt) + 1)
            if hi < lo:
                continue
            P = len(r.prompt)
            k = hi - lo + 1
            steps += k
            contexts += k * (P - 1) + (lo + hi) * k // 2
        return steps, contexts


def _ttft(run: Run) -> float:
    """A turn that failed or never finished counts as waiting for its
    first token until the client stopped waiting."""
    vals = [(r.first if r.ok else run.stopped) - r.due for r in run.measured]
    return percentile(vals, 95)


def _tpot(run: Run) -> float:
    """A turn that failed or never finished counts as taking from its
    first token (or its due time, without one) until the client stopped
    waiting for all the tokens it asked for."""
    vals = [((r.done - r.first) / (len(r.output) - 1) if r.ok
             else (run.stopped - (r.first or r.due)) / max(1, r.max_new - 1))
            * 1e3
            for r in run.measured if not r.ok or len(r.output) > 1]
    return percentile(vals, 95)


def tokens_in(r, a: float, b: float) -> float:
    """Output tokens of turn ``r`` that came inside [a, b): its first at
    ``first``, the rest evenly spaced up to its completion."""
    n = len(r.output) if r.output is not None else 0
    if not r.ok or n == 0:
        return 0.0
    got = 1.0 if a <= r.first < b else 0.0
    if n > 1 and r.done > r.first:
        lo, hi = max(a, r.first), min(b, r.done)
        if hi > lo:
            got += (n - 1) * (hi - lo) / (r.done - r.first)
    return got


def _output_tok_s(run: Run) -> float:
    return sum(tokens_in(r, run.t0, run.t1)
               for r in run.records) / run.seconds


END_TO_END = {
    "ttft_p95_s": _ttft,
    "tpot_p95_ms": _tpot,
    "output_tok_s": _output_tok_s,
    "setup_s": lambda run: run.setup_s,
}
