"""From what a run observed to the numbers it reports. JAX-free.

:class:`Observations` is everything a run saw: the client's records, the
/metrics counters at the window's two ends (and, in a traced run, at 2 Hz
in between and at the two ends of the traced stretch), the spans fetched
from /admin/trace, and the child's reduction of the device trace. The
end-to-end metrics are computed here from the client's records alone.
Each per-layer metric has a small reader of its own under
``layer_metrics/``, which is handed the observations and returns a
number, or None where it found nothing to read.

Which requests count: those *due* inside the window. A request that
failed, was shed or was cut is counted in ``failed`` and misses every
latency. Tokens count by the time the client read them.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

# The limits a co-pilot's user feels (slo_share, find_knee.py).
SLO_TTFT_MS = 1000.0
SLO_TPOT_MS = 60.0


def percentile(xs: list, p: float) -> Optional[float]:
    """Nearest rank on the sorted sample. Copied from
    p2p_llm_chat_tpu/loadgen/report.py."""
    if not xs:
        return None
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))]


def ttft_ms(rec) -> Optional[float]:
    """First streamed token read, from when the request was *due*."""
    if not rec.ok:
        return None
    return (rec.chunk_t[0] - rec.due_t) * 1e3


def ttft_from_send_ms(rec) -> Optional[float]:
    if not rec.ok or rec.send_t is None:
        return None
    return (rec.chunk_t[0] - rec.send_t) * 1e3


def tpot_ms(rec) -> Optional[float]:
    """(last token - first chunk) / (tokens - tokens in the first chunk):
    the pace at which a suggestion streams once it has started."""
    if not rec.ok:
        return None
    later = rec.tokens - rec.chunk_tokens[0]
    if later <= 0:
        return None
    return (rec.chunk_t[-1] - rec.chunk_t[0]) * 1e3 / later


def gaps_ms(rec) -> list:
    return [(b - a) * 1e3 for a, b in zip(rec.chunk_t, rec.chunk_t[1:])]


def token_gaps_ms(rec) -> list:
    """One entry per token streamed after the first chunk: the gap before
    its chunk over the tokens the chunk carried (a fused dispatch
    streams its tokens together; each waited a share of the gap)."""
    return [g / n for g, n in zip(gaps_ms(rec), rec.chunk_tokens[1:])
            for _ in range(n)]


@dataclass
class Observations:
    records: list                   # loadgen.Record, every request started
    ramp_s: float
    window_s: float
    cell: object = None             # manifest.Cell
    counters_start: dict = field(default_factory=dict)
    counters_end: dict = field(default_factory=dict)
    samples: list = field(default_factory=list)     # [(t, counters), ...]
    stretch_start: dict = field(default_factory=dict)
    stretch_end: dict = field(default_factory=dict)
    stretch_s: float = 0.0          # wall between the stretch's scrapes
    spans: dict = field(default_factory=dict)       # name -> [dur_ms, ...]
    trace: dict = field(default_factory=dict)       # trace_reduce.reduce()
    device: dict = field(default_factory=dict)      # child's device report
    peaks: dict = field(default_factory=dict)       # this device's row

    @property
    def lo(self) -> float:
        return self.ramp_s

    @property
    def hi(self) -> float:
        return self.ramp_s + self.window_s

    def counted(self) -> list:
        """Requests due inside the window."""
        return [r for r in self.records if self.lo <= r.due_t < self.hi]

    def counted_ok(self) -> list:
        return [r for r in self.counted() if r.ok]

    def tokens_in_window(self) -> int:
        return sum(n for r in self.records
                   for t, n in zip(r.chunk_t, r.chunk_tokens)
                   if self.lo <= t < self.hi)

    def counter_delta(self, name: str, stretch: bool = False
                      ) -> Optional[float]:
        a, b = ((self.stretch_start, self.stretch_end) if stretch
                else (self.counters_start, self.counters_end))
        if name not in a or name not in b:
            return None
        return b[name] - a[name]

    def decode_steps(self, stretch: bool = False) -> Optional[float]:
        """Decode steps taken: fused steps plus the dispatches that were
        not fused (one step each)."""
        ticks = self.counter_delta("serve_decode_ticks_total", stretch)
        fticks = self.counter_delta("decode_fused_ticks_total", stretch)
        fsteps = self.counter_delta("decode_fused_steps_total", stretch)
        if ticks is None or fticks is None or fsteps is None:
            return None
        return fsteps + (ticks - fticks)


def end_to_end(obs: Observations) -> dict:
    """Every end-to-end metric the records support, by name. A run
    reports the ones its cell lists. ``setup_s`` is the parent's."""
    ok = obs.counted_ok()
    ttfts = [x for x in map(ttft_ms, ok) if x is not None]
    tpots = [x for x in map(tpot_ms, ok) if x is not None]
    return {
        "ttft_p50_ms": percentile(ttfts, 50),
        "ttft_p95_ms": percentile(ttfts, 95),
        "tpot_p50_ms": percentile(tpots, 50),
        # Inter-token latency: the median over every token streamed to
        # the requests due in the window (thousands), not over requests
        # (dozens): the one pace that repeats within 1% (PERF.md, PR 22).
        "itl_p50_ms": percentile([g for r in ok for g in token_gaps_ms(r)],
                                 50),
        "out_tok_s": obs.tokens_in_window() / obs.window_s,
    }


def counts(obs: Observations) -> tuple:
    """(attempted, failed)."""
    counted = obs.counted()
    return len(counted), sum(1 for r in counted if not r.ok)
