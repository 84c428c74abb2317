"""itl_p90_ms: 90th percentile of every gap between consecutive output
tokens of a request, over all such gaps in the window (a token's time is
the end of the scheduling round that emitted it). Not the 95th: below the
knee 2-6% of the gaps, by the order of arrivals, span prefill rounds that
stall decoding, so a 95th percentile flips between a decode tick and a
stall from seed to seed."""

import harness


def read(rec):
    reqs = rec.events.get("requests")
    if reqs is None:
        return None
    lo, hi = rec.window
    gaps = []
    for r in reqs:
        ts = [t for t in r["tokens"] if lo <= t <= hi]
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    return 1e3 * harness.percentile(gaps, 90) if gaps else None
