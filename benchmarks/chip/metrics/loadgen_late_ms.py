"""loadgen_late_ms: 95th percentile, over the requests due in the window,
of submit time - due time: how late the load generator ran (it submits
between scheduling rounds, so a long round makes later arrivals late)."""

import harness


def read(rec):
    lo, hi = rec.window
    late = [late for r, late in zip(rec.events.get("requests", ()),
                                    rec.events.get("late", ()))
            if lo <= r["due"] <= hi]
    return 1e3 * harness.percentile(late, 95) if late else None
