"""ttft_p50_ms: median, over every request due in the window, of
the time from its due time to its first output token; a request with no
first token by the end of the window counts the time from its due time to
the window's end. The median, because a window holds about twenty
requests at this cell's rate: the highest percentile with about ten
requests beyond it."""

import harness


def read(rec):
    reqs = rec.events.get("requests")
    if not reqs:
        return None
    hi = rec.window[1]
    ttft = [(r["tokens"][0] if r["tokens"] and r["tokens"][0] <= hi else hi)
            - r["due"] for r in reqs if r["due"] <= hi]
    return 1e3 * harness.percentile(ttft, 50)
