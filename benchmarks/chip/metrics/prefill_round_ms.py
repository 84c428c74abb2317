"""prefill_round_ms: host-synced time of the window's prefill rounds over
their count. Each Server.run(max_steps=1) is classified by the change in
Server.stats (prefill_rounds or decode_ticks); its time includes the
round's admission (slot reset) and the host's scheduling."""


def read(rec, kind="prefill"):
    lo, hi = rec.window
    rounds = [b - a for a, b, k, *_ in rec.events.get("rounds", ())
              if k == kind and a >= lo and b <= hi]
    return 1e3 * sum(rounds) / len(rounds) if rounds else None
