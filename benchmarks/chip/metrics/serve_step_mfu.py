"""serve_step_mfu: the whole serve step's share of the chips' bf16 peak:
the model FLOPs of the tokens the window's rounds advanced (the
configuration's count per token and tier: the forward pass, plus the
variance contraction for approximate-tier rows) over the rounds'
host-synced time and chips x peak."""


def read(rec):
    lo, hi = rec.window
    rounds = [r for r in rec.events.get("rounds", ())
              if r[0] >= lo and r[1] <= hi]
    if not rounds:
        return None
    t = sum(b - a for a, b, *_ in rounds)
    flops = sum(r[4] for r in rounds)
    return 100.0 * flops / t / (rec.chips * rec.peak["bf16_flops"])
