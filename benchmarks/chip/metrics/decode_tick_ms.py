"""decode_tick_ms: host-synced time of the window's decode ticks over their
count, classified as for prefill_round_ms."""

import harness


def read(rec):
    return harness.load_module(
        harness.HERE / "metrics" / "prefill_round_ms.py").read(rec, "decode")
