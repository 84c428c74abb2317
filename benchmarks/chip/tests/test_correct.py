"""The comparison that decides `correct`, driven through the whole run path
on the CPU (the look for a chip skipped), at sizes a test run holds:

- the unbroken program reads as correct;
- the control (the reference one precision lower, in the program's place)
  fails one of the cell's numbers;
- each fault the cell can have, planted in the timed path, reads as not
  correct.

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests/test_correct.py
"""
from __future__ import annotations

import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import harness  # noqa: E402
import run  # noqa: E402

sys.path.insert(0, str(harness.SRC))

# xlstm-125m's pattern at small widths.
XLSTM_SMALL = {"n_layers": 4, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
               "d_head": 32, "vocab": 256, "slots": 4}
# The serving limit at that size, from readings on the CPU: the program's
# mean logit gap 0.0015, the fp8 control's 0.029 (a 4 s window at 4
# requests/s). The cell's own limit, at published widths, is in
# cells/xlstm125m-chat.tiers.json, from chip readings.
XLSTM_SMALL_LIMITS = {"logit_gap_mean": 0.01}
SEED = 3_000_000_017  # above 2**31, as a run's seed may be
SECONDS = 4.0
CELL = "xlstm125m-chat.tiers"


def _cell():
    cell = harness.load_cell(CELL)
    cell.config.update(XLSTM_SMALL)
    cell.traffic.update({"rate_per_s": 4.0, "check_requests": 8})
    cell.limits = dict(XLSTM_SMALL_LIMITS)
    return cell


def _run(cell) -> dict:
    return run.run_cell(cell.name, SEED, SECONDS, False, require_chip=False,
                        cell=cell)


def _readings(cell):
    import jax

    rec = cell.driver.run(cell, jax.devices()[:1], seed=SEED,
                          seconds=SECONDS, trace=False, t_start=0.0,
                          control=True)
    return rec.counters["compared"], rec.counters["control"]


def _fails_one(numbers: dict, limits: dict) -> bool:
    return any(numbers[n] > v for n, v in limits.items())


def test_serve_program_is_correct():
    out = _run(_cell())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_serve_control_is_not_correct():
    cell = _cell()
    program, control = _readings(cell)
    assert not _fails_one(program, cell.limits), program
    assert _fails_one(control, cell.limits), control


def test_serve_token_altered_is_not_correct(monkeypatch):
    from repro.launch import serve

    cell = _cell()
    emit = serve.Server._emit

    def altered(self, i, tok):
        if len(self.active[i].out) % 4 == 3:  # every fourth token
            tok = (tok + 1) % cell.config["vocab"]
        return emit(self, i, tok)

    monkeypatch.setattr(serve.Server, "_emit", altered)
    out = _run(cell)
    assert not out["correct"], out["checks"]
