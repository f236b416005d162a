"""Host orchestration per round (sim/engine.py).

Self time of the engine's host-only spans in the window (``mar_decisions``:
events, pricing and MAR decisions; ``bank_flush``; ``record_rounds``), per
round completed.  None of them holds a child span, so self time is the
span's length.
"""

SPANS = ("mar_decisions", "bank_flush", "record_rounds")


def read(win):
    if not win.rounds:
        return None
    us = sum(e["dur"] for e in win.spans if e["name"] in SPANS)
    return us / 1e3 / win.rounds
