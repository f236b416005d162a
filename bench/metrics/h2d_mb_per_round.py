"""Host-to-device bytes per round (core/server.py shard packs, masks,
weights), from the program's ``fl/h2d_bytes`` counter over the window."""

COUNTER = "fl/h2d_bytes"


def read(win):
    if not win.rounds:
        return None
    moved = (win.after["counters"].get(COUNTER, 0.0)
             - win.before["counters"].get(COUNTER, 0.0))
    return moved / 1e6 / win.rounds
