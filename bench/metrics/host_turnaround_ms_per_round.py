"""The host's turnaround between dispatch blocks, per round (sim/engine.py,
core/server.py).

For each ``block_exec`` span in the window, the latest ``loss_sync`` span
that ends before it, with no other ``block_exec`` between them, marks the
moment the host knew the previous block program had finished.  From that
``loss_sync``'s end to the ``block_exec``'s end, the host works and no block
program of the job is queued: it records the rounds, runs the boundary and
the MAR decisions, builds the bank carry, looks up the shard pack, places
the inputs and enqueues the program.  The stretches are summed and divided
by the rounds completed in the window.

The stretch before the window's first ``block_exec`` is left out: its
``loss_sync`` falls before the window.  The definition assumes unfenced
spans (the benchmark's tracer does not fence): a fenced ``block_exec``
would end with the program and hold device time.

A program without ``loss_sync`` spans has nothing to read; the metric is
then left out.
"""

SYNC, EXEC = "loss_sync", "block_exec"


def read(win):
    if not win.rounds:
        return None
    sync_end, stretches = None, []
    for e in sorted((e for e in win.spans if e["name"] in (SYNC, EXEC)),
                    key=lambda e: e["ts"]):
        end = e["ts"] + e["dur"]
        if e["name"] == SYNC:
            sync_end = end
        else:
            if sync_end is not None and sync_end <= e["ts"]:
                stretches.append(end - sync_end)
            sync_end = None
    if not stretches:
        return None
    return sum(stretches) / 1e3 / win.rounds
