"""Share of the window in which no operation ran on the device.

Source: the profiler trace; busy time is the union of the intervals of the
device's operations (``bench/tracefile.py``), averaged over the chips.
"""


def read(win):
    if win.seconds <= 0:
        return None
    return 100.0 * (1.0 - win.busy_s / win.seconds)
