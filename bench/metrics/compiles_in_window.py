"""XLA compiles of the engine's round programs inside the window, from
``FedRAC.compile_stats()`` after the window less before it.  Nothing
should compile there."""


def read(win):
    return float(win.after["compiles"] - win.before["compiles"])
