"""The whole dispatch step's share of the chip's peak.

Operations the rounds completed in the window require (forward and
backward of every sample a real member trained on, and the master's
teacher forward on every sample of a KD slave; padded capacity rows do not
count), over the traced window's length times the chip's bf16 peak.  fp32
convolutions at default precision run as one bf16 pass on the MXU, so the
bf16 peak bounds this work.
"""


def read(win):
    if not win.rounds or win.seconds <= 0:
        return None
    return 100.0 * win.flops / (win.seconds * win.peaks["bf16_flops_per_s"])
