"""The focusing program's share of its roofline, closed-loop cells (moves
scenes_per_s): the least time the chip needs for the window's scenes
(``work.least_seconds``: nominal operations over the peak FLOP/s, or one
read of the echo and one write of the image over the peak bandwidth,
whichever is longer) over the time the chip was busy with the focusing
program. Host-device copies are not device operations in the trace, so
they are not in the busy time."""
from sarbench import work


def read(run):
    if run.trace is None or run.window.scenes == 0:
        return None
    busy = run.trace.busy_seconds()
    if busy <= 0.0:
        return None
    least, _ = work.least_seconds(run.scene.na, run.scene.nr, run.peak)
    return 100.0 * run.window.scenes * least / busy
