"""Share of the traced window in which no operation ran on the chip,
closed-loop cells (moves scenes_per_s). Read from the device trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share()
