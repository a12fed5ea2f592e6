"""Share of the window the closed loop spent copying echoes to the chip
and images back (its ``h2d`` and ``d2h`` spans, host clock); moves
scenes_per_s."""


def read(run):
    if run.window.seconds <= 0.0:
        return None
    return 100.0 * run.spans.total("h2d", "d2h") / run.window.seconds
