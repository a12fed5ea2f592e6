"""Share of the chip's busy time spent converting between complex64 scenes
and the kernels' two f32 planes, closed-loop cells (moves scenes_per_s):
device operations under a ``split`` or ``unsplit`` scope, and the
compiler's own unpacking of the program's complex argument, over the
union of all device operations. Read from the device trace
(``scopes.ScopedTrace``); None where the traced program does not name
its plan steps."""
from sarbench import scopes


def read(run):
    st = scopes.for_run(run)
    if st is None or not st.names_steps():
        return None
    busy = st.busy_seconds()
    if busy <= 0.0:
        return None
    return 100.0 * st.glue_seconds() / busy
