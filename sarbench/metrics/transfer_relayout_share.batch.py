"""Share of the closed loop's copy spans (``h2d`` and ``d2h``) in which
some host thread of the runtime converts between the host's layout and
the chip's, closed-loop cells (moves scenes_per_s): row-major into tiled
on the way in (``Linearize``); on the way out, tiled back to row-major
(``Transpose::ExecuteChunk``) and a complex64's two f32 halves joined into
interleaved pairs (``X64FromTuple``). The rest of the spans is the DMA
and the runtime's waiting. Read from the profiler trace, on the clock of
the spans; None where the trace holds none of the runtime's copy
events."""
from sarbench import scopes


def read(run):
    st = scopes.for_run(run)
    if st is None or not st.has_host_events(scopes.RELAYOUT
                                            + scopes.TRANSFER):
        return None
    return 100.0 * st.host_share(scopes.RELAYOUT, scopes.COPY_SPANS)
