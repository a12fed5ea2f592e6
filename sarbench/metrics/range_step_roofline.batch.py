"""The range step's share of its roofline, closed-loop cells (moves
scenes_per_s): the least time the chip needs for the nominal work of the
window's range steps over the device seconds under the program's
``range_comp_rcmc`` step scope (``scopes.ScopedTrace.seconds_by_step``).

The nominal work of one scene's range step (``fused3``'s range FFT,
matched filter, RCMC phase and IFFT) is that of the transform, whatever
its radix: 5 N log2 N operations for each of a forward and an inverse
FFT of na lines of nr samples (``work.fft_ops``), 6 a sample for each of
its two filter multiplies, and one read and one write of the complex64
scene, 16 na nr bytes. The least time is the larger of operations over
the peak FLOP/s and bytes over the peak bandwidth. None where the traced
program does not name its plan steps, or ran no range step."""
from sarbench import scopes, work

STEP = "range_comp_rcmc"
FILTERS = 2


def step_ops(na: int, nr: int) -> float:
    return (2 * na * work.fft_ops(nr)
            + FILTERS * work.OPS_PER_FILTER_SAMPLE * na * nr)


def step_bytes(na: int, nr: int) -> float:
    return float(work.BYTES_PER_SAMPLE * na * nr)


def least_seconds(na: int, nr: int, peak: dict) -> float:
    return max(step_ops(na, nr) / peak["flops_per_s"],
               step_bytes(na, nr) / peak["hbm_bytes_per_s"])


def read(run):
    st = scopes.for_run(run)
    if st is None or not st.names_steps() or run.window.scenes == 0:
        return None
    seconds = st.seconds_by_step().get(STEP, 0.0)
    if seconds <= 0.0:
        return None
    least = least_seconds(run.scene.na, run.scene.nr, run.peak)
    return 100.0 * run.window.scenes * least / seconds
