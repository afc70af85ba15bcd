"""Share of its memory roofline that the single-function Pallas kernel
(``_faas_kernel``, launched by ``faas_sweep_pallas`` in
``kernels/faas_event_step.py``) reaches.

The least time is the bytes the staged-draw semantics requires of the
window's requests (``kernel_bytes``: draw stacks read once, per-row
inputs and outputs) over the chip's HBM bandwidth; the kernel time is
the summed device duration of the kernel's operations in the trace.  A
window that ran the kernel while the trace shows none of it is an error,
never a silent gap in the line.
"""

from traces import kernel_seconds

KERNEL = "faas_kernel"
LAUNCHER = "faas_sweep_pallas"


def read(ctx):
    nbytes = sum(r["kernel_bytes"].get(KERNEL, 0) for r in ctx.requests)
    if nbytes <= 0:
        return None
    secs = kernel_seconds(ctx.events, KERNEL, LAUNCHER)
    if secs <= 0:
        raise RuntimeError(f"the window's requests ran {KERNEL}, but no operation "
                           "of the trace is it: has the kernel been renamed?")
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / secs
