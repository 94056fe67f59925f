"""The pointer init kernel's (`fused_local_phase`) share of its roofline.

The least time of one call is the HBM bytes of its input (the
ghost-extended block) and of its int32 pointer output, each counted once,
at the chip's HBM peak: the kernel does a few compares per byte, so bytes
bound it.  The bytes come from the shapes and dtypes in the call's own HLO
text, where the compiler also says which buffers it placed in HBM; a call
whose buffers all sit in on-chip memory has no HBM roofline and is left
out.  The share is the least time of every call in the window over the
device time of those calls (trace events named
``fused_local_phase_<mode>``).
"""
import devtrace

PATTERN = r"^%fused_local_phase_(manifold|cc)\b"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    least = spent = 0.0
    for name, seconds in ctx.trace.op_events(PATTERN):
        b = devtrace.hbm_bytes(name)
        if b:
            least += b / ctx.peak["hbm_bytes_per_s"]
            spent += seconds
    if spent <= 0:
        return None
    return 100.0 * least / spent
