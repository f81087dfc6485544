"""Kernel ``decode_attention`` (Pallas ``_decode_kernel``, in the trace
``%decode_attention_pallas.N``, a ``tpu_custom_call``): the least
time the chip needs for the decode attention of the steps inside the
traced span (live keys of each active slot only;
``counts.decode_attention_cost``), over the device time of the kernel's
ops in the trace, %. Bound by HBM bandwidth at these shapes."""
from chipbench import counts, devtrace

NAMES = ("decode_attention_pallas",)


def read(ctx):
    if not ctx.events or not ctx.peaks:
        return None
    steps = [s for s in ctx.traced_steps() if s.live]
    secs, calls = devtrace.kernel_seconds(ctx.events, NAMES)
    if not steps or not calls or secs <= 0:
        return None
    dims = ctx.cell.family.attention_dims(ctx.conf)
    need = sum(counts.roofline_seconds(
        *counts.decode_attention_cost(dims, ctx.conf, s.live), ctx.peaks)
        for s in steps)
    return 100.0 * need / secs
