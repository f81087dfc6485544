"""Kernel ``flash_attention`` (Pallas ``_flash_fwd_kernel``, in the trace
``%flash_attention_fwd.N``, a ``tpu_custom_call``): the least
time the chip needs for causal attention over the prompts prefilled
inside the traced span, at their real length (``counts.
flash_attention_cost``), over the device time of the kernel's ops in the
trace, %. Bound by compute at these lengths."""
from chipbench import counts, devtrace

NAMES = ("flash_attention_fwd",)


def read(ctx):
    if not ctx.events or not ctx.peaks:
        return None
    pre = [p for s in ctx.traced_steps() for p in s.prefills]
    secs, calls = devtrace.kernel_seconds(ctx.events, NAMES)
    if not pre or not calls or secs <= 0:
        return None
    dims = ctx.cell.family.attention_dims(ctx.conf)
    need = sum(counts.roofline_seconds(
        *counts.flash_attention_cost(dims, ctx.conf, len(p.req.prompt)),
        ctx.peaks) for p in pre)
    return 100.0 * need / secs
