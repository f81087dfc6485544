"""Model step: operations the prompts prefilled before the traced span
need, at their real length and not the padded bucket (the model
family's ``prefill_flops``), over the summed prefill and insert time
(the engine's ``iteration_log``) times the chip's bf16 peak, %."""


def read(ctx):
    pre = [p for s in ctx.host_steps() for p in s.prefills]
    if not pre or not ctx.peaks:
        return None
    flops = sum(ctx.cell.family.prefill_flops(ctx.conf, len(p.req.prompt))
                for p in pre)
    secs = sum(p.prefill_s for p in pre)
    return 100.0 * flops / (secs * ctx.peaks["bf16_flops_per_s"])
