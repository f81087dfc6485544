"""Model step: operations the decode steps before the traced span need
(linear layers and logits per active slot, and what the model's family
adds, such as attention over each slot's live length;
``counts.decode_step_flops``) over their summed time (the
engine's ``iteration_log``) times the chip's bf16 peak, %."""
from chipbench import counts


def read(ctx):
    steps = [s for s in ctx.host_steps() if s.decode_s > 0]
    if not steps or not ctx.peaks:
        return None
    family = ctx.cell.family
    flops = sum(counts.decode_step_flops(family, ctx.conf, s.live)
                for s in steps)
    secs = sum(s.decode_s for s in steps)
    return 100.0 * flops / (secs * ctx.peaks["bf16_flops_per_s"])
