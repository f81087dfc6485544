"""Engine admission: 90th percentile over requests due before the traced
span of the time each waited in the engine's own queue, from the
engine's stamps (``admitted`` less ``submitted``), ms. Unlike
``queue_wait_p90_ms`` it leaves out how late the load generator
submitted: per request, the time to first token from when it was due is
that lateness, this wait and the prefill (``prefill_done`` less
``admitted``). A program that stamps no ``admitted`` reads nothing."""
from chipbench.percentiles import percentile


def read(ctx):
    if ctx.backlog:
        return None
    waits = [s.er.admitted - s.er.submitted for s in ctx.host_due()
             if s.er is not None and getattr(s.er, "admitted", 0.0)]
    return percentile(waits, 0.90) * 1e3 if waits else None
