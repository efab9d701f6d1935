"""Model FLOP utilization of the traced serving call (%): the model FLOPs
of every completed request's prefill and decode tokens, counted once (not
per replica, no padding) by the configuration's `flops` module, over the
call's length on the trace's clock, over the chips' bf16 peak. Work that
the call redoes or that protection adds does not count."""


def read(d):
    if not d.work or not d.reduction.devices:
        return None
    peak = d.peak["bf16_flops_per_s"] * d.chips
    flops = d.flops.serving_flops(d.cfg, d.work)
    return 100.0 * flops / d.reduction.window_s / peak
