"""Share of the traced serving call in which the device runs the packed
prefill program (%): the device time of its launches (line `XLA Modules`
of the trace, program `jit_fn`, the jitted `BucketedPrefill._packed_fn`)
over the call's length. Admission work that delays every running
stream's next token."""

PREFILL_PROGRAMS = ("jit_fn",)


def read(d):
    t = sum(d.reduction.programs_s.get(p, 0.0) for p in PREFILL_PROGRAMS)
    if not t:
        return None
    return 100.0 * t / d.reduction.window_s
