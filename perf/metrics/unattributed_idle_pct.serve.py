"""Share of the traced serving call in which the device idles while no
program span is open on the host (%): the idle seconds that
`trace_reduce` gives to `(no host span)`, over the call's length. Host
work that a later change adds outside every span shows up here; 0 when
every idle stretch falls in some span."""
from perf import trace_reduce


def read(d):
    red = d.reduction
    if not red.devices:
        return None
    return 100.0 * red.idle_by_span_s.get(trace_reduce.NO_SPAN, 0.0) \
        / red.window_s
