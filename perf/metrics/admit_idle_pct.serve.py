"""Share of the traced serving call in which the device idles while the
host admits requests (%): the idle seconds given to the program's `admit`
spans (the first-token readback, the scatter into the slots, the admission
snapshots, the release of one-token budgets) and to the `prefill_pack`
launches nested in them, over the call's length. A program that names no
serving stage (no `serve_start` span) gives nothing to read."""

SPANS = ("admit", "prefill_pack")


def read(d):
    if not d.reduction.devices or not any(
            name == "serve_start" for name, _, _, _ in d.spans):
        return None
    idle = d.reduction.idle_by_span_s
    return 100.0 * sum(idle.get(s, 0.0) for s in SPANS) \
        / d.reduction.window_s
