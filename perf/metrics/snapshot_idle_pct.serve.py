"""Share of the traced serving call in which the device idles while the
host cuts Tier-0 slot snapshots (%): the idle seconds given to the
program's `slot_snapshot` spans (each slot's cache image sliced and copied
into its ring, at a clean flush edge or at admission), over the call's
length. A program that names its serving stages (a `serve_start` span)
and takes no snapshot, as under the `none` backend, reads 0; one that
names no stage gives nothing to read."""

SPAN = "slot_snapshot"


def read(d):
    if not d.reduction.devices or not any(
            name == "serve_start" for name, _, _, _ in d.spans):
        return None
    return 100.0 * d.reduction.idle_by_span_s.get(SPAN, 0.0) \
        / d.reduction.window_s
