"""Share of the held-expert rows that did useful work in the traced serving
call (%): the (token, expert) routes of real tokens that landed on experts
held here, over the expert rows the held-expert matmuls ran (pads, dummy
pack rows and inactive slots included), prefill and decode together. The
program counts both on the device and reads them back once, with the
call's final flush, as args of its `serve_finish` span. A program that
counts no experts gives nothing to read."""

PHASES = ("prefill", "decode")


def read(d):
    for name, _, _, args in d.spans:
        if name != "serve_finish" or "moe_rows_decode" not in args:
            continue
        rows = sum(args[f"moe_rows_{p}"] for p in PHASES)
        held = sum(args[f"moe_routes_held_{p}"] for p in PHASES)
        return 100.0 * held / rows if rows else None
    return None
