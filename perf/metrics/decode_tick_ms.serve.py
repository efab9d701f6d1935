"""Mean host time of one protected decode tick (ms): the program's
`decode_tick` spans in the traced serving call. A tick issues the step;
every `validate_lag`-th tick also waits in the deferred flush for the
window's predicates and tokens, so this is the host's pace of ticks."""


def read(d):
    ticks = [e - s for name, s, e, _ in d.spans if name == "decode_tick"]
    if not ticks:
        return None
    return 1e-6 * sum(ticks) / len(ticks)
