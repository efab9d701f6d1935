"""Mean host time of one deferred flush (ms): the program's
`deferred_flush` spans in the traced serving call, each one readback of a
window's commit predicates with its parked tokens (every `validate_lag`
ticks). The device idles for the part of it after its queue drains."""


def read(d):
    flushes = [e - s for name, s, e, _ in d.spans if name == "deferred_flush"]
    if not flushes:
        return None
    return 1e-6 * sum(flushes) / len(flushes)
