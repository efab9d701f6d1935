"""Roofline share of the fingerprint kernel in the traced serving call (%).

Bytes: what the admission validation must cover, from shapes (the
configuration's `flops` module): for each packed prefill launch (one
`prefill_pack` span), each replica and each row of the compiled pack, the
row's logits and its cache rows. The per-tick validation of the token vector (a few
hundred bytes) is left out of the bytes and not of the time. Time: the
device time of every `sedar_fingerprint` op in the trace. Bound: HBM
bandwidth (a hash reads each byte once and computes little)."""


def _pack_rows(n):
    """Rows of the compiled pack that holds n prompts (a power of two)."""
    k = 1
    while k < n:
        k *= 2
    return k


def read(d):
    t = d.reduction.op_seconds("sedar_fingerprint")
    packs = [a.get("pack", 1) for name, _, _, a in d.spans
             if name == "prefill_pack"]
    if not t or not packs:
        return None
    need = sum(d.flops.prefill_lane_bytes(
        d.cfg, d.max_len, _pack_rows(int(n)), d.replicas) for n in packs)
    return 100.0 * need / t / (d.peak["hbm_bytes_per_s"] * d.chips)
