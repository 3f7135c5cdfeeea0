"""The least bytes that the traced decode-only steps need (weights once,
the keys and values of the real contexts, the new positions written) over
their device busy time times the chips' HBM bandwidth."""
from chipbench import counts


def read(r):
    steps = [s for s in r.traced_steps() if not s.chunks and s.decodes]
    busy = sum(r.device_s(s) for s in steps)
    if not steps or busy <= 0:
        return None
    need = sum(counts.decode_step_bytes(r.terms, s.decodes) for s in steps)
    return 100.0 * need / (busy * r.peaks["hbm_bytes_per_s"] * r.chips)
