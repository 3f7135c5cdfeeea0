"""Model operations of the traced steps (real tokens and contexts only)
over their device busy time times the chips' bf16 peak."""
from chipbench import counts


def read(r):
    steps = r.traced_steps()
    busy = sum(r.device_s(s) for s in steps)
    if not steps or busy <= 0:
        return None
    flops = sum(counts.step_flops(r.hf, s.chunks, s.decodes) for s in steps)
    return 100.0 * flops / (busy * r.peaks["bf16_flops_per_s"] * r.chips)
