"""Host milliseconds per step in the engine: the ``execute`` span's wall
time less the device's busy time inside it, mean over traced steps."""


def read(r):
    steps = r.traced_steps()
    if not steps:
        return None
    return 1e3 * sum(r.span_s(s, "execute") - r.device_s(s)
                     for s in steps) / len(steps)
