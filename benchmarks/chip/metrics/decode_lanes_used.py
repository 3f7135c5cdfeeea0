"""Share of the engine's decode lanes that carry a real decode, mean over
the window's steps, counted from the scheduler's plans."""


def read(r):
    if not r.steps:
        return None
    return 100.0 * sum(len(s.decodes) for s in r.steps) \
        / (len(r.steps) * r.decode_lanes)
