"""Host milliseconds per step in the scheduler: the ``next_plan`` and
``on_tokens`` spans of every traced step."""


def read(r):
    steps = [s for s in r.traced_steps()
             if {"next_plan", "on_tokens"} <= set(r.spans[s.idx])]
    if not steps:
        return None
    return 1e3 * sum(r.span_s(s, "next_plan") + r.span_s(s, "on_tokens")
                     for s in steps) / len(steps)
