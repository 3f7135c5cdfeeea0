"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the requests the program finished (drawn from the seed, the longest
always in it) goes through the configuration's plain reference: one
forward over the prompt and the served tokens, in float32 at ``highest``
precision, from weights rebuilt from the seed.  At each served position
the reference's best logit is compared with the logit of the token the
program served there; the widest such gap over the sample is the number
compared.  Served tokens are greedy, so a program that computes what the
reference computes serves the reference's best token up to rounding, and
the gap stays at the size of bf16 rounding.

The control puts the reference, computed one precision step lower
(float8 matmul inputs), in the program's place: at the same positions the
gap of the token it ranks first.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.driver import Log

ALIGN = 256
# sequences are padded to a multiple of BUCKET: a few compiled shapes, and
# a short prompt pays for a short forward
BUCKET = 2048


def pick(logs: dict, seed: int, k: int, w0: float, w1: float) -> List[Log]:
    """Up to ``k`` finished requests, preferring those finished in the
    window, drawn from ``seed``; the longest is always among them."""
    done = [g for g in logs.values()
            if g.finished is not None and not g.rejected
            and len(g.output) == g.job.max_new_tokens]
    in_win = [g for g in done if w0 <= g.finished <= w1]
    pool = sorted(in_win if len(in_win) >= k else done,
                  key=lambda g: g.job.req_id)
    if not pool:
        return []
    longest = max(pool, key=lambda g: (len(g.job.prompt) + len(g.output),
                                       -g.job.req_id))
    rest = [g for g in pool if g is not longest]
    rng = np.random.default_rng([abs(int(seed)), 0xC4EC])
    idx = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(idx)]


def pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


class Reference:
    """The configuration's reference at padded shapes: sequences padded to
    a multiple of ``BUCKET`` up to ``t_pad``, served positions to
    ``p_pad``."""

    def __init__(self, ref_mod, hf: dict, make_weights, seed: int,
                 t_pad: int, p_pad: int):
        self.t_pad = pad_to(t_pad, ALIGN)
        self.p_pad = p_pad
        self.w = make_weights(seed)

        def served(w, toks, pos, served_tok):
            lg = ref_mod.logits_at(w, hf, toks, pos)
            best = lg.max(-1)
            got = jnp.take_along_axis(lg, served_tok[:, None], 1)[:, 0]
            return best - got

        def control(w, toks, pos, served_tok):
            lg = ref_mod.logits_at(w, hf, toks, pos)
            lo = ref_mod.logits_at(w, hf, toks, pos, control=True)
            best = lg.max(-1)
            top = jnp.argmax(lo, -1)
            got = jnp.take_along_axis(lg, top[:, None], 1)[:, 0]
            return best - got

        self._served = jax.jit(served)
        self._control = jax.jit(control)

    def _args(self, g: Log):
        prompt, out = list(g.job.prompt), list(g.output)
        seq = prompt + out[:-1]
        n = len(out)
        if len(seq) > self.t_pad or n > self.p_pad:
            raise ValueError(f"request {g.job.req_id} ({len(seq)} tokens, "
                             f"{n} served) exceeds the reference's shapes")
        toks = np.zeros((min(pad_to(len(seq), BUCKET), self.t_pad),),
                        np.int32)
        toks[:len(seq)] = seq
        pos = np.full((self.p_pad,), len(seq) - 1, np.int32)
        pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        served = np.zeros((self.p_pad,), np.int32)
        served[:n] = out
        return n, (jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(served))

    def gaps(self, g: Log) -> np.ndarray:
        """Reference best minus the served token's logit, per position."""
        n, a = self._args(g)
        return np.asarray(self._served(self.w, *a))[:n]

    def control_gaps(self, g: Log) -> np.ndarray:
        """Reference best minus the logit of the control's first token."""
        n, a = self._args(g)
        return np.asarray(self._control(self.w, *a))[:n]

    def free(self):
        for a in jax.tree.leaves(self.w):
            a.delete()
