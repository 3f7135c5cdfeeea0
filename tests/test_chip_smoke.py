"""chip_smoke.py's phases at reduced size on the CPU, kernels interpreted.

The script's own run is on a TPU at Granite-8B widths; here the same phase
functions drive a ``.reduced()`` Granite through ``OnlineServer`` on both
paged attention backends, check the logits against the float32 forward at
the script's own tolerances, and run the tp=4 phase on four host devices.
"""
import contextlib
import io
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import chip_smoke as cs  # noqa: E402

TINY = cs.Scale(serve_kw=dict(chunk_size=32, n_slots=4, max_len=256,
                              paged=True, block_size=16),
                n_requests=3, prompt_lens=(40, 90), new_tokens=4)


def _cfg():
    return cs.smoke_config(depth=2).reduced()


def test_one_chip_phases_serve_and_match_f32():
    cfg = _cfg()
    results = cs.one_chip(cfg, seed=0, scale=TINY)
    for backend in ("xla", "pallas"):
        r = results[backend]
        assert r.steps > 0 and r.hybrid_steps > 0   # piggybacking happened
        assert r.decode_tokens > 0
        assert r.interpret                          # CPU: interpret mode
        assert not r.has_kernel                     # no Mosaic kernel on CPU
        assert r.logits.shape == (cfg.vocab_size,)
        assert np.all(np.isfinite(r.logits))
    # on the CPU both backends are the same f32-accumulated math
    assert cs.rel_error(results["pallas"].logits,
                        results["xla"].logits) < cs.TOL_BF16_PAIR
    with pytest.raises(cs.SmokeFailure, match="interpret"):
        cs.require_native_kernels(results["pallas"])


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_four_chip_phase_matches_tp1():
    cfg = _cfg()
    results = cs.four_chips(cfg, seed=1, scale=TINY)
    for r in results.values():
        assert r.tp == 4 and r.steps > 0


def test_check_close_rejects_wrong_logits():
    want = np.linspace(-1.0, 1.0, 64).astype(np.float32)
    cs.check_close("same", want + 1e-4, want, cs.TOL_VS_F32)
    with pytest.raises(cs.SmokeFailure):
        cs.check_close("shifted", np.roll(want, 1), want, cs.TOL_VS_F32)


def test_main_exits_nonzero_without_tpu():
    assert jax.devices()[0].platform != "tpu"
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cs.main([])
    assert rc != 0
    assert '"ok"' not in out.getvalue()
    assert "needs a TPU" in err.getvalue()
