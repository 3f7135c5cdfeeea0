#!/usr/bin/env python3
"""Smoke run of the SARATHI serving path on TPU at Granite-8B widths.

    python chip_smoke.py              # one chip: xla and pallas backends
    python chip_smoke.py --chips 4    # tensor-parallel phase only

Builds ``granite-8b`` at its published widths (d_model 4096, 32 query and
8 KV heads of 128, d_ff 14336, vocab 49152) in bf16 with random weights
from ``--seed``, cut from 36 to 16 layers so the weights and a 4,097-block
paged KV pool fit one 16 GB chip.  Eight requests (prompts of 1,000-3,000
tokens, 32 output tokens each) go through ``OnlineServer`` with the
``sarathi_serve`` policy, so chunked prefills and piggybacked decodes both
run, once per paged attention backend (``xla``, then ``pallas``) in this
one process.  For one prompt the packed step's prefill logits are checked
against the model's plain batched forward in float32 at ``highest``
matmul precision.  Every projection carries its published bias, drawn
nonzero, so the check sees one the served path drops.

``--chips 4`` runs only the tensor-parallel phase: the same config served
at ``tp=4`` on both backends, its prefill logits checked against ``tp=1``
on device 0 of the same host.

Every line but the last is smoke output, not a benchmark number.  The
last line is ``{"ok": true, "device": {...}}``; it is printed only when
every check passed.  Without a TPU the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import List, Optional, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# libtpu logs to /tmp unless told otherwise; the smoke writes nothing
# outside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import ChunkWork, IterationPlan, plan_chunks  # noqa: E402
from repro.kernels.ops import resolve_interpret  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.scheduler import Request  # noqa: E402
from repro.serving import OnlineServer  # noqa: E402

ARCH = "granite-8b"
# Granite-8B-Code's biases: Q, K, V and O (``attention_bias``), gate, up
# and down (``mlp_bias``)
BIASES = ("bq", "bk", "bv", "bo", "b_gate", "b_up", "b_down")
# 36 bf16 layers are 36 x 436 MB = 15.7 GB of weights alone; 16 layers
# (7.0 GB) plus the tied embedding (0.4 GB) and the KV pool (4.3 GB) fit
# 16 GB
DEPTH = 16


@dataclasses.dataclass(frozen=True)
class Scale:
    """Serving geometry and traffic of one smoke run."""
    serve_kw: dict
    n_requests: int
    prompt_lens: tuple
    new_tokens: int


# n_slots=16 at max_len=4096 gives the engine's default 16 * 256 + 1 =
# 4,097-block pool of 16-token blocks (65,552 tokens)
FULL = Scale(serve_kw=dict(chunk_size=256, n_slots=16, max_len=4096,
                           paged=True, block_size=16),
             n_requests=8, prompt_lens=(1000, 3000), new_tokens=32)

# Relative L2 error bounds on one prompt's last-position logits:
# - served bf16 step vs the float32 forward: the served path rounds every
#   activation and matmul output to bf16 (8-bit mantissa, 2^-9 relative
#   step); over 16 layers of ~10 roundings each, independent errors add
#   in quadrature to about 1e-2 of the logits' norm — 5e-2 leaves 5x room
#   while a wrong mask, table or head mapping moves the logits by O(1);
# - xla vs pallas backend, and tp=4 vs tp=1: both bf16, they differ only
#   in accumulation order (kernel flash steps, all-reduce partial sums),
#   each rounding once more in bf16 — bounded by the same 5e-2.
TOL_VS_F32 = 5e-2
TOL_BF16_PAIR = 5e-2

_BACKEND_KNOB = "REPRO_PAGED_ATTN_BACKEND"


class SmokeFailure(RuntimeError):
    """A smoke check failed."""


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def smoke_config(depth: int = DEPTH):
    """Granite-8B at published widths, cut in depth only."""
    return dataclasses.replace(get_config(ARCH), n_layers=depth)


def init_params(cfg, seed: int, dtype=jnp.bfloat16, out_shardings=None):
    """Random weights from ``seed``, generated on the device under jit.
    The model's init leaves every projection bias at zero; here each is
    drawn with std 0.1, so the logit checks see a bias that is dropped."""
    model = build_model(cfg)

    def make(key):
        params = model.init_params(key, dtype)
        leaves, tree = jax.tree_util.tree_flatten_with_path(params)
        keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            (0.1 * jax.random.normal(k, a.shape)).astype(a.dtype)
            if getattr(p[-1], "key", None) in BIASES else a
            for k, (p, a) in zip(keys, leaves)])

    fn = jax.jit(make, out_shardings=out_shardings)
    return jax.block_until_ready(fn(jax.random.PRNGKey(seed)))


def make_requests(cfg, seed: int, scale: Scale = FULL) -> List[Request]:
    """Fresh requests, all arriving at t=0, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    lo, hi = scale.prompt_lens
    lens = rng.integers(lo, hi + 1, scale.n_requests)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, int(L)).tolist(),
                    max_new_tokens=scale.new_tokens, req_id=i)
            for i, L in enumerate(lens)]


def check_prompt_of(cfg, seed: int, scale: Scale = FULL) -> List[int]:
    """The prompt of the logit check: the shortest served prompt."""
    return min((r.prompt for r in make_requests(cfg, seed, scale)), key=len)


@contextlib.contextmanager
def paged_backend(name: str):
    saved = os.environ.get(_BACKEND_KNOB)
    os.environ[_BACKEND_KNOB] = name
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop(_BACKEND_KNOB)
        else:
            os.environ[_BACKEND_KNOB] = saved


def prefill_logits(engine, prompt: Sequence[int], req_id: int = -1):
    """Prefill ``prompt`` chunk by chunk through the engine's packed step;
    returns the final chunk's last-position logits as float32."""
    engine.add_request(req_id)
    try:
        for c in plan_chunks(len(prompt), engine.C):
            engine.execute(IterationPlan(chunk=ChunkWork(
                req_id, prompt[c.start:c.start + c.length], c.start,
                c.is_last)))
        return np.asarray(engine.chunk_logits[0], np.float32)
    finally:
        engine.release(req_id)


def reference_logits(cfg, params, prompt: Sequence[int]):
    """The model's plain batched forward in float32 at ``highest`` matmul
    precision: the bf16 weights are exact in f32, and an f32 embedding
    makes every activation (and every matmul, by promotion) f32."""
    model = build_model(cfg)
    ref_params = dict(params, embed=params["embed"].astype(jnp.float32))
    fwd = jax.jit(lambda p, t: model.forward_batched(
        p, t, logits_mode="last")[0])
    with jax.default_matmul_precision("highest"):
        out = fwd(ref_params, jnp.asarray(prompt, jnp.int32)[None])
    return np.asarray(out[0], np.float32)


def rel_error(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check_close(what: str, got, want, tol: float) -> float:
    err = rel_error(got, want)
    agree = int(np.argmax(got)) == int(np.argmax(want))
    say(f"logits {what}: rel_l2={err!r} (limit {tol}) "
        f"max_abs={float(np.max(np.abs(got - want)))!r} "
        f"argmax_agrees={agree}")
    if not err <= tol:
        raise SmokeFailure(f"logits {what}: rel_l2 {err} > {tol}")
    return err


@dataclasses.dataclass
class PhaseResult:
    backend: str
    tp: int
    warmup_s: float           # both packed-step shapes: compile + one run
    steps: int
    hybrid_steps: int
    prefill_tokens: int
    decode_tokens: int
    interpret: bool           # resolve_interpret() during the phase
    has_kernel: bool          # tpu_custom_call in the compiled step
    logits: np.ndarray


def _free(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.delete()


def serve_phase(cfg, params, requests: Sequence[Request],
                check_prompt: Sequence[int], *, backend: str, tp: int = 1,
                devices=None, dtype=jnp.bfloat16,
                scale: Scale = FULL) -> PhaseResult:
    """Serve ``requests`` through ``OnlineServer`` on one paged attention
    backend, then take ``check_prompt``'s prefill logits from the same
    engine.  With no requests only the logits are taken."""
    with paged_backend(backend):
        interpret = resolve_interpret()
        srv = OnlineServer(cfg, params, policy="sarathi_serve", dtype=dtype,
                           tp=tp, devices=devices, **scale.serve_kw)
        eng = srv.engine
        try:
            warmup_s, steps = 0.0, []
            if requests:
                t0 = time.perf_counter()
                srv.executor.warmup()
                jax.block_until_ready(eng.cache)
                warmup_s = time.perf_counter() - t0
                res = srv.run(requests, warmup=False)
                short = {r.req_id: len(res.outputs.get(r.req_id, []))
                         for r in requests
                         if len(res.outputs.get(r.req_id, []))
                         < r.max_new_tokens}
                if short:
                    raise SmokeFailure(
                        f"{backend}: requests finished short "
                        f"(req: tokens) {short}")
                steps = res.iterations
            logits = prefill_logits(eng, check_prompt)
            # the compiled hybrid step (shared with the call above)
            hlo = eng._step.lower(eng.params,
                                  eng._pack(None, [], pad_chunk=True),
                                  eng.cache, eng._key).compile().as_text()
            return PhaseResult(
                backend=backend, tp=tp, warmup_s=warmup_s, steps=len(steps),
                hybrid_steps=sum(1 for s in steps
                                 if s.n_prefill_tokens and s.n_decode_tokens),
                prefill_tokens=sum(s.n_prefill_tokens for s in steps),
                decode_tokens=sum(s.n_decode_tokens for s in steps),
                interpret=interpret,
                has_kernel="tpu_custom_call" in hlo, logits=logits)
        finally:
            _free(eng.cache)


def report(r: PhaseResult) -> None:
    say(f"phase backend={r.backend} tp={r.tp}: warmup (compile + run of "
        f"both step shapes) {r.warmup_s:.1f} s; served {r.steps} steps "
        f"({r.hybrid_steps} hybrid), {r.prefill_tokens} prefill + "
        f"{r.decode_tokens} decode tokens; interpret={r.interpret} "
        f"tpu_custom_call={r.has_kernel}")


def require_native_kernels(r: PhaseResult) -> None:
    if r.interpret:
        raise SmokeFailure("pallas phase resolved interpret mode on a TPU "
                           "(is REPRO_PALLAS_INTERPRET set?)")
    if not r.has_kernel:
        raise SmokeFailure("pallas phase's compiled step has no "
                           "tpu_custom_call")


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def one_chip(cfg, seed: int, scale: Scale = FULL) -> dict:
    """Both backends on the first device, each checked against the f32
    forward and against each other; returns {backend: PhaseResult}."""
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    say(f"params initialised on device in {time.perf_counter() - t0:.1f} s")
    check_prompt = check_prompt_of(cfg, seed, scale)
    results = {}
    for backend in ("xla", "pallas"):
        r = serve_phase(cfg, params, make_requests(cfg, seed, scale),
                        check_prompt, backend=backend, scale=scale)
        report(r)
        if dev.platform != "cpu":
            say(f"peak_bytes_in_use after {backend}: {peak_bytes(dev)}")
        results[backend] = r
    want = reference_logits(cfg, params, check_prompt)
    say(f"logit check on a {len(check_prompt)}-token prompt")
    for backend, r in results.items():
        check_close(f"{backend} vs f32", r.logits, want, TOL_VS_F32)
    check_close("pallas vs xla", results["pallas"].logits,
                results["xla"].logits, TOL_BF16_PAIR)
    return results


def tp_shardings(cfg, mesh):
    from jax.sharding import NamedSharding
    from repro.sharding import policy
    shapes = jax.eval_shape(
        lambda k: build_model(cfg).init_params(k, jnp.bfloat16),
        jax.random.PRNGKey(0))
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        policy.param_pspecs(cfg, shapes, mesh=mesh))


def param_devices(params) -> set:
    return {d for leaf in jax.tree.leaves(params) for d in leaf.devices()}


def four_chips(cfg, seed: int, scale: Scale = FULL, tp: int = 4) -> dict:
    """tp=1 logits on device 0, then both backends served at ``tp`` over
    the first ``tp`` devices and checked against them; returns
    {backend: PhaseResult} of the tp runs."""
    from jax.sharding import SingleDeviceSharding
    from repro import sharding as shd
    devs = jax.devices()[:tp]
    check_prompt = check_prompt_of(cfg, seed, scale)

    params1 = init_params(cfg, seed,
                          out_shardings=SingleDeviceSharding(devs[0]))
    base = serve_phase(cfg, params1, [], check_prompt, backend="xla",
                       devices=[devs[0]], scale=scale)
    _free(params1)
    say(f"tp=1 reference logits on {devs[0]}")

    mesh = shd.make_tp_mesh(tp, devs)
    params = init_params(cfg, seed, out_shardings=tp_shardings(cfg, mesh))
    placed = param_devices(params)
    w = params["groups"][0]["ffn"]["w_gate"]
    shard_devs = {s.device for s in w.addressable_shards}
    if len(placed) != tp or len(shard_devs) != tp:
        raise SmokeFailure(f"tp={tp} params landed on {len(placed)} "
                           f"device(s), w_gate on {len(shard_devs)}")
    say(f"tp={tp} params on {len(placed)} distinct devices; w_gate shard "
        f"{w.addressable_shards[0].data.shape} of {w.shape}")
    results = {}
    for backend in ("xla", "pallas"):
        r = serve_phase(cfg, params, make_requests(cfg, seed, scale),
                        check_prompt, backend=backend, tp=tp, devices=devs,
                        scale=scale)
        report(r)
        check_close(f"tp={tp} {backend} vs tp=1", r.logits, base.logits,
                    TOL_BF16_PAIR)
        results[backend] = r
    if devs[0].platform != "cpu":
        for d in devs:
            say(f"{d}: peak_bytes_in_use={peak_bytes(d)} "
                f"bytes_in_use={int(d.memory_stats()['bytes_in_use'])}")
    return results


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    cfg = smoke_config()
    say(f"device_kind={dev.device_kind} devices={len(devices)}")
    say(f"{cfg.name} cut from {get_config(ARCH).n_layers} to {cfg.n_layers} "
        f"layers (depth only; widths as published): 36 bf16 layers alone "
        f"need ~15.7 GB of a 16 GB chip")
    t0 = time.perf_counter()
    if args.chips == 4:
        results = four_chips(cfg, args.seed)
    else:
        results = one_chip(cfg, args.seed)
    require_native_kernels(results["pallas"])
    n_cached = sum(1 for p in Path(cache_dir).rglob("*") if p.is_file()) \
        if Path(cache_dir).is_dir() else 0
    say(f"compile cache {cache_dir}: {n_cached} files; total "
        f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
