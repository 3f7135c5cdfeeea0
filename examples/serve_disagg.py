"""Phase-disaggregated serving on the REAL engines: prefill replicas run
prompts to the first token, decode replicas carry generation to
completion, and each request's KV cache is extracted / transferred /
installed between them (DistServe-style, README §Disaggregated serving).

    PYTHONPATH=src python examples/serve_disagg.py \\
        [--n 8] [--rate 8.0] [--n-prefill 1] [--n-decode 1] [--paged]

``--unchunked`` switches the prefill replicas from SARATHI chunked
prefills (the *hybrid* mode) to whole-prompt prefills (classic
disaggregation).  Greedy token outputs are bit-identical to the
monolithic engine either way — the handoff is a pure cache relocation.

(Monolithic counterparts: serve_online.py / serve_offline.py.)
"""
import argparse
import os

from repro.configs import list_archs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list_archs())
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--n-prefill", type=int, default=1)
    ap.add_argument("--n-decode", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4, help="per replica")
    ap.add_argument("--unchunked", action="store_true",
                    help="whole-prompt prefill replicas (DistServe mode; "
                         "default is chunked = hybrid mode)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pools (handoff moves block contents)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel chips per replica")
    ap.add_argument("--hw", default="a100-80gb",
                    help="hardware profile pricing the KV-transfer term")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    n_dev = (args.n_prefill + args.n_decode) * args.tp
    if n_dev > 1:
        # must land before the first jax call locks the device count
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={n_dev}")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ReplicaSet, format_table, online_workload
    from repro.sim.hardware import PROFILES

    cfg = get_config(args.arch).reduced()
    params = build_model(cfg).init_params(jax.random.PRNGKey(args.seed))
    reqs = online_workload(args.n, rate=args.rate, pd_ratio=8.0,
                           min_len=16, max_len=64,
                           vocab_size=cfg.vocab_size, seed=args.seed)

    rs = ReplicaSet(cfg, params, n_prefill=args.n_prefill,
                    n_decode=args.n_decode,
                    prefill_chunked=not args.unchunked,
                    chunk_size=args.chunk, n_slots=args.slots,
                    max_len=512, max_prompt_len=64, paged=args.paged,
                    block_size=args.block_size, prefill_tp=args.tp,
                    decode_tp=args.tp, hw=PROFILES[args.hw.lower()],
                    seed=args.seed)
    res = rs.run(reqs)

    mode = "disagg" if args.unchunked else "hybrid"
    util = res.replica_utilization()
    print(f"mode={mode} prefill={args.n_prefill} decode={args.n_decode} "
          f"handoffs={res.n_handoffs} "
          f"kv_moved={res.kv_transfer_bytes / 1e6:.2f}MB "
          f"kv_transfer={res.kv_transfer_time * 1e3:.3f}ms "
          f"preemptions={res.n_preemptions}")
    print("replica utilization: "
          + " ".join(f"{k}={v:.0%}" for k, v in util.items()))
    print(format_table(res.summary(), unit="ms"))
    for h in res.handoffs:
        print(f"  handoff req {h.req_id}: {h.src} -> {h.dst} "
              f"tokens={h.n_tokens} blocks={h.n_blocks} "
              f"bytes={h.n_bytes / 1e3:.1f}KB delay={h.delay * 1e6:.1f}us")


if __name__ == "__main__":
    main()
