"""Online continuous serving on the REAL engine: Poisson arrivals drive the
token-budget (sarathi_serve) scheduler; per-request TTFT / TBT / queueing
delay are measured on the wall clock and summarised as percentiles.

    PYTHONPATH=src python examples/serve_online.py \
        [--arch tinyllama-1.1b] [--n 8] [--rate 8.0] [--policy sarathi_serve]

``--pp N`` serves on the pipeline-parallel engine instead: the layer stack
is partitioned over N stages (forced host devices on CPU — the script sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` when unset, which
is why jax is imported only after argument parsing), up to N micro-batches
are in flight, and the summary gains a per-stage bubble line.  ``--tp M``
makes the engine (or each stage) tensor-parallel over M chips — pp*tp
devices total; token outputs are bit-identical at tp=1 and tolerance-tier
equivalent at tp>1 (README §Tensor-parallel x pipeline-parallel).

(Offline counterpart — static request list, no clock: serve_offline.py.)
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
    ap.add_argument("--policy", default="sarathi_serve",
                    choices=["sarathi_serve", "sarathi", "orca"])
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--budget", type=int, default=None,
                    help="token budget (default chunk + decode slots)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV block pool (repro.cache)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="pool size (default: dense-equivalent capacity; "
                         "shrink to exercise preemption)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share committed prompt blocks across requests "
                         "(requires --paged; README §Prefix caching)")
    ap.add_argument("--shared-frac", type=float, default=0.5,
                    help="with --prefix-cache: fraction of each prompt "
                         "drawn from a common system prefix")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages (1 = single device)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel chips (per stage with --pp; "
                         "pp*tp devices total)")
    args = ap.parse_args()

    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache requires --paged (shared blocks live in "
                 "the block pool)")
    if args.prefix_cache and args.policy != "sarathi_serve":
        ap.error("--prefix-cache requires --policy sarathi_serve")

    if args.pp * args.tp > 1:
        # must land before the first jax call locks the device count
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.pp * args.tp}")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import (OnlineServer, format_table, online_workload,
                               shared_prefix_workload)

    cfg = get_config(args.arch).reduced()
    params = build_model(cfg).init_params(jax.random.PRNGKey(args.seed))

    if args.prefix_cache:
        # a workload the cache can actually hit: one system prefix per
        # group, unique user tails
        shared = int(48 * args.shared_frac) // args.block_size \
            * args.block_size
        reqs = shared_prefix_workload(args.n, shared_len=shared,
                                      unique_len=max(48 - shared, 1),
                                      n_decode=8, rate=args.rate,
                                      vocab_size=cfg.vocab_size,
                                      seed=args.seed)
    else:
        reqs = online_workload(args.n, rate=args.rate, pd_ratio=8.0,
                               min_len=16, max_len=64,
                               vocab_size=cfg.vocab_size, seed=args.seed)
    srv = OnlineServer(cfg, params, policy=args.policy,
                       chunk_size=args.chunk, n_slots=args.slots,
                       token_budget=args.budget, max_len=512,
                       max_prompt_len=64, paged=args.paged,
                       block_size=args.block_size, n_blocks=args.n_blocks,
                       pp=args.pp, tp=args.tp,
                       prefix_cache=args.prefix_cache)
    res = srv.run(reqs)

    hybrid = sum(1 for it in res.iterations
                 if it.n_prefill_tokens and it.n_decode_tokens)
    print(f"policy={args.policy} rate={args.rate:g}/s "
          f"iterations={len(res.iterations)} hybrid={hybrid}"
          + (f" paged(bs={args.block_size}, "
             f"blocks={srv.engine.block_manager.n_blocks}, "
             f"util mean={res.mean_pool_util:.0%} "
             f"peak={res.peak_pool_util:.0%}, "
             f"preemptions={res.n_preemptions})" if args.paged else ""))
    if res.pipeline is not None:
        st = res.pipeline
        print(f"pp={st.pp} tp={st.tp} microbatches={st.n_microbatches} "
              f"bubble={st.bubble_fraction:.1%} "
              f"stage_busy=[{', '.join(f'{b:.2f}s' for b in st.stage_busy)}]")
    print(format_table(res.summary(), unit="ms"))
    for rid in sorted(res.traces):
        t = res.traces[rid]
        print(f"  req {rid}: arrive={t.arrival:7.3f}s "
              f"queue={(t.queue_delay or 0) * 1e3:7.1f}ms "
              f"ttft={(t.ttft or 0) * 1e3:7.1f}ms "
              f"tokens={t.n_tokens}")


if __name__ == "__main__":
    main()
