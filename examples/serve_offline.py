"""End-to-end serving driver: a batch of requests with the paper's workload
shape (Zipf lengths, fixed P:D) served under each scheduling policy, with
correctness cross-checks and per-policy iteration statistics.

    PYTHONPATH=src python examples/serve_offline.py \
        [--arch tinyllama-1.1b] [--n 12] [--policy all] [--chunk 16]

For ONLINE serving — timestamped arrivals, the token-budget sarathi_serve
scheduler, and TTFT/TBT percentile metrics — see examples/serve_online.py.
"""
import argparse
import time

import jax

from repro.configs import get_config, list_archs
from repro.data import serving_workload
from repro.models import build_model
from repro.scheduler import Request
from repro.serving import Server


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list_archs())
    ap.add_argument("--n", type=int, default=12)
    ap.add_argument("--policy", default="all",
                    choices=["all", "sarathi", "orca", "request_level"])
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    wl = serving_workload(args.n, pd_ratio=8.0, min_len=16, max_len=64,
                          vocab_size=cfg.vocab_size, seed=args.seed)

    policies = (["sarathi", "orca", "request_level"]
                if args.policy == "all" else [args.policy])
    outputs = {}
    for policy in policies:
        reqs = [Request(prompt=p, max_new_tokens=d) for p, d in wl]
        if model.needs_memory:
            for r in reqs:
                r.memory = jax.random.normal(
                    jax.random.PRNGKey(r.req_id),
                    (cfg.n_frontend_tokens, cfg.d_model)) * 0.02
        srv = Server(cfg, params, policy=policy, chunk_size=args.chunk,
                     n_slots=args.slots, max_len=512, max_prompt_len=64)
        t0 = time.perf_counter()
        res = srv.run(reqs)
        dt = time.perf_counter() - t0
        toks = res.total_prefill_tokens + res.total_decode_tokens
        mixed = sum(1 for s in res.iterations
                    if s.n_prefill_tokens and s.n_decode_tokens)
        print(f"{policy:14s} iters={len(res.iterations):4d} "
              f"hybrid_iters={mixed:4d} tokens={toks:5d} "
              f"wall={dt:6.2f}s tok/s={toks / dt:8.1f}")
        outputs[policy] = [tuple(res.outputs[r.req_id]) for r in reqs]

    if len(outputs) > 1:
        base = outputs[policies[0]]
        for p in policies[1:]:
            assert outputs[p] == base, f"{p} output != {policies[0]}"
        print("all policies produced IDENTICAL greedy outputs "
              "(chunked-prefill equivalence, paper Fig. 6)")


if __name__ == "__main__":
    main()
