"""Serving launcher.

Host mode (default): run a reduced config end-to-end on local devices.
Production mode (--dry-run): lower + compile the serve step (decode /
hybrid) for the 16x16 or 2x16x16 mesh without allocation.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b \
        --dry-run --shape decode_32k [--multi-pod]
"""
import argparse


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--variant", default="")
    ap.add_argument("--policy", default="sarathi")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)

    if args.dry_run:
        import os
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.dry_run:
        from repro.launch.dryrun import run_one
        run_one(args.arch, args.shape, args.multi_pod, args.variant)
        return

    import jax
    from repro.configs import get_config
    from repro.data import serving_workload
    from repro.models import build_model
    from repro.scheduler import Request
    from repro.serving import Server

    cfg = get_config(args.arch, variant=args.variant).reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    wl = serving_workload(args.n_requests, pd_ratio=8.0, min_len=16,
                          max_len=48, vocab_size=cfg.vocab_size)
    reqs = []
    for p, d in wl:
        r = Request(prompt=p, max_new_tokens=d)
        if model.needs_memory:
            r.memory = jax.random.normal(
                jax.random.PRNGKey(r.req_id),
                (cfg.n_frontend_tokens, cfg.d_model)) * 0.02
        reqs.append(r)
    srv = Server(cfg, params, policy=args.policy, chunk_size=args.chunk,
                 n_slots=4, max_len=256, max_prompt_len=64)
    res = srv.run(reqs)
    toks = res.total_prefill_tokens + res.total_decode_tokens
    print(f"served {len(reqs)} requests, {toks} tokens, "
          f"{len(res.iterations)} iterations "
          f"({sum(1 for s in res.iterations if s.n_prefill_tokens and s.n_decode_tokens)} decode-maximal)")
    for rid, out in sorted(res.outputs.items()):
        print(f"  req {rid}: {out[:8]}{'...' if len(out) > 8 else ''}")


if __name__ == "__main__":
    main()
