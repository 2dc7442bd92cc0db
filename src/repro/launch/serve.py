"""Serving launcher: two workloads behind one front door.

* ``--workload lm`` (the default): batched prefill + greedy decode on
  local devices —

      PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \\
          --reduced --batch 4 --prompt-len 32 --gen 16

* ``--workload graph``: the online GraphSAGE serving engine
  (``repro.serving``) under synthetic multi-tenant traffic — concurrent
  callers with zipf-skewed seed popularity enqueue into the
  size-or-deadline ``RequestQueue``, every drain fuses the pending
  requests into ONE ``aggregate_multi`` SSD command block (tenant-tagged
  segments scatter results back to their callers), the hot-vertex cache
  absorbs repeat self-row lookups, and the run closes with the engine's
  health snapshot (finds-per-query, StepMonitor stats, cache hit rate) —

      PYTHONPATH=src python -m repro.launch.serve --workload graph \\
          --requests 48 --tenants 4 --cache 32 --batch 8
"""

from __future__ import annotations

import argparse
import sys
import time


def _main_lm(args) -> int:
    if not args.arch:
        print("--workload lm requires --arch", file=sys.stderr)
        return 2

    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.common.schema import init_params
    from repro.models import transformer as T
    from repro.train import make_decode_step, make_prefill_step

    cfg = configs.smoke_config(args.arch) if args.reduced else configs.get_config(args.arch)
    cache_len = args.prompt_len + args.gen
    key = jax.random.PRNGKey(0)
    params = init_params(T.model_schema(cfg, max_seq=cache_len), key)

    batch = {"tokens": jax.random.randint(key, (args.batch, args.prompt_len),
                                          0, cfg.vocab)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(key, (args.batch, cfg.enc_seq, cfg.d_model))
    if cfg.vision_seq:
        batch["vision"] = jax.random.normal(key, (args.batch, cfg.vision_seq, cfg.d_model))

    prefill = jax.jit(make_prefill_step(cfg, cache_len=cache_len))
    decode = jax.jit(make_decode_step(cfg))

    t0 = time.perf_counter()
    logits, caches = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill: {args.batch}×{args.prompt_len} tokens in "
          f"{t_prefill * 1e3:.1f} ms")

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        logits, caches = decode(params, tok, caches, pos)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    toks = args.batch * (args.gen - 1)
    print(f"decode: {toks} tokens in {dt * 1e3:.1f} ms "
          f"({toks / max(dt, 1e-9):.1f} tok/s batch, "
          f"{dt * 1e3 / max(args.gen - 1, 1):.2f} ms/step)")
    gen = jnp.concatenate(out_tokens, axis=1)
    print("generated ids[0]:", gen[0].tolist())
    return 0


def _main_graph(args) -> int:
    import numpy as np

    from repro.graph import uniform_graph
    from repro.serving import ServingEngine

    rng = np.random.default_rng(args.seed)
    V = args.vertices
    g = uniform_graph(V, args.degree * V, seed=args.seed,
                      n_features=args.features)
    indptr, indices, _ = g.to_csr()

    eng = ServingEngine(g.features, indptr, indices, fanout=args.fanout,
                        max_batch=args.batch,
                        max_delay_s=args.max_delay_ms / 1e3,
                        cache_capacity=args.cache, sample_seed=args.seed)
    print(f"graph serving: V={V} E={args.degree * V} F={args.features} "
          f"fanout={args.fanout} | batch={args.batch} "
          f"deadline={args.max_delay_ms}ms cache={args.cache} "
          f"tenants={args.tenants}")

    # zipf-skewed seed popularity over a permuted rank order — the hot-set
    # concentration the hot-vertex cache exploits (I-GCN's islandization)
    order = rng.permutation(V)
    p = np.empty(V)
    p[order] = 1.0 / (np.arange(V) + 1.0)
    p /= p.sum()

    served = 0
    per_tenant = [0] * args.tenants
    t0 = time.perf_counter()
    for i in range(args.requests):
        n_seeds = int(rng.integers(1, 4))
        seeds = rng.choice(V, n_seeds, p=p)
        tenant = i % args.tenants
        eng.submit(seeds, tenant=tenant)
        per_tenant[tenant] += 1
        served += eng.poll()          # dispatches when size/deadline fires
    served += eng.flush()
    dt = time.perf_counter() - t0

    snap = eng.health_snapshot()
    stats = snap["stats"]
    print(f"served {served}/{args.requests} requests "
          f"({', '.join(f't{t}:{n}' for t, n in enumerate(per_tenant))}) "
          f"in {dt * 1e3:.1f} ms")
    print(f"command blocks: {stats['command_blocks']} "
          f"({stats['queries'] / max(stats['command_blocks'], 1):.1f} "
          f"queries/block) | finds: {stats['find']} "
          f"({snap['finds_per_query']:.3f}/query vs 1.000 naive)")
    if "cache" in snap:
        c = snap["cache"]
        print(f"hot cache: {c['hits']}/{c['hits'] + c['misses']} lookups hit "
              f"(rate {c['hit_rate']:.2f}), {c['resident']}/{c['capacity']} "
              f"rows resident, {c['evictions']} evictions")
    mon = snap["monitor"]
    print(f"health: {mon['steps']} dispatches recorded "
          f"({mon['flagged']} flagged), ewma "
          f"{mon['ewma_s'] * 1e3:.1f} ms/dispatch, "
          f"queue depth {snap['queue_depth']}")
    return 0 if served == args.requests else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "graph"), default="lm")
    # lm workload
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="lm: prefill batch; graph: queue max_batch")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    # graph workload
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--vertices", type=int, default=256)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--fanout", type=int, default=10)
    ap.add_argument("--cache", type=int, default=32,
                    help="hot-vertex cache capacity (0 disables)")
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    return _main_graph(args) if args.workload == "graph" else _main_lm(args)


if __name__ == "__main__":
    sys.exit(main())
