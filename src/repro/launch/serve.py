"""Serving launcher: continuous-batching engine over a synthetic stream.

Drives :class:`repro.serve.engine.Engine` with open-loop Poisson arrivals
(exponential inter-arrival gaps measured in engine iterations — the
deterministic analogue of wall-clock arrivals) and mixed prompt/generation
lengths, then prints throughput + slot-utilization stats.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --policy bf16_sr_kahan --slots 16 --rate 0.5 --requests 64

Paged KV pool + chunked prefill (token-granular memory; more lanes per
byte on mixed-length traffic, bounded TTFT on long prompts):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --paged --page-size 16 --slots 16 --n-pages 24 --prefill-chunk 8

Stochastic sampling (deterministic per (seed, rid); greedy is the
default and stays bitwise-parity with ``generate``). With ``--paged``
the prompt-prefix cache is on by default (``--no-prefix-cache`` to
disable):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --paged --temperature 0.8 --top-k 40 --top-p 0.95 --sample-seed 7

On a mesh (8 virtual devices: 4 data × 2 model, KV pool sharded on both):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --data-parallel 4 --model-parallel 2 --slots 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.policy import get_policy
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import registry as R
from repro.serve.engine import Engine


def synthetic_stream(rng: np.random.Generator, n_requests: int, *,
                     rate: float, prompt_lens: tuple[int, int],
                     gen_lens: tuple[int, int], vocab: int):
    """(arrival_step, prompt, max_new) triples with Poisson arrivals.

    ``rate`` is requests per engine iteration; prompt/generation lengths
    are drawn uniformly from their (lo, hi) ranges — the mixed-length
    traffic that makes static batching pay for its stragglers.
    """
    t = 0.0
    out = []
    for _ in range(n_requests):
        t += rng.exponential(1.0 / max(rate, 1e-9))
        s0 = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        gen = int(rng.integers(gen_lens[0], gen_lens[1] + 1))
        prompt = rng.integers(0, vocab, size=s0).astype(np.int32)
        out.append((int(t), prompt, gen))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default="bf16_sr")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrival rate, requests per engine step")
    ap.add_argument("--prompt-lens", type=int, nargs=2, default=(4, 12))
    ap.add_argument("--gen-lens", type=int, nargs=2, default=(4, 48))
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="mesh data-axis size (0 = no mesh)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--fused-decode", action="store_true",
                    help="decode attention via the fused Pallas kernel "
                         "(one launch per lane, parked lanes skipped "
                         "in-kernel); token parity with the generic path")
    ap.add_argument("--paged", action="store_true",
                    help="back full-context attention layers with the "
                         "paged KV pool (token-granular allocation via a "
                         "per-lane block table); token parity with the "
                         "contiguous pool")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (with --paged)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="pool pages (default slots*ceil(max_len/page): "
                         "byte parity with the contiguous pool; lower it "
                         "to oversubscribe lanes per byte)")
    ap.add_argument("--prefill-chunk", type=int, default=1,
                    help="prompt tokens admitted per engine iteration "
                         "(>1 = chunked prefill, interleaved with decode)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for every request "
                         "(0 = greedy, the bitwise-parity path)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k largest logits (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base sampling seed; each token's key is "
                         "fold_in(fold_in(seed, rid), position), so runs "
                         "and preemption-recomputes are reproducible")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prompt-prefix page sharing (with "
                         "--paged it is on by default for attention-only "
                         "full-context stacks)")
    args = ap.parse_args()

    enable_compile_cache()
    policy = get_policy(args.policy)
    cfg = R.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)

    mesh = None
    if args.data_parallel:
        mesh = make_local_mesh(args.data_parallel, args.model_parallel)
    engine = Engine(params, cfg, policy, n_slots=args.slots,
                    max_len=args.max_len, mesh=mesh, eos_id=args.eos_id,
                    fused_decode=args.fused_decode, paged=args.paged,
                    page_size=args.page_size, n_pages=args.n_pages,
                    prefill_chunk=args.prefill_chunk,
                    prefix_cache=False if args.no_prefix_cache else None)

    rng = np.random.default_rng(args.seed)
    # every request must fit the pool: clamp generation lengths to what the
    # longest prompt leaves room for, and reject impossible flag combos
    hi = min(args.gen_lens[1], args.max_len - args.prompt_lens[1])
    if hi < 1:
        ap.error(f"--max-len {args.max_len} leaves no room to generate "
                 f"after a {args.prompt_lens[1]}-token prompt; raise "
                 f"--max-len or lower --prompt-lens")
    stream = synthetic_stream(rng, args.requests, rate=args.rate,
                              prompt_lens=tuple(args.prompt_lens),
                              gen_lens=(min(args.gen_lens[0], hi), hi),
                              vocab=cfg.vocab)
    paged_desc = (f"paged page={args.page_size} pages={engine.pool.n_pages} "
                  if args.paged else "contiguous ")
    print(f"[serve] {args.arch} policy={policy.name} slots={args.slots} "
          f"max_len={args.max_len} kv_dtype={np.dtype(engine.pool.dtype).name} "
          f"{paged_desc}pool={engine.pool.nbytes() / 2**20:.1f} MiB "
          f"chunk={args.prefill_chunk} "
          f"mesh={'x'.join(map(str, mesh.devices.shape)) if mesh else 'none'}")

    t0 = time.time()
    completions, queued = [], 0
    latencies, ttfts = [], []
    arrivals: dict[int, int] = {}
    while queued < len(stream) or engine.has_work():
        while queued < len(stream) and stream[queued][0] <= engine.stats.steps:
            arrive, prompt, gen = stream[queued]
            arrivals[engine.submit(
                prompt, gen, temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p,
                seed=args.sample_seed)] = arrive
            queued += 1
        if not engine.has_work():      # open-loop gap: idle until next arrival
            engine.stats.steps += 1
            engine.stats.slot_steps += engine.pool.n_slots
            continue
        for c in engine.step():
            completions.append(c)
            latencies.append(c.finished_step - c.admitted_step)
            ttfts.append(c.first_token_step - arrivals[c.rid])
    dt = time.time() - t0

    st = engine.stats
    print(f"[serve] {st.finished}/{args.requests} finished in {st.steps} "
          f"steps ({dt:.2f}s incl. compile)")
    print(f"[serve] {st.tokens_generated} tokens generated → "
          f"{st.tokens_generated / dt:.1f} tok/s; KV utilization "
          f"{st.utilization:.1%} (live tokens / pool capacity); lane "
          f"occupancy {st.lane_occupancy:.1%} (prefill share "
          f"{st.prefill_slot_steps / max(st.active_slot_steps, 1):.1%})")
    if args.paged:
        print(f"[serve] pages: {engine.pool.n_pages} total, "
              f"{st.kv_pages_live} live at drain; "
              f"{st.preemptions} preemptions")
        if engine.prefix_cache:
            print(f"[serve] prefix cache: {st.prefix_hits} hits, "
                  f"{st.prefix_tokens_reused} prefill tokens skipped; "
                  f"{engine.pool.n_cached_pages} pages indexed at drain")
    if latencies:
        lat, tf = np.asarray(latencies), np.asarray(ttfts)
        print(f"[serve] latency (engine steps): p50={np.percentile(lat, 50):.0f} "
              f"p95={np.percentile(lat, 95):.0f} max={lat.max()}; "
              f"TTFT p50={np.percentile(tf, 50):.0f} "
              f"p99={np.percentile(tf, 99):.0f}")
    for c in completions[:4]:
        print(f"  rid={c.rid} {c.finish_reason:6s} prompt={c.prompt.size:3d} "
              f"gen={c.tokens.size:3d} tokens={c.tokens[:8].tolist()}…")


if __name__ == "__main__":
    main()
