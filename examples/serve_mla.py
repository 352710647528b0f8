"""Continuous-batching MLA serving driver (the paper is an inference paper
— this is the headline example): a Poisson stream of requests with mixed
prompt/generation lengths served from the PAGED latent-KV pool, with
mid-generation admission and the execution scheme re-dispatched every step
on the live (batch, max cache_len) point.

    PYTHONPATH=src python examples/serve_mla.py --requests 10 --max-batch 4
    PYTHONPATH=src python examples/serve_mla.py --platform edge_tpu

The compact latent cache ((D_kvl + D_rope) bytes/token vs 2*H*Dh dense) is
what makes a shared block pool pay off: ~16x more requests fit the same
HBM, and the paged layout stops ragged requests from stranding capacity.

PR 2 adds the serving-side dual of that result — cutting redundant
TOKENS, not just bytes: every prompt here opens with the same
``--shared-prefix-len`` system preamble, and the radix prefix cache
(runtime.prefix_cache) maps those leading blocks to the SAME ref-counted
pool blocks (copy-on-write at the first divergent/partial block), so
only each prompt's un-cached suffix is prefilled — in fixed-size batched
chunks straight into the pool (``--prefill-chunk``: one compiled prefill
shape per chunk size instead of one retrace per prompt length).  Flags:

  --shared-prefix-len N  common preamble tokens (0: fully random prompts)
  --no-prefix-cache      disable block sharing (PR-1 behaviour)
  --prefill-chunk N      batched prefill chunk size (0: per-request prefill)
  --temperature T        sample with temperature T (0: greedy argmax);
  --top-k K              PRNG keys fold (request id, absolute position),
                         so recompute-preemption replay is deterministic

PR 3 closes the loop on the prefill phase itself: the chunked prefill's
chunk-attention can run through the fused paged Pallas kernel
(kernels.mla_prefill — the multi-query sibling of the flash-decode
kernel) instead of materializing the contiguous block-table view in HBM
every chunk:

  --prefill-impl {auto,gather,pallas}
                         'gather' = reference view (what PR 2 shipped);
                         'pallas' = in-place block-table walk, no gather
                         ever written (token-identical, tier-1-gated);
                         'auto' follows --impl ('kernel' -> pallas)
  --impl {ref,kernel}    attention impl for decode AND (via 'auto' above)
                         prefill; on CPU the kernels need the Pallas
                         interpreter: REPRO_PALLAS_INTERPRET=1

PR 4 lifts the single-host restriction — the same engine serves sharded:

  --mesh DPxMP           e.g. '2x2': batch rows (token / block-table /
                         length) shard over 'data', heads over 'model',
                         the latent pool replicates on every device (its
                         compactness is what makes that affordable — the
                         paper's bandwidth argument scaled out; the
                         per-device cache traffic still shrinks by DP).
                         Tokens are identical to single-host serving
                         (tests/test_mesh_paged.py).  On CPU this script
                         forces the virtual device count for you.

PR 5 adds speculative decoding on top of all of it — the paper's
compute-bound-decode finding turned into throughput: a cheap draft
proposes k tokens, the target scores all k+1 positions in ONE forward
(the chunked-prefill machinery at chunk k+1), and rejections are a pure
host-side length rewind.  Emitted tokens are identical to plain decode
under greedy and seeded sampling; only the tokens-per-step ratio moves:

  --spec-k K             draft window (0 = off; composes with --mesh,
                         --prefill-impl, the prefix cache, preemption)
  --draft SPEC           'shallow:N' = self-speculation on the target's
                         own first N layers (weights shared by
                         reference) | 'self' = identity-draft oracle
                         (acceptance is exactly 100%)

PR 8 shrinks the pool itself — the bytes axis of the paper's argument,
quantized: the {ckv|krope} block pool can store int8 (or fp8 where the
jax build has float8_e4m3fn) with per-token-row f32 scales riding the
pool pytree.  Writes quantize in the scatter paths, the Pallas kernels
dequantize in-register while walking the block table (no pool-sized f32
copy ever lands in HBM), and the online softmax rescales by AMLA-style
exponent addition (integer add into the f32 exponent field instead of a
per-element multiply):

  --cache-dtype {bf16,int8,fp8}
                         pool storage dtype.  int8 cuts modeled cache
                         bytes/token to ~0.3x bf16 at DeepSeek shapes
                         (the auto-dispatch crossovers shift
                         accordingly); greedy tokens stay parity with
                         bf16 on the smoke model, and per-dtype
                         logit-error bounds vs the fp32 oracle are gated
                         in tests/test_quant_cache.py.  Requires
                         --prefill-chunk > 0.

PR 7 makes the whole run observable (repro.obs) — spans and metrics:

  --trace PATH           Chrome/Perfetto trace-event JSON: per-request
                         lifecycle spans (arrival -> queued -> prefill ->
                         decode -> finish/preempt) on one track per
                         request + per-step phase spans (schedule /
                         prefill chunks / draft / verify / device_step /
                         host_sample).  Open at https://ui.perfetto.dev.
  --metrics PATH         metrics-registry JSON (counters, gauges,
                         TTFT/TPOT/queue-delay/step-time histograms with
                         p50/p95/p99 + the engine summary) and a printed
                         table.

PR 9 overlaps host and device — ``--engine async`` runs the double-
buffered AsyncPagedMLAEngine: the fused decode+sample step for tick N is
dispatched and the host immediately schedules tick N+1 (admission, block
growth, CoW drain) while the device executes; only the sampled token ids
sync back, one tick later.  Tokens are identical to the synchronous
engine under greedy AND seeded sampling, preemption and speculation
included (tests/test_async_engine.py).

PR 10 makes the stream conversational: DECODE-filled blocks register
into the radix trie as each request crosses a block boundary (a second
turn re-hits its own generation, not just the shared preamble), prefix
matching is token-granular (a hit may end mid-block — the partial block
forks copy-on-write), and requests carry ``SamplingParams`` — including
``n``-way parallel sampling, which prefills once and forks the sequence
n ways through the ref-counted block pool:

  --n N                  parallel samples per request: one prefill, then
                         an n-way copy-on-write fork; children sample
                         with their own rid-folded PRNG keys, so tokens
                         match n independent requests while the prompt
                         blocks are allocated once per group
  --admission {cache_aware,fcfs}
                         'cache_aware' admits the waiting request with
                         the longest cached prefix first (fewest new
                         prefill tokens); 'fcfs' is strict arrival order
  --admission-age-bound N
                         starvation bound: a request bypassed N times is
                         admitted unconditionally next

Serving-flags summary (all compose):

  flag              default   effect
  --requests        10        number of requests in the Poisson stream
  --arrival-rate    0.4       mean requests per decode step (Poisson)
  --seed            0         weight init + sampling PRNG + workload seed
  --platform        ''        hwmodel point auto-dispatch prices against
                              ('' = the attached TPU's own; tpu_v5e on CPU)
  --engine          sync      paged engine: 'sync' | 'async' (overlapped)
  --max-batch       4         decode slots (continuous batching)
  --block-size      8         tokens per pool block
  --num-blocks      48        pool capacity
  --no-prefix-cache off       disable radix block sharing
  --prefill-chunk   16        batched prefill chunk (0 = per-request)
  --prefill-impl    auto      'gather' view vs 'pallas' in-place kernel
  --impl            ref       decode attention: 'ref' | 'kernel'
  --cache-dtype     bf16      pool storage: 'bf16' | 'int8' | 'fp8'
  --temperature     0.0       0 = greedy; else seeded sampling
  --top-k           0         top-k filter when sampling
  --mesh            ''        'DPxMP' sharded serving
  --spec-k          0         speculative decoding draft window
  --draft           shallow:2 draft spec ('shallow:N' | 'self')
  --trace           ''        Perfetto trace-event JSON output path
  --metrics         ''        metrics-registry JSON output path
  --n               1         parallel samples per request (CoW fork)
  --admission       cache_aware  'cache_aware' | 'fcfs' waiting order
  --admission-age-bound 64    cache-aware admission starvation bound
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# --mesh on CPU needs the forced device count set BEFORE jax initializes;
# peek at argv so `python examples/serve_mla.py --mesh 2x2` (or --mesh=2x2)
# just works.
_spec = ""
for _i, _a in enumerate(sys.argv):
    if _a == "--mesh" and _i + 1 < len(sys.argv):
        _spec = sys.argv[_i + 1]
    elif _a.startswith("--mesh="):
        _spec = _a.split("=", 1)[1]
if _spec:
    try:
        _need = 1
        for _d in _spec.lower().replace(",", "x").split("x"):
            _need *= int(_d)
    except ValueError:
        _need = 0
    from repro.envflags import force_host_device_count
    force_host_device_count(_need)

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
import repro.models as models
from repro.core.schemes import auto_dispatch, step_time
from repro.hwmodel.platforms import PLATFORMS, resolve_platform
from repro.launch.compile_cache import enable_compile_cache
from repro.nn import module as nnm
from repro.runtime import (AsyncPagedMLAEngine, PagedMLAEngine, Request,
                           SamplingParams, blocks_for)

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=10)
ap.add_argument("--max-batch", type=int, default=4)
ap.add_argument("--block-size", type=int, default=8)
ap.add_argument("--num-blocks", type=int, default=48)
ap.add_argument("--arrival-rate", type=float, default=0.4,
                help="mean requests per decode step (Poisson)")
ap.add_argument("--platform", default="", choices=[""] + sorted(PLATFORMS),
                help="hwmodel point auto dispatch prices against; '' = the "
                     "attached TPU's own (by device_kind), tpu_v5e on CPU")
ap.add_argument("--shared-prefix-len", type=int, default=16)
ap.add_argument("--no-prefix-cache", action="store_true")
ap.add_argument("--prefill-chunk", type=int, default=16)
ap.add_argument("--prefill-impl", default="auto",
                choices=("auto", "gather", "pallas"))
ap.add_argument("--impl", default="ref", choices=("ref", "kernel"))
ap.add_argument("--cache-dtype", default="bf16",
                choices=("bf16", "int8", "fp8"),
                help="pool storage dtype: int8/fp8 quantize on write with "
                     "per-row f32 scales, dequantized in-register by the "
                     "kernels (~0.3x cache bytes/token vs bf16)")
ap.add_argument("--temperature", type=float, default=0.0)
ap.add_argument("--top-k", type=int, default=0)
ap.add_argument("--mesh", default="",
                help="device mesh 'DPxMP' (e.g. '2x2' = data x model); "
                     "'' = single host")
ap.add_argument("--spec-k", type=int, default=0,
                help="speculative decoding draft window (0 = off)")
ap.add_argument("--draft", default="shallow:2",
                help="draft under --spec-k: 'shallow:N' | 'self'")
ap.add_argument("--trace", default="",
                help="write Perfetto trace-event JSON (request lifecycle "
                     "+ step phase spans) to this path")
ap.add_argument("--metrics", default="",
                help="write metrics-registry JSON to this path and print "
                     "the metrics table")
ap.add_argument("--seed", type=int, default=0)
ap.add_argument("--engine", default="sync", choices=("sync", "async"),
                help="paged engine: 'sync' waits on the device each tick; "
                     "'async' double-buffers host scheduling against device "
                     "execution (token-identical)")
ap.add_argument("--n", type=int, default=1,
                help="parallel samples per request: one prefill, then an "
                     "n-way copy-on-write fork of the sequence")
ap.add_argument("--admission", default="cache_aware",
                choices=("cache_aware", "fcfs"),
                help="waiting-queue order: longest-cached-prefix first "
                     "(aging-bounded) vs strict arrival order")
ap.add_argument("--admission-age-bound", type=int, default=64,
                help="admit a request unconditionally after cache-aware "
                     "admission bypassed it this many times")
args = ap.parse_args()
enable_compile_cache()

cfg = configs.smoke("deepseek-v2-236b")
mla = cfg.mla_config()
plat = resolve_platform(args.platform)
bs = args.block_size
mesh = None
if args.mesh:
    from repro.launch.serve import _parse_mesh
    mesh = _parse_mesh(args.mesh)
    print(f"mesh {args.mesh}: batch over 'data', heads over 'model', "
          f"latent pool replicated ({jax.device_count()} devices)")

print(f"platform {plat.name}: ridge OI = {plat.ridge_oi:.0f} FLOP/B")
for L, B in ((64, 1), (64, args.max_batch), (2048, args.max_batch)):
    sch = auto_dispatch(mla, plat, cache_len=L, batch=B, paged_block=bs)
    ts = {s: step_time(s, mla, plat, cache_len=L, batch=B, paged_block=bs)
          for s in ("seq", "rc", "ru")}
    print(f"  live point (B={B}, L={L}): " + "  ".join(
        f"{s}={t*1e6:7.2f}us" for s, t in ts.items()) + f"  -> '{sch}'")

params = nnm.init_params(jax.random.PRNGKey(args.seed),
                         models.model_defs(cfg), jnp.float32)
# Poisson arrivals, mixed prompt/generation lengths (quantized to bound
# prefill recompiles).
rng = np.random.default_rng(args.seed + 1)
gaps = rng.exponential(1.0 / args.arrival_rate, args.requests)
arrivals = np.floor(np.cumsum(gaps)).astype(int)
preamble = rng.integers(0, cfg.vocab,
                        (args.shared_prefix_len,)).astype(np.int32)
reqs = []
for i in range(args.requests):
    plen = int(rng.choice([8, 16, 24, 32]))
    gen = int(rng.integers(4, 20))
    prompt = np.concatenate(
        [preamble, rng.integers(0, cfg.vocab, (plen,)).astype(np.int32)])
    # rids spaced by n: fork-group children claim rid+1..rid+n-1
    reqs.append(Request(rid=i * args.n, prompt=prompt,
                        arrival=int(arrivals[i]),
                        sampling=SamplingParams(max_tokens=gen, n=args.n)))

per_req = max(blocks_for(r.plen + r.max_new + 1, bs) for r in reqs)
draft_cfg = draft_params = None
if args.spec_k:
    from repro.runtime.spec import parse_draft_spec
    draft_cfg, draft_params = parse_draft_spec(args.draft, cfg, params)
    print(f"speculative decoding: k={args.spec_k}, draft={args.draft} "
          f"({draft_cfg.n_layers} of {cfg.n_layers} layers)")
tel = None
if args.trace or args.metrics:
    from repro.obs import Telemetry
    tel = Telemetry.on(trace=bool(args.trace), metrics=bool(args.metrics))
engine_cls = AsyncPagedMLAEngine if args.engine == "async" else PagedMLAEngine
engine = engine_cls(cfg, params, num_blocks=args.num_blocks,
                    block_size=bs, max_batch=args.max_batch,
                    max_blocks_per_req=per_req,
                    compute_dtype=jnp.float32, impl=args.impl,
                    scheme="auto", platform=plat,
                    enable_prefix_cache=not args.no_prefix_cache,
                    prefill_mode="chunked" if args.prefill_chunk
                    else "per_request",
                    prefill_impl=args.prefill_impl,
                    prefill_chunk=args.prefill_chunk or 32,
                    temperature=args.temperature, top_k=args.top_k,
                    sample_seed=args.seed, mesh=mesh,
                    spec_k=args.spec_k, draft_cfg=draft_cfg,
                    draft_params=draft_params,
                    cache_dtype=args.cache_dtype, telemetry=tel,
                    admission=args.admission,
                    admission_age_bound=args.admission_age_bound)
total_need = sum(blocks_for(r.plen + r.max_new + 1, bs) for r in reqs)
print(f"\n{args.requests} requests (prompts 8-32, gen 4-19), pool "
      f"{args.num_blocks - 1} usable blocks x {bs} tokens "
      f"(peak demand {total_need} blocks if all resident)")

t0 = time.time()
summary = engine.run(reqs, log_every=10)
dt = time.time() - t0

lat = [r.finished_step - r.arrival for r in engine.sched.finished]
print(f"\nserved {args.requests} requests in {summary['steps']:.0f} steps / "
      f"{dt:.2f}s wall ({summary['tokens_per_s']:.1f} decode tok/s on "
      f"{jax.devices()[0].platform})")
print(f"  mid-generation admissions : {summary['mid_gen_admissions']:.0f}"
      f" / {summary['admissions']:.0f}")
print(f"  preemptions (recompute)   : {summary['preemptions']:.0f}")
print(f"  cache utilization         : {summary['cache_utilization']:.2f} "
      f"(valid tokens / allocated block slots)")
print(f"  pool occupancy            : {summary['pool_occupancy']:.2f}")
print(f"  scheme usage              : {summary['schemes_used']}")
print(f"  prefix hit rate           : {summary['prefix_hit_rate']:.2f} "
      f"({summary['prefix_hit_tokens']:.0f}/{summary['prompt_tokens']:.0f} "
      f"prompt tokens shared)")
print(f"  prefilled tokens / chunks : {summary['prefill_tokens']:.0f} / "
      f"{summary['prefill_chunks']:.0f} "
      f"({summary['prefill_compiles']:.0f} compiled prefill shapes)")
print(f"  cache evictions / CoW     : {summary['prefix_evictions']:.0f} / "
      f"{summary['prefix_cow_copies']:.0f}")
if args.n > 1:
    print(f"  fork groups / children    : {summary['fork_groups']:.0f} / "
          f"{summary['fork_children']:.0f} (one prefill per group)")
if args.spec_k:
    print(f"  spec accept / emit rate   : "
          f"{summary['spec_accept_rate']:.2f} "
          f"({summary['spec_accepted']:.0f}/"
          f"{summary['spec_drafted']:.0f} drafts), "
          f"{summary['spec_mean_emitted']:.2f} tokens/round over "
          f"{summary['spec_rounds']:.0f} rounds")
print(f"  latency steps p50/max     : {int(np.median(lat))}/{int(max(lat))}")
first = min(engine.sched.finished, key=lambda r: r.rid)
print("first request's tokens:", np.asarray(first.output)[:16])

if tel is not None:
    tel.finalize(engine)
    written = tel.export(trace_path=args.trace or None,
                         metrics_path=args.metrics or None)
    for channel, path in written.items():
        print(f"telemetry: {channel} -> {path}")
    if tel.metrics is not None:
        ttft = tel.metrics.histogram("ttft_ms").summary()
        print(f"  TTFT ms p50/p95           : {ttft.get('p50', 0):.1f}/"
              f"{ttft.get('p95', 0):.1f}")
        print(tel.metrics.render_table())

# latent-cache footprint vs dense-KV equivalent (the paper's Fig 3 point),
# at the pool's STORAGE dtype (int8/fp8 pay 1 byte/elem + per-row scales)
from repro.core.cache import bytes_per_token_latent
lat_b = bytes_per_token_latent(
    mla.kv_lora_rank, mla.qk_rope_dim, 2,
    None if args.cache_dtype == "bf16" else args.cache_dtype)
dense_b = 2 * cfg.n_heads * mla.qk_dim * 2
print(f"KV bytes/token/layer: latent {lat_b:.0f} ({args.cache_dtype}) vs "
      f"dense {dense_b} ({dense_b / lat_b:.1f}x smaller -> "
      f"{dense_b / lat_b:.1f}x more requests per pool)")
