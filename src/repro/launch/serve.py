"""Serving launcher: prefill a batch of prompts, decode N tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v2-236b \
        --smoke --batch 4 --prompt-len 32 --gen 16 --scheme auto

``--scheme auto`` runs the paper's co-design insight end-to-end: the MLA
execution scheme (rc / ru / seq) is picked per deployment point from the
platform's compute-to-bandwidth ratio (core.schemes.auto_dispatch).

``--paged`` serves the same load through the continuous-batching runtime
instead (paged latent-KV pool + per-request block tables + mid-generation
admission; runtime.engine).  With ``--scheme auto`` the dispatch re-runs
EVERY step on the live (batch, max cache_len) point.

Paged-runtime knobs (PR 2):

  --no-prefix-cache   disable the radix prefix cache (runtime.prefix_cache):
                      by default requests sharing a prompt prefix fork the
                      same pool blocks (ref-counted, copy-on-write at the
                      first divergent/partial block) and only prefill the
                      un-cached suffix; released blocks stay LRU-evictable.
  --prefill-chunk N   chunk size of the batched paged prefill (one compiled
                      prefill shape per chunk size — NOT per prompt length);
                      0 falls back to PR-1's per-request prefill (which
                      also forces the prefix cache off).
  --temperature/--top-k
                      sampling beyond greedy argmax; the PRNG key is folded
                      with (request id, absolute token position) so
                      recompute-preemption replay stays deterministic.

Prefill impl switch (PR 3):

  --prefill-impl {auto,gather,pallas}
                      chunk-attention path of the batched paged prefill.
                      'gather' materializes the contiguous (B, S)
                      block-table view in HBM every chunk (the reference
                      path); 'pallas' runs the fused paged prefill kernel
                      (kernels.mla_prefill) that walks the block table in
                      place — same tokens, no gather ever written.  'auto'
                      (default) follows --impl: 'kernel' (or its alias
                      'pallas') uses the kernel, 'ref' the gather view.
                      Both paths are token-identical (tier-1-gated in
                      tests/test_prefill_kernel.py + tests/test_paged.py).

Sharded serving (PR 4) — composes with --paged:

  --mesh DPxMP        device mesh, e.g. '2x2' = (data=2, model=2).  The
                      contiguous path shards per make_prefill_step /
                      make_serve_step; the PAGED path shards the batch
                      (token / block-table / length rows) over 'data' and
                      heads over 'model' while the latent pool replicates
                      on every device (runtime.steps: the compact cache
                      is what makes replication affordable; per-device
                      cache traffic still drops by the DP factor).
                      Outputs are token-identical to single-host serving
                      (tests/test_mesh_paged.py).  Needs
                      jax.device_count() >= DP*MP: on CPU set
                      XLA_FLAGS=--xla_force_host_platform_device_count=N.
  --policy            weight-sharding rules for the mesh
                      (nn.sharding.make_rules mode; default 'serve').

Speculative decoding (PR 5) — composes with --paged and --mesh:

  --spec-k K          draft K tokens per tick and verify them in ONE
                      k+1-query target forward (the prefill-chunk
                      machinery at chunk K+1; runtime.steps
                      .make_verify_step).  Rejected drafts are a pure
                      host-side length rewind.  Outputs are
                      token-identical to plain paged decode under greedy
                      AND seeded sampling (tests/test_spec_decode.py);
                      draft quality only moves throughput.
  --draft SPEC        draft model: 'shallow:N' (self-speculation — the
                      target's own first N layers, weights shared by
                      reference; default shallow:2) or 'self' (identity
                      draft, the 100%-acceptance oracle).

Quantized latent pool (PR 8) — composes with every paged flag:

  --cache-dtype {bf16,int8,fp8}
                      storage dtype of the paged {ckv|krope} latent pool.
                      'int8' (and 'fp8' on jax builds with
                      float8_e4m3fn) stores 1-byte payloads with per-
                      token-row f32 scales riding the pool pytree;
                      quantize-on-write in the prefill/decode scatter
                      paths, dequantize in-register inside the Pallas
                      kernels (never a pool-sized f32 copy in HBM).
                      Cuts modeled cache bytes/token to ~0.3x bf16 at
                      DeepSeek shapes, shifting the rc/ru/seq crossovers
                      auto_dispatch sees (core.schemes.cache_width);
                      greedy decode stays token-parity with bf16 on the
                      smoke models, with per-dtype logit-error bounds
                      vs the fp32 oracle gated in
                      tests/test_quant_cache.py.  Requires
                      --prefill-chunk > 0 (the per-request scatter
                      carries no scales).  'bf16' (default) is the
                      unquantized pool at the compute dtype.

Async double-buffered engine + HTTP frontend (PR 9):

  --engine {sync,async}
                      which paged engine runs the load.  'async'
                      (AsyncPagedMLAEngine) dispatches the fused
                      decode+sample step and returns WITHOUT syncing:
                      the host prepares tick N+1 (admission, block
                      growth, CoW drain) while the device executes
                      tick N, and only the sampled token ids sync back
                      a tick later.  Token-identical to 'sync' under
                      greedy AND seeded sampling, preemption included
                      (tests/test_async_engine.py).
  --serve             instead of running the synthetic batch, start the
                      stdlib HTTP/SSE frontend (launch.server) on
                      --host:--port and serve live requests:
                      POST /v1/generate (SSE streaming or blocking
                      JSON; per-request max_tokens + stop sequences),
                      POST /v1/cancel, GET /v1/health, GET
                      /v1/metrics.  Requires --paged.  A client
                      disconnect mid-stream cancels the request and
                      frees its pool blocks.
  --host / --port     frontend bind address (default 127.0.0.1:8000).

Multi-turn & parallel sampling (PR 10):

  --n N               parallel samples per request (SamplingParams.n):
                      the prompt prefills ONCE, then the sequence forks
                      N ways through refcounted block sharing +
                      copy-on-write on the partial tail block
                      (runtime.scheduler.fork_group).  Each fork samples
                      its own fold(rid + i, position) key stream, so the
                      group is token-identical to N independent seeded
                      requests while allocating strictly fewer blocks.
                      Per-request knobs ride runtime.sampling
                      .SamplingParams; the legacy Request(prompt,
                      max_new, stop=...) constructor still works through
                      a deprecation shim.
  --admission {cache_aware,fcfs}
                      admission order of waiting requests.
                      'cache_aware' (default) admits the request with
                      the longest currently-cached prefix first (probed
                      fork-free via PrefixCache.lookup_len) so warm
                      conversation turns jump cold prompts; requests
                      bypassed --admission-age-bound times are served
                      regardless (starvation bound).  'fcfs' restores
                      strict arrival order.
  --admission-age-bound N
                      how many times cache-aware admission may bypass a
                      waiting request before it is served unconditionally
                      (default 64).

  Decode-filled blocks also register in the radix trie as generation
  crosses each block boundary, so a follow-up turn whose prompt embeds
  the previous turn's output re-hits its OWN generation, and prefix
  matches are token-granular (a hit may end mid-block; the tail is
  materialized copy-on-write).  Both behaviors are on by default with
  the prefix cache and off with --no-prefix-cache.

Common knobs: --arch picks the model family/config, --smoke shrinks it
to CI size (and computes in f32; published widths run in bf16),
--platform names the hwmodel deployment point that auto_dispatch prices
schemes against (default: the attached TPU's own, looked up by
device_kind; tpu_v5e on a host without one), and --seed seeds weight
init and the sampling PRNG.

On CPU the Pallas kernels (--impl kernel, --prefill-impl pallas) run
only in the interpreter, which REPRO_PALLAS_INTERPRET=1 asks for; on a
TPU they always compile.  The persistent compilation cache lives where
JAX_COMPILATION_CACHE_DIR says, else in .jax_cache/ at the repo root
(launch.compile_cache).

Telemetry (PR 7) — composes with every paged flag:

  --trace PATH        record per-request lifecycle spans (arrival ->
                      queued -> prefill -> decode -> finish/preempt) and
                      per-step phase spans (schedule / prefill chunks /
                      draft / verify / device_step / host_sample) and
                      write Chrome/Perfetto trace-event JSON to PATH
                      (load it at https://ui.perfetto.dev or
                      chrome://tracing).
  --metrics PATH      write the metrics-registry JSON (counters, gauges,
                      TTFT/TPOT/queue-delay/step-time histograms with
                      p50/p95/p99, plus the engine summary verbatim) to
                      PATH and print the human-readable table.
                      Spans are always recorded into the process
                      recorder (repro.obs, a bounded ring); --trace
                      exports the part of it this run recorded.

Serving-flags summary (the paged runtime; all compose):

  flag              default   effect
  --paged           off       continuous batching over the block pool
  --block-size      16        tokens per pool block
  --num-blocks      sized     pool capacity
  --no-prefix-cache off       disable radix block sharing
  --prefill-chunk   32        batched prefill chunk (0 = per-request)
  --prefill-impl    auto      'gather' view vs 'pallas' in-place kernel
  --impl            ref       decode attention: 'ref' | 'kernel'
  --cache-dtype     bf16      pool storage: 'bf16' | 'int8' | 'fp8'
  --temperature     0.0       0 = greedy; else seeded sampling
  --top-k           0         top-k filter when sampling
  --mesh            ''        'DPxMP' sharded serving
  --policy          serve     weight-sharding rules under --mesh
  --spec-k          0         speculative decoding draft window
  --draft           shallow:2 draft spec ('shallow:N' | 'self')
  --trace           ''        Perfetto trace-event JSON output path
  --metrics         ''        metrics-registry JSON output path
  --engine          sync      paged engine: 'sync' | 'async' (overlapped)
  --serve           off       HTTP/SSE frontend instead of batch mode
  --host            127.0.0.1 frontend bind host (with --serve)
  --port            8000      frontend bind port (with --serve)
  --n               1         parallel samples per request (fork + CoW)
  --admission       cache_aware  admission order: 'cache_aware' | 'fcfs'
  --admission-age-bound 64    starvation bound of cache-aware admission

Static audit (PR 6): every step factory this CLI dispatches to
(decode/prefill/verify x gather/pallas x scheme, single-device and
--mesh) is compiled — never run — by ``repro.analysis.audit`` and
checked for donation aliasing, pool-gather byte budgets, dtype
discipline, and roofline conformance against ``hwmodel``'s cost model
(``make audit`` / the CI ``audit`` job; tolerance bands live in
``analysis/audit.py:TOLERANCES``, suppressions in
``analysis/audit_allowlist.py``).  A serve-path change that drops a
donation or inflates pool traffic fails the gate before any benchmark
notices.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, models
from repro.core import mla as mlalib
from repro.core.schemes import auto_dispatch
from repro.hwmodel.platforms import PLATFORMS, resolve_platform
from repro.launch.compile_cache import enable_compile_cache
from repro.nn import module as nnm
from repro.runtime.steps import make_prefill_step, make_serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--scheme", default="auto",
                    help="auto | naive | seq | rc | ru")
    ap.add_argument("--platform", default="", choices=[""] + sorted(PLATFORMS),
                    help="hwmodel point auto dispatch prices against; '' = "
                         "the attached TPU's own (by device_kind), tpu_v5e "
                         "on a host without one")
    ap.add_argument("--impl", default="ref")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="continuous batching over the paged latent pool")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool blocks (0 = sized for the request load)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix prefix-cache block sharing")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="batched paged prefill chunk size "
                         "(0 = PR-1 per-request prefill)")
    ap.add_argument("--prefill-impl", default="auto",
                    choices=("auto", "gather", "pallas"),
                    help="chunked-prefill attention path: 'gather' "
                         "materializes the block-table view (reference), "
                         "'pallas' walks the block table in place via the "
                         "fused prefill kernel; 'auto' follows --impl")
    ap.add_argument("--cache-dtype", default="bf16",
                    choices=("bf16", "int8", "fp8"),
                    help="paged latent-pool storage dtype: int8/fp8 "
                         "quantize on write with per-token-row f32 scales "
                         "and dequantize in-register in the kernels "
                         "(~0.3x cache bytes/token vs bf16); requires "
                         "--paged and --prefill-chunk > 0")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples with a per-request PRNG "
                         "key folded with the absolute token position")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter when sampling (0 = full vocab)")
    ap.add_argument("--mesh", default="",
                    help="device mesh 'DPxMP' (e.g. '2x2' = data x model); "
                         "'' = single host.  Composes with --paged.  On "
                         "CPU, force devices first: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--policy", default="serve",
                    choices=("serve", "serve_2dtp", "dp", "tp"),
                    help="weight-sharding rules under --mesh "
                         "(nn.sharding.make_rules mode)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft K tokens per tick "
                         "and verify them in one K+1-query forward "
                         "(0 = off; requires --paged, composes with "
                         "--mesh; token-identical to plain decode)")
    ap.add_argument("--draft", default="shallow:2",
                    help="draft model under --spec-k: 'shallow:N' = the "
                         "target's own first N layers (self-speculation) "
                         "| 'self' = identity draft (acceptance oracle)")
    ap.add_argument("--trace", default="",
                    help="write Chrome/Perfetto trace-event JSON (request "
                         "lifecycle + step phase spans) to this path; "
                         "requires --paged")
    ap.add_argument("--metrics", default="",
                    help="write metrics-registry JSON (counters/gauges/"
                         "histograms + engine summary) to this path and "
                         "print the table; requires --paged")
    ap.add_argument("--engine", default="sync", choices=("sync", "async"),
                    help="paged engine: 'sync' steps the device and waits; "
                         "'async' double-buffers — host schedules tick N+1 "
                         "while the device runs tick N (token-identical)")
    ap.add_argument("--serve", action="store_true",
                    help="start the HTTP/SSE frontend (launch.server) on "
                         "--host:--port instead of running the synthetic "
                         "batch; requires --paged")
    ap.add_argument("--host", default="127.0.0.1",
                    help="frontend bind host (with --serve)")
    ap.add_argument("--port", type=int, default=8000,
                    help="frontend bind port (with --serve)")
    ap.add_argument("--n", type=int, default=1,
                    help="parallel samples per request: prefill once, "
                         "fork the sequence n ways copy-on-write "
                         "(SamplingParams.n); requires --paged")
    ap.add_argument("--admission", default="cache_aware",
                    choices=("cache_aware", "fcfs"),
                    help="admission order of waiting requests: "
                         "'cache_aware' admits the longest-cached-prefix "
                         "first (aging-bounded), 'fcfs' strict arrival "
                         "order; requires --paged")
    ap.add_argument("--admission-age-bound", type=int, default=64,
                    help="serve a waiting request unconditionally after "
                         "cache-aware admission bypassed it this many "
                         "times")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.smoke(args.arch) if args.smoke else configs.full(args.arch)
    dtype = jnp.float32 if args.smoke else jnp.bfloat16
    params = nnm.init_params(jax.random.PRNGKey(args.seed),
                             models.model_defs(cfg), dtype)
    mesh = _parse_mesh(args.mesh)

    if args.paged:
        return _serve_paged(args, cfg, params, dtype, mesh)
    if args.cache_dtype != "bf16":
        raise SystemExit("--cache-dtype requires --paged (only the paged "
                         "latent pool stores quantized)")
    if args.spec_k:
        raise SystemExit("--spec-k requires --paged (the draft/verify "
                         "phases run on the paged runtime)")
    if args.trace or args.metrics:
        raise SystemExit("--trace/--metrics require --paged (the "
                         "telemetry subsystem instruments the "
                         "continuous-batching engine)")
    if args.serve or args.engine != "sync":
        raise SystemExit("--serve/--engine require --paged (the frontend "
                         "and the async double-buffer run on the paged "
                         "runtime)")
    if args.n != 1:
        raise SystemExit("--n requires --paged (parallel sampling forks "
                         "the paged block pool copy-on-write)")

    scheme = args.scheme
    if scheme == "auto":
        if cfg.attn_kind == "mla":
            platform = resolve_platform(args.platform)
            cap = args.prompt_len + args.gen
            scheme = auto_dispatch(cfg.mla_config(), platform, cache_len=cap,
                                   batch=args.batch)
            print(f"[serve] auto_dispatch({platform.name}, L={cap}, "
                  f"B={args.batch}) -> scheme '{scheme}'")
        else:
            scheme = "seq"

    if cfg.attn_kind == "mla":
        # engine build: attach precomputed absorbed weights for 'ru'
        # (BEFORE the step builders, so mesh in_shardings see the final
        # param tree — see steps.paged_param_shardings)
        params = _prepare_mla(params, cfg, scheme)

    capacity = args.prompt_len + args.gen + 1
    tmpl = params if mesh is not None else None
    prefill = make_prefill_step(cfg, mesh, batch=args.batch,
                                capacity=capacity, compute_dtype=dtype,
                                impl=args.impl, scheme=scheme,
                                policy=args.policy, params_template=tmpl)
    step = make_serve_step(cfg, mesh, compute_dtype=dtype, impl=args.impl,
                           scheme=scheme, policy=args.policy,
                           params_template=tmpl)
    if mesh is not None:
        # with a mesh the serve-step builder closes over the cache pytree
        # (shardings depend on its structure); commit the weights once
        from repro.runtime.steps import commit_params
        params = commit_params(params, cfg, mesh, args.policy)
        step = step(jax.eval_shape(
            lambda: models.init_cache(cfg, args.batch, capacity, dtype)),
            args.batch, capacity)

    # independent streams for tokens and embeds: reusing one key would
    # correlate the draws (jaxlint JL001, enforced by `make audit`)
    tok_key, emb_key = jax.random.split(jax.random.PRNGKey(args.seed + 1))
    toks = jax.random.randint(tok_key, (args.batch, args.prompt_len), 0,
                              cfg.vocab)
    kw = {}
    if cfg.family in ("vlm", "encdec"):
        P = cfg.n_patches if cfg.family == "vlm" else cfg.n_frames
        kw["embeds"] = jax.random.normal(emb_key, (args.batch, P, cfg.d_model),
                                         dtype) * 0.02

    t0 = time.time()
    logits, cache = prefill(params, toks, **kw)
    logits.block_until_ready()
    print(f"[serve] prefill {args.batch}x{args.prompt_len}: "
          f"{time.time() - t0:.2f}s")

    out_tokens = [np.asarray(jnp.argmax(logits, -1))]
    t0 = time.time()
    for i in range(args.gen - 1):
        tok = jnp.asarray(out_tokens[-1])
        logits, cache = step(params, tok, cache, args.prompt_len + i)
        out_tokens.append(np.asarray(jnp.argmax(logits, -1)))
    jax.block_until_ready(logits)
    dt = time.time() - t0
    print(f"[serve] decoded {args.gen - 1} steps in {dt:.2f}s "
          f"({(args.gen - 1) * args.batch / max(dt, 1e-9):.1f} tok/s), "
          f"scheme={scheme}")
    print("[serve] sample:", np.stack(out_tokens, 1)[0][:16])


def _parse_mesh(spec: str):
    """'' -> None; 'DPxMP' (e.g. '2x2') -> Mesh((dp, mp), (data, model))."""
    if not spec:
        return None
    from repro.launch.mesh import make_mesh
    try:
        dp, mp = (int(x) for x in spec.lower().replace(",", "x").split("x"))
    except ValueError:
        raise SystemExit(f"--mesh expects 'DPxMP' (e.g. '2x2'), got {spec!r}")
    need = dp * mp
    if jax.device_count() < need:
        raise SystemExit(
            f"--mesh {spec}: needs {need} devices, found "
            f"{jax.device_count()}.  On CPU force virtual devices first: "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return make_mesh((dp, mp), ("data", "model"))


def _serve_paged(args, cfg, params, dtype, mesh=None):
    """Continuous-batching path: the fixed (batch x prompt x gen) load
    becomes a staggered request stream against the paged runtime.  With a
    mesh, batch rows shard over 'data', heads over 'model', and the pool
    replicates (runtime.steps) — same tokens as single-host serving."""
    from repro.runtime import (AsyncPagedMLAEngine, PagedMLAEngine, Request,
                               SamplingParams, blocks_for)

    engine_cls = AsyncPagedMLAEngine if args.engine == "async" \
        else PagedMLAEngine
    bs = args.block_size
    per_req = blocks_for(args.prompt_len + args.gen + 1, bs)
    # fork children share the prompt blocks; each needs its own tail run
    per_group = per_req + (args.n - 1) * blocks_for(args.gen + 1, bs)
    num_blocks = args.num_blocks or (1 + args.batch * per_group)
    draft_cfg = draft_params = None
    if args.spec_k:
        from repro.runtime.spec import parse_draft_spec
        draft_cfg, draft_params = parse_draft_spec(args.draft, cfg, params)
        print(f"[serve] speculative decoding: k={args.spec_k}, "
              f"draft={args.draft} ({draft_cfg.n_layers} layers)")
    tel = None
    if args.trace or args.metrics:
        from repro.obs import Telemetry
        tel = Telemetry.on(trace=bool(args.trace),
                           metrics=bool(args.metrics))
    engine = engine_cls(
        cfg, params, num_blocks=num_blocks, block_size=bs,
        max_batch=max(args.batch, args.n), max_blocks_per_req=per_req,
        compute_dtype=dtype, impl=args.impl, scheme=args.scheme,
        platform=resolve_platform(args.platform),
        enable_prefix_cache=not args.no_prefix_cache,
        prefill_mode="chunked" if args.prefill_chunk else "per_request",
        prefill_impl=args.prefill_impl,
        prefill_chunk=args.prefill_chunk or 32,
        temperature=args.temperature, top_k=args.top_k,
        sample_seed=args.seed, mesh=mesh, shard_policy=args.policy,
        spec_k=args.spec_k, draft_cfg=draft_cfg, draft_params=draft_params,
        cache_dtype=args.cache_dtype, telemetry=tel,
        admission=args.admission,
        admission_age_bound=args.admission_age_bound)
    if args.serve:
        from repro.launch.server import Frontend
        fe = Frontend(engine, host=args.host, port=args.port)
        print(f"[serve] HTTP/SSE frontend on http://{fe.host}:{fe.port} "
              f"(engine={args.engine}; POST /v1/generate, /v1/cancel; "
              f"GET /v1/health, /v1/metrics; Ctrl-C to stop)")
        return fe.serve_forever()
    rng = np.random.default_rng(args.seed + 1)
    # rids are spaced by n: a fork group's children claim rid+1..rid+n-1.
    reqs = [Request(rid=i * args.n,
                    prompt=rng.integers(0, cfg.vocab,
                                        (args.prompt_len,)).astype(np.int32),
                    arrival=2 * i,
                    sampling=SamplingParams(max_tokens=args.gen, n=args.n))
            for i in range(args.batch)]
    t0 = time.time()
    summary = engine.run(reqs, log_every=8)
    dt = time.time() - t0
    print(f"[serve] paged: {summary['decode_tokens']:.0f} decode tokens in "
          f"{dt:.2f}s ({summary['tokens_per_s']:.1f} tok/s), "
          f"{summary['mid_gen_admissions']:.0f} mid-generation admissions, "
          f"cache utilization {summary['cache_utilization']:.2f}, "
          f"schemes {summary['schemes_used']}")
    print(f"[serve] prefix cache: hit rate "
          f"{summary['prefix_hit_rate']:.2f} "
          f"({summary['prefix_hit_tokens']:.0f}/"
          f"{summary['prompt_tokens']:.0f} prompt tokens), "
          f"{summary['prefill_tokens']:.0f} prefilled in "
          f"{summary['prefill_chunks']:.0f} chunks, "
          f"{summary['prefill_compiles']:.0f} prefill compiles")
    if args.n > 1:
        print(f"[serve] parallel sampling: {summary['fork_groups']:.0f} "
              f"groups forked n={args.n} "
              f"({summary['fork_children']:.0f} children, one prefill per "
              f"group)")
    if args.spec_k:
        print(f"[serve] spec decode: {summary['spec_rounds']:.0f} rounds, "
              f"accept rate {summary['spec_accept_rate']:.2f} "
              f"({summary['spec_accepted']:.0f}/"
              f"{summary['spec_drafted']:.0f} drafts), "
              f"{summary['spec_mean_emitted']:.2f} tokens/round, "
              f"{summary['spec_compiles']:.0f} spec compiles")
    first = min(engine.sched.finished, key=lambda r: r.rid)
    print("[serve] sample:", np.asarray(first.output[:16]))
    if tel is not None:
        tel.finalize(engine)
        written = tel.export(trace_path=args.trace or None,
                             metrics_path=args.metrics or None)
        for channel, path in written.items():
            print(f"[serve] telemetry: {channel} -> {path}")
        if tel.metrics is not None:
            print(tel.metrics.render_table())


def _prepare_mla(params, cfg, scheme):
    """Attach absorbed weights on every MLA sublayer (stacked or not)."""
    if scheme != "ru":
        return params
    return mlalib.attach_absorbed_tree(params, cfg.mla_config())


if __name__ == "__main__":
    main()
