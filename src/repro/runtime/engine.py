"""Continuous-batching MLA serving engine over the paged latent-KV pool.

Glues the host-side ``ContinuousScheduler`` (admission, radix prefix
cache, block tables, eviction) to the jitted device steps:

  * batched CHUNKED prefill straight into the pool
    (``make_chunked_prefill_step``): admitted requests prefill together,
    fixed-size chunk by chunk, attending their prefix-cache hits through
    the block table — one compiled step shape per chunk size instead of
    one retrace per prompt length, and no contiguous-entries detour.
    While any row decodes, a tick runs ONE chunk step before its decode
    step, so running rows wait one chunk, not a whole prompt
    (``_run_chunked_prefill``).
    (``prefill_mode='per_request'`` keeps PR-1's bucketed per-request
    prefill + scatter for A/B comparison; it forces the prefix cache off
    because it recomputes and rewrites whole prompts.)
  * one paged decode step per scheduler tick over ALL slots (inactive
    slots, and rows still prefilling, ride along as empty rows pointing
    at the null block; their logits are discarded);
  * sampling: greedy argmax by default; ``temperature > 0`` switches to
    temperature / top-k sampling with a per-request PRNG key folded with
    the ABSOLUTE token position, so recompute-preemption replay remains
    deterministic (replayed tokens live in the prompt; fresh tokens
    re-land on the same fold(rid, position) stream);
  * ``schemes.auto_dispatch`` re-run EVERY step on the live
    (batch, max cache_len) point with the paged-bytes cost term, so the
    rc/ru/seq choice tracks the batch composition — jitted steps are
    cached per scheme and swapped freely because all schemes compute the
    same function with identical weights (the paper's core claim);
  * optional ``mesh``/``shard_policy``: decode and chunked prefill run
    sharded — batch (token / block-table / length rows) over the DP axes,
    heads over 'model', the latent pool replicated over every axis (its
    compactness is what makes full replication affordable — the paper's
    bandwidth argument scaled out).  ``max_batch`` is padded up to a DP
    multiple (free: inactive slots carry length 0 and null tables), the
    scheduler stays host-global and unsharded, and outputs are
    token-identical to single-host serving (tests/test_mesh_paged.py).

Two engines share this machinery (and the scheduler, steps and stats):

  * ``PagedMLAEngine`` — the synchronous reference tick: schedule ->
    device step -> host sample, one barrier per tick.  Ground truth for
    every parity gate.
  * ``AsyncPagedMLAEngine`` — the double-buffered production tick: the
    host runs tick N+1's scheduling (admission, block growth, CoW drain)
    while the device still executes tick N, sampling is folded into the
    compiled step (``make_paged_sample_step``) so only the (B,) accepted
    tokens ever sync back, and token values are accounted one tick late —
    token-identical to the synchronous engine (docs/architecture.md walks
    the argument; tests/test_async_engine.py pins it).

Both engines expose ``request_cancel`` (thread-safe, processed at tick
start) and honor per-request ``stop`` sequences / ``max_new`` budgets via
the scheduler — the frontend hooks (launch/server.py) need nothing else.

Used by examples/serve_mla.py, benchmarks/bench_serving.py and
``python -m repro.launch.serve --paged`` (``--serve`` puts the HTTP/SSE
frontend on top).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import models
from ..core import cache as cachelib
from ..core import mla as mlalib
from ..core.schemes import PlatformPoint, auto_dispatch
from ..models.common import ModelConfig
from ..obs import Telemetry
from ..obs.trace import PID_ENGINE, PID_REQUESTS
from . import spec as speclib
from .scheduler import ContinuousScheduler, Request, blocks_for
from .steps import (make_chunked_prefill_step, make_paged_sample_step,
                    make_paged_serve_step, make_prefill_step,
                    make_verify_step, scatter_prefill_to_paged)

# PID_ENGINE tid 0 carries the host-phase spans; the async engine's
# device spans live on their own track so a device step spanning two host
# ticks cannot break tid-0 span nesting (obs.trace.validate_trace).
TID_DEVICE = 1


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    decode_tokens: int = 0
    prefill_tokens: int = 0         # tokens actually prefilled (cache
    prompt_tokens: int = 0          # hits excluded) vs tokens submitted
    prefill_chunks: int = 0
    admissions: int = 0
    mid_gen_admissions: int = 0     # admitted while other slots were decoding
    preemptions: int = 0
    scheme_switches: int = 0
    spec_rounds: int = 0            # speculative draft+verify ticks
    spec_slot_rounds: int = 0       # per-slot verify rows across rounds
    spec_drafted: int = 0           # draft tokens proposed
    spec_accepted: int = 0          # draft tokens accepted by the target
    interleaved_chunks: int = 0     # chunk steps run while rows decoded
    util_valid_sum: float = 0.0     # time-avg of valid/allocated
    util_pool_sum: float = 0.0
    util_samples: int = 0
    wall: float = 0.0
    schemes_used: Dict[str, int] = dataclasses.field(default_factory=dict)

    def summary(self) -> Dict[str, float]:
        n = max(self.util_samples, 1)
        return {
            "steps": self.steps,
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            "prompt_tokens": self.prompt_tokens,
            "prefill_chunks": self.prefill_chunks,
            "interleaved_chunks": self.interleaved_chunks,
            "admissions": self.admissions,
            "mid_gen_admissions": self.mid_gen_admissions,
            "preemptions": self.preemptions,
            "scheme_switches": self.scheme_switches,
            "tokens_per_s": (self.decode_tokens / self.wall)
            if self.wall > 0 else 0.0,
            "spec_rounds": self.spec_rounds,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_accept_rate": self.spec_accepted / self.spec_drafted
            if self.spec_drafted else 0.0,
            # per-REQUEST tokens per verify: the quantity the hwmodel
            # break-even E* is stated in (1 <= E <= k + 1)
            "spec_mean_emitted": self.decode_tokens / self.spec_slot_rounds
            if self.spec_slot_rounds else 0.0,
            "cache_utilization": self.util_valid_sum / n,
            "pool_occupancy": self.util_pool_sum / n,
            "schemes_used": dict(self.schemes_used),
        }


class PagedMLAEngine:
    def __init__(self, cfg: ModelConfig, params, *, num_blocks: int,
                 block_size: int, max_batch: int,
                 max_blocks_per_req: Optional[int] = None,
                 compute_dtype=jnp.float32, impl: str = "ref",
                 scheme: str = "auto",
                 platform: Optional[PlatformPoint] = None,
                 enable_prefix_cache: bool = True,
                 prefill_chunk: int = 32,
                 prefill_mode: str = "chunked",
                 prefill_impl: Optional[str] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0,
                 mesh=None, shard_policy: str = "serve",
                 spec_k: int = 0, draft_cfg: Optional[ModelConfig] = None,
                 draft_params=None,
                 cache_dtype: str = "bf16",
                 admission: str = "cache_aware",
                 admission_age_bound: int = 64,
                 decode_block_reuse: bool = True,
                 partial_match: bool = True,
                 telemetry: Optional[Telemetry] = None):
        if cfg.attn_kind != "mla":
            raise NotImplementedError("PagedMLAEngine requires an MLA model")
        if scheme == "auto" and platform is None:
            raise ValueError("scheme='auto' needs a PlatformPoint")
        if prefill_mode not in ("chunked", "per_request"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        cache_dtype = "bf16" if cache_dtype is None else cache_dtype
        cachelib.cache_dtype_info(cache_dtype)   # validate the name early
        if cache_dtype != "bf16" and prefill_mode != "chunked":
            raise NotImplementedError(
                "quantized cache_dtype requires prefill_mode='chunked' "
                "(the per-request scatter carries no scales)")
        if mesh is not None and prefill_mode != "chunked":
            # the per-request path jits an UNSHARDED contiguous prefill and
            # scatters into the (replicated) pool — keep the A/B baseline
            # single-host rather than half-shard it
            raise NotImplementedError(
                "mesh serving requires prefill_mode='chunked' (the "
                "per-request A/B path is single-host)")
        if impl == "pallas":        # alias: the kernel impl IS Pallas
            impl = "kernel"
        if prefill_impl in ("auto", ""):
            prefill_impl = None
        if prefill_impl not in (None, "gather", "pallas"):
            raise ValueError(f"unknown prefill_impl {prefill_impl!r} "
                             "(None/'auto' | 'gather' | 'pallas')")
        if prefill_mode != "chunked" and enable_prefix_cache:
            # the per-request path recomputes + rewrites WHOLE prompts,
            # which would scatter over read-only shared blocks
            enable_prefix_cache = False
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k:
            if prefill_mode != "chunked":
                raise NotImplementedError(
                    "speculative decoding requires prefill_mode='chunked' "
                    "(the draft pool is filled by the same chunked path)")
            if draft_cfg is None or draft_params is None:
                raise ValueError(
                    "spec_k > 0 needs draft_cfg + draft_params — build "
                    "them with runtime.spec.shallow_draft / identity_draft")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab} != target {cfg.vocab}")
            if draft_cfg.attn_kind != "mla":
                raise NotImplementedError("drafts must be MLA models "
                                          "(they share the paged runtime)")
        self.cfg = cfg
        self.mla = cfg.mla_config()
        self.mesh = mesh
        self.shard_policy = shard_policy
        # DP shard count: the batch dim (token/table/length rows) shards
        # over ('pod', 'data'); 'model' shards heads and replicates the
        # pool (see steps.cache_pspecs paged=).
        self._dp = 1
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for a in ("pod", "data"):
                self._dp *= sizes.get(a, 1)
            # pad the slot count to a DP multiple so PS(dp) divides the
            # batch dim.  Free: the extra slots are ordinary empty slots
            # (length 0, null block table) until the scheduler admits
            # into them — and more slots never hurts admission.
            max_batch = -(-max_batch // self._dp) * self._dp
        # 'ru' streams the precomputed absorbed weights; attach them once
        # so every scheme's jitted step sees the same param tree.  A fixed
        # non-ru scheme never reads them — skip the compute and memory.
        self.params = mlalib.attach_absorbed_tree(params, self.mla) \
            if scheme in ("auto", "ru") else params
        if mesh is not None:
            from .steps import commit_params
            self.params = commit_params(self.params, cfg, mesh,
                                        shard_policy)
        self.compute_dtype = compute_dtype
        self.cache_dtype = cache_dtype
        self.impl = impl
        self.scheme = scheme
        self.platform = platform
        self.block_size = block_size
        self.prefill_mode = prefill_mode
        # chunked-prefill attention path: None follows ``impl`` ('ref' ->
        # gather view, 'kernel' -> Pallas); 'gather'/'pallas' override it
        # so the prefill path can be A/B'd with the decode path pinned
        # (bench_serving's prefill-kernel row does exactly that).
        self.prefill_impl = prefill_impl
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._sample_seed = int(sample_seed)
        self._sample_key = jax.random.PRNGKey(sample_seed)
        # cancellation flags from other threads (the HTTP frontend),
        # drained at the start of every tick
        self._cancel_lock = threading.Lock()
        self._cancels: set = set()
        # max_blocks_per_req bounds the block-table WIDTH, i.e. the extent
        # every decode step scans per request — size it to the workload's
        # longest request, not the pool (nb = pool size would make each
        # step's cost scale with total pool capacity).
        self.sched = ContinuousScheduler(
            num_blocks=num_blocks, block_size=block_size,
            max_batch=max_batch, max_blocks_per_req=max_blocks_per_req,
            enable_prefix_cache=enable_prefix_cache,
            decode_window=spec_k + 1,
            admission=admission,
            admission_age_bound=admission_age_bound,
            decode_block_reuse=decode_block_reuse,
            partial_match=partial_match)
        self.pool = models.init_paged_cache(cfg, num_blocks, block_size,
                                            compute_dtype,
                                            cache_dtype=cache_dtype)
        # -- speculative decoding: draft model + its own paged pool -------
        # The draft pool shares the scheduler's GEOMETRY (block size, block
        # ids, tables) with the target pool — one host-side allocator and
        # one block table serve both — so accept/reject is a shared length
        # rewind and every block-level op (CoW copies, eviction reuse)
        # applies to both pools in lockstep.
        self.spec_k = int(spec_k)
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.draft_pool = None
        # drafts always decode with 'seq' (all schemes compute the same
        # function; 'seq' needs no absorbed leaves, so shallow drafts
        # sliced from an un-absorbed tree work for every engine scheme)
        self._draft_scheme = "seq"
        if spec_k:
            self.draft_pool = models.init_paged_cache(
                draft_cfg, num_blocks, block_size, compute_dtype,
                cache_dtype=cache_dtype)
            if mesh is not None and draft_params is not params:
                # shallow drafts alias embed/ln_f/first-N-layer leaves of
                # the target: reuse the committed buffers instead of
                # device_put-ing a second copy of each shared weight
                from .steps import commit_draft_params
                self.draft_params = commit_draft_params(
                    draft_params, draft_cfg, mesh, shard_policy,
                    target_host=params, target_committed=self.params)
        if mesh is not None:
            # the pool replicates over every mesh axis (host-global block
            # tables may point any DP shard at any block); committing it
            # here keeps the donated in/out shardings copy-free.
            from jax.sharding import NamedSharding, PartitionSpec as PS
            repl = lambda tree: jax.device_put(
                tree, jax.tree.map(lambda _: NamedSharding(mesh, PS()),
                                   tree))
            self.pool = repl(self.pool)
            if self.draft_pool is not None:
                self.draft_pool = repl(self.draft_pool)
        if spec_k and self.draft_params is params:
            # identity draft ('self'): share the engine's prepared tree
            # (absorbed leaves attached / mesh-committed above)
            self.draft_params = self.params
        self.pending = np.zeros((max_batch,), np.int32)   # next token to feed
        # (chunk logits, [(slot, request)]) of rows whose prompt the last
        # tick's chunk step ended while rows decoded: they sample their
        # first token at the start of this tick's chunk work
        self._ended: Optional[Tuple[object, List[Tuple[int, Request]]]] = None
        self._decode_steps: Dict[str, object] = {}
        self._prefills: Dict[int, object] = {}     # per_request: cap -> fn
        self._chunk_steps: Dict[int, object] = {}  # chunked: chunk size -> fn
        self._verify_steps: Dict[str, object] = {}  # spec: scheme -> fn
        self._draft_decode_step = None
        self._draft_chunk_steps: Dict[int, object] = {}
        self._copy_block = jax.jit(cachelib.copy_block_paged,
                                   donate_argnums=(0,))
        self._copy_blocks = jax.jit(cachelib.copy_blocks_paged,
                                    donate_argnums=(0,))
        self._last_scheme: Optional[str] = None
        self.stats = EngineStats()
        # bytes one cached token occupies across ALL layers at the POOL's
        # storage dtype — the occupancy gauges below convert allocated
        # blocks to HBM bytes through this, so telemetry prices the same
        # pool the dispatcher does (int8 pools would otherwise report
        # bf16-sized occupancy; pinned by tests/test_quant_cache.py)
        self.cache_token_bytes = cfg.n_layers * cachelib.bytes_per_token_latent(
            cfg.kv_lora_rank, cfg.qk_rope_dim,
            dtype_bytes=jnp.dtype(compute_dtype).itemsize,
            cache_dtype=cache_dtype)
        # -- telemetry (repro.obs): the default records into the process
        # recorder; Telemetry.off()'s span() returns a shared null context
        # manager, so the instrumented hot path costs one call per site.
        self.tel = telemetry if telemetry is not None \
            else Telemetry.default()
        self.sched.tracer = self.tel.tracer
        self.tel.tracer.set_process_name(PID_ENGINE, "engine")
        self.tel.tracer.set_thread_name(PID_ENGINE, 0, "step phases")
        self.tel.tracer.set_process_name(PID_REQUESTS, "requests")
        if self.tel.enabled:
            self.sched.prefix.tel = self.tel

    # ------------------------------------------------------------ build ---

    def _decode_step(self, scheme: str):
        if scheme not in self._decode_steps:
            self._decode_steps[scheme] = make_paged_serve_step(
                self.cfg, self.mesh, compute_dtype=self.compute_dtype,
                impl=self.impl, scheme=scheme, policy=self.shard_policy,
                cache_dtype=self.cache_dtype)
        return self._decode_steps[scheme]

    def _prefill(self, cap: int):
        if cap not in self._prefills:
            # prefill attention runs in "MHA mode"; the scheme only matters
            # at decode, so one prefill serves every scheme.
            self._prefills[cap] = make_prefill_step(
                self.cfg, None, batch=1, capacity=cap,
                compute_dtype=self.compute_dtype, impl=self.impl)
        return self._prefills[cap]

    def _chunk_impl(self) -> str:
        """Chunk-attention impl of the prefill AND verify steps: follows
        ``prefill_impl`` when overridden, else the engine ``impl``."""
        return {"gather": "ref", "pallas": "kernel",
                None: self.impl}[self.prefill_impl]

    def _chunk_step(self, chunk: int):
        if chunk not in self._chunk_steps:
            # a FIXED engine scheme prefills with the same absorption
            # ordering (all schemes compute the same function); 'auto'
            # pins prefill to 'seq' so the per-step decode dispatch does
            # not multiply compiled chunk shapes, and 'naive' has no
            # latent chunk path.
            scheme = self.scheme if self.scheme in ("seq", "rc", "ru") \
                else "seq"
            self._chunk_steps[chunk] = make_chunked_prefill_step(
                self.cfg, self.mesh, compute_dtype=self.compute_dtype,
                impl=self._chunk_impl(), scheme=scheme,
                policy=self.shard_policy, cache_dtype=self.cache_dtype)
        return self._chunk_steps[chunk]

    def _draft_chunk_step(self, chunk: int):
        """Draft-model sibling of :meth:`_chunk_step`: keeps the draft
        pool prompt-complete so drafting can start right after prefill."""
        if chunk not in self._draft_chunk_steps:
            self._draft_chunk_steps[chunk] = make_chunked_prefill_step(
                self.draft_cfg, self.mesh,
                compute_dtype=self.compute_dtype, impl=self._chunk_impl(),
                scheme=self._draft_scheme, policy=self.shard_policy,
                cache_dtype=self.cache_dtype)
        return self._draft_chunk_steps[chunk]

    def _draft_step(self):
        if self._draft_decode_step is None:
            self._draft_decode_step = make_paged_serve_step(
                self.draft_cfg, self.mesh,
                compute_dtype=self.compute_dtype, impl=self.impl,
                scheme=self._draft_scheme, policy=self.shard_policy,
                cache_dtype=self.cache_dtype)
        return self._draft_decode_step

    def _verify_step(self, scheme: str):
        if scheme not in self._verify_steps:
            self._verify_steps[scheme] = make_verify_step(
                self.cfg, self.mesh, compute_dtype=self.compute_dtype,
                impl=self._chunk_impl(), scheme=scheme,
                policy=self.shard_policy, cache_dtype=self.cache_dtype)
        return self._verify_steps[scheme]

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill step shapes built so far: bounded by the number
        of chunk sizes (chunked mode) instead of prompt-length buckets."""
        return (len(self._chunk_steps) + len(self._prefills)
                + len(self._draft_chunk_steps))

    @property
    def spec_compiles(self) -> int:
        """Distinct speculative step shapes: verify steps (<= one per
        scheme, all at chunk k+1) + the single draft decode step."""
        return len(self._verify_steps) + (self._draft_decode_step is not None)

    def _pick_scheme(self, verify_k: int = 0) -> str:
        active = self.sched.active_slots
        cache_len = int(self.sched.lengths[active].max()) + 1 if active else 1
        if self.scheme != "auto":
            self._last_scheme = self.scheme
            return self.scheme
        s = auto_dispatch(self.mla, self.platform, cache_len=cache_len,
                          batch=max(len(active), 1),
                          paged_block=self.block_size,
                          dp_shards=self._dp, verify_k=verify_k,
                          cache_dtype=self.cache_dtype)
        if self._last_scheme is not None and s != self._last_scheme:
            self.stats.scheme_switches += 1
        self._last_scheme = s
        return s

    # -------------------------------------------------------- sampling ----

    def _sample_tokens(self, rows, slots) -> Dict[int, int]:
        """Sample one token per slot; ``rows`` (len(slots), V) carries the
        logits row of each listed slot (still occupied by its request).

        temperature <= 0: one batched greedy argmax.  Otherwise
        temperature / top-k sampling, one batched device call: per-slot
        keys fold(fold(seed, rid), position), position = absolute index
        of the sampled token in the request's full sequence — invariant
        under recompute preemption (the folded prompt grows by exactly
        the generated tokens), so replay drains the same PRNG stream per
        request regardless of batch composition and reproduces the same
        output."""
        if self.temperature <= 0.0:
            arg = np.asarray(jnp.argmax(rows, axis=-1))
            return {s: int(arg[i]) for i, s in enumerate(slots)}
        rids, poss = [], []
        for s in slots:
            req = self.sched.slots[s]
            rids.append(req.rid)
            poss.append(req.plen + len(req.tokens))
        toks = self._sample_rows(rows, rids, poss)
        return {s: int(toks[i]) for i, s in enumerate(slots)}

    def _sample_rows(self, rows, rids, poss) -> np.ndarray:
        """Temperature / top-k sample one token per logits row with the
        fold(fold(seed, rid), position) key stream (see _sample_tokens;
        also the verify positions of a speculative round — same keys, so
        spec-decode emits the exact tokens plain decode would)."""
        if self.mesh is not None:
            # Gather the (few-KB) logits rows to the host and re-feed them
            # as a single-device array: under the pre-0.5 jax default
            # (threefry_partitionable=False) the SAME random op lowered
            # over a sharded operand draws DIFFERENT bits than unsharded,
            # so sampling straight from the vocab-sharded logits would
            # silently fork the PRNG stream from the single-host engine.
            # Host-side rows make the sampled stream topology-invariant.
            rows = jnp.asarray(np.asarray(rows))
        return np.asarray(self._sample_fn(
            rows, jnp.asarray(rids, jnp.uint32),
            jnp.asarray(poss, jnp.uint32)))

    @functools.cached_property
    def _sample_fn(self):
        base, temp, top_k = self._sample_key, self.temperature, self.top_k

        def run(rows, rids, poss):
            keys = jax.vmap(lambda r, p: jax.random.fold_in(
                jax.random.fold_in(base, r), p))(rids, poss)
            rows = rows.astype(jnp.float32) / temp
            if top_k > 0:
                kth = jnp.sort(rows, axis=-1)[:, -top_k]
                rows = jnp.where(rows >= kth[:, None], rows, -jnp.inf)
            return jax.vmap(jax.random.categorical)(keys, rows)

        return jax.jit(run)

    # --------------------------------------------------------- prefill ----

    def _prefill_stage(self, admitted, step_i: int,
                       was_decoding: bool) -> None:
        """The tick's prefill stage, one path for both engines: account
        this tick's admissions, then prefill — chunked, one tick's chunk
        work over every prefilling row (:meth:`_run_chunked_prefill`); per
        request, each just-admitted prompt whole."""
        for _, req in admitted:
            self.stats.admissions += 1
            self.stats.prompt_tokens += req.plen
            if was_decoding:
                self.stats.mid_gen_admissions += 1
        chunked = self.prefill_mode == "chunked"
        if not (self.sched.prefilling if chunked else admitted):
            return
        with self.tel.tracer.span("prefill"):
            if chunked:
                self._run_chunked_prefill(step_i)
            else:
                self._run_per_request_prefill(admitted, step_i)
        # fork-group tail copies queued by fork_group must land
        # before both forks' decode writes dispatch
        self._drain_cow()

    def _run_chunked_prefill(self, step_i: int) -> None:
        """One tick's chunk work over the scheduler's prefilling rows.
        A chunk step advances every prefilling row by up to
        ``prefill_chunk`` UN-CACHED prompt tokens — rows admitted this
        tick beside rows part-way through — scattering latents straight
        into the pool.  While any row decodes, the tick runs ONE chunk
        step and its decode step follows, so running rows wait one chunk
        step, not a whole prompt; rows whose prompt that chunk ends take
        their first token at the start of the next tick's chunk work
        (:meth:`_first_tokens`), since the decode step dispatched behind
        the chunk would otherwise hold the host until both ran.  With
        none decoding nothing waits: chunk steps run back to back, and
        rows take their first token as their prompt ends, until a row
        decodes or none is left prefilling."""
        C = self.prefill_chunk
        step_fn = self._chunk_step(C)
        tr = self.tel.tracer
        if self._ended is not None:
            # rows preempted or cancelled since have left their slot
            logits, rows = self._ended
            self._ended = None
            self._first_tokens(logits, [
                s for s, req in rows if self.sched.slots[s] is req
                and self.sched.prefilling.get(s) == req.plen], step_i)
        while self.sched.prefilling:
            decoding = self.sched.n_active
            tokens, lens, nv, finishing = self.sched.next_chunk(C)
            with tr.span("prefill_chunk",
                         args={"rows": int(np.count_nonzero(nv)),
                               "tokens": int(nv.sum()),
                               "decoding": decoding},
                         detail=functools.partial(_chunk_rows, lens, nv)):
                logits, self.pool = step_fn(
                    self.params, jnp.asarray(tokens), self.pool,
                    jnp.asarray(self.sched.block_table), jnp.asarray(lens),
                    jnp.asarray(nv))
                if self.spec_k:
                    # the draft prefills the SAME chunk into its own pool,
                    # so a request can start drafting the moment it is
                    # admitted (prefix-cache hits skip both pools
                    # symmetrically: shared block ids carry valid latents
                    # in each)
                    _, self.draft_pool = self._draft_chunk_step(C)(
                        self.draft_params, jnp.asarray(tokens),
                        self.draft_pool, jnp.asarray(self.sched.block_table),
                        jnp.asarray(lens), jnp.asarray(nv))
            self.stats.prefill_tokens += int(nv.sum())
            self.stats.prefill_chunks += 1
            if decoding:
                self.stats.interleaved_chunks += 1
                if finishing:
                    self._ended = (logits, [(s, self.sched.slots[s])
                                            for s in finishing])
                break
            self._first_tokens(logits, finishing, step_i)
            if self.sched.n_active:
                break

    def _first_tokens(self, logits, slots, step_i: int) -> None:
        """Rows whose prompt ended in the chunk step that returned
        ``logits`` sample generated token #1 from their last-valid row,
        register their blocks in the radix cache, fork their group and
        decode from this tick on."""
        for slot in slots:
            tok = self._sample_tokens(logits[slot][None], [slot])[slot]
            # register blocks only now — their latents are in the pool
            self.sched.commit_prefill(slot)
            self._fork_and_seed(slot, logits[slot][None], step_i)
            if self.sched.record_prefill_sample(slot, tok, step_i) is None:
                self.pending[slot] = tok

    def _run_per_request_prefill(self, admitted, step_i: int) -> None:
        """PR-1's path: contiguous per-request prefill (bucketed capacities
        to bound recompiles) + whole-block scatter into the pool.  Kept
        for A/B benchmarking; incompatible with prefix sharing."""
        for slot, req in admitted:
            cap = blocks_for(req.plen, self.block_size) * self.block_size
            logits, entries = self._prefill(cap)(
                self.params, jnp.asarray(req.prompt, jnp.int32)[None])
            pages = jnp.asarray(self.sched.block_table[slot], jnp.int32)
            self.pool = scatter_prefill_to_paged(self.pool, entries, pages)
            self.stats.prefill_tokens += req.plen
            tok = self._sample_tokens(logits[0][None], [slot])[slot]
            self.sched.commit_prefill(slot)
            self._fork_and_seed(slot, logits[0][None], step_i)
            if self.sched.record_prefill_sample(slot, tok, step_i) is None:
                self.pending[slot] = tok

    # ------------------------------------------------------------- run ----

    def validate_sampling(self, sp) -> None:
        """Raise ValueError unless the per-request ``SamplingParams`` are
        servable by THIS engine.  Knobs that are engine-global
        (temperature / top_k / seed) must MATCH the engine's configuration
        when set: the async engine bakes them into the compiled fused
        step (make_paged_sample_step), so honoring a per-request override
        would mint a new compiled-step variant per value — exactly what
        the hot-path auditor's compiled-variant matrix forbids.  None
        always means 'inherit'.  The HTTP frontend calls this on the
        handler thread so a mismatch becomes a 400, not a worker death."""
        if sp is None:
            return
        if sp.temperature is not None \
                and float(sp.temperature) != self.temperature:
            raise ValueError(
                f"temperature={sp.temperature} != engine temperature "
                f"{self.temperature}; per-request overrides are baked "
                f"into the compiled step — set it engine-wide or leave "
                f"None to inherit")
        if sp.top_k is not None and int(sp.top_k) != self.top_k:
            raise ValueError(
                f"top_k={sp.top_k} != engine top_k {self.top_k}; set it "
                f"engine-wide or leave None")
        if sp.seed is not None and int(sp.seed) != self._sample_seed:
            raise ValueError(
                f"seed={sp.seed} != engine sample_seed "
                f"{self._sample_seed}; set it engine-wide or leave None")

    def submit(self, req: Request) -> None:
        self.validate_sampling(req.sampling)
        self.sched.submit(req)

    @property
    def idle(self) -> bool:
        """No queued, running or otherwise unaccounted work — the driver
        loop (runtime.loop.drive) may stop ticking."""
        return self.sched.all_done

    def request_cancel(self, rid: int) -> None:
        """Flag ``rid`` for cancellation.  Thread-safe: the frontend's
        connection handlers call this from their own threads; the engine
        drains the flags at the start of its next tick and releases the
        request's slot and blocks (scheduler.cancel)."""
        with self._cancel_lock:
            self._cancels.add(rid)

    def _process_cancels(self, step_i: int) -> None:
        with self._cancel_lock:
            rids, self._cancels = self._cancels, set()
        for rid in sorted(rids):
            self.sched.cancel(rid, step_i)

    def _drain_cow(self) -> None:
        """Apply the scheduler's queued copy-on-write block copies to the
        device pool(s).  Independent pairs batch into ONE device op per
        pool (core.cache.copy_blocks_paged), padded to the next power of
        two with (0, 0) null pairs — block 0 is the reserved NULL block,
        so copying it onto itself is a no-op — bounding compiled variants
        to log2(max batch).  A CHAINED batch (some dst re-read as a later
        src, e.g. preemption-replay cascades) must apply in queue order
        and falls back to sequential single-block copies."""
        pairs = self.sched.drain_cow()
        if not pairs:
            return
        srcs = [p[0] for p in pairs]
        dsts = [p[1] for p in pairs]
        if len(pairs) == 1 or (set(srcs) & set(dsts)):
            for src, dst in pairs:
                self.pool = self._copy_block(self.pool,
                                             jnp.asarray(src, jnp.int32),
                                             jnp.asarray(dst, jnp.int32))
                if self.draft_pool is not None:
                    # block-level ops track both pools (same geometry)
                    self.draft_pool = self._copy_block(
                        self.draft_pool, jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32))
            return
        n = 1
        while n < len(pairs):
            n *= 2
        pad = n - len(pairs)
        s = jnp.asarray(srcs + [0] * pad, jnp.int32)
        d = jnp.asarray(dsts + [0] * pad, jnp.int32)
        self.pool = self._copy_blocks(self.pool, s, d)
        if self.draft_pool is not None:
            self.draft_pool = self._copy_blocks(self.draft_pool, s, d)

    def _fork_and_seed(self, slot: int, row, step_i: int) -> None:
        """Fork a just-prefilled n > 1 parent (scheduler.fork_group) and
        sample every child's first token from the parent's last-position
        prefill logits — each on its OWN fold(child rid, position) key
        stream, so the group is token-identical to n independent
        requests.  Runs between commit_prefill and the parent's own
        record_prefill_sample: a parent finishing instantly (max_tokens
        == 1) has then already handed its children their refcounts."""
        kids = self.sched.fork_group(slot)
        if not kids:
            return
        cslots = [cs for cs, _ in kids]
        rows = jnp.broadcast_to(row, (len(kids),) + tuple(row.shape[1:]))
        picks = self._sample_tokens(rows, cslots)
        for cs in cslots:
            tok = picks[cs]
            if self.sched.record_prefill_sample(cs, tok, step_i) is None:
                self.pending[cs] = tok

    def _sync_device(self) -> None:
        """Block until this tick's device work has retired.  jax dispatch
        is asynchronous: without this barrier the step wall clock stops
        while decode/prefill launches are still in flight and ``wall`` /
        ``tokens_per_s`` measure dispatch, not compute (pinned by
        tests/test_obs.py)."""
        jax.block_until_ready(self.pool)
        if self.draft_pool is not None:
            jax.block_until_ready(self.draft_pool)

    def step(self) -> None:
        """One scheduler tick: admit, the prefill stage (one chunk step
        while rows decode), then one batched decode step over all slots."""
        t0 = time.perf_counter()
        step_i = self.stats.steps
        self._process_cancels(step_i)
        was_decoding = self.sched.n_active > 0
        tr = self.tel.tracer

        with tr.span("step"):
            with tr.span("schedule"):
                # grow running requests BEFORE admitting: otherwise a
                # just-admitted request could take the last blocks, get
                # preempted immediately, and throw away the prefill it
                # just paid for.
                self.stats.preemptions += len(
                    self.sched.ensure_step_capacity())
                self._drain_cow()
                admitted = self.sched.try_admit(step_i)
                # partial-hit tail copies queued by try_admit must land
                # before prefill gathers/writes touch the pool
                self._drain_cow()
            self._prefill_stage(admitted, step_i, was_decoding)

            active = self.sched.active_slots
            if active and self.spec_k:
                self._spec_round(active, step_i)
            elif active:
                scheme = self._pick_scheme()
                self.stats.schemes_used[scheme] = \
                    self.stats.schemes_used.get(scheme, 0) + 1
                step_fn = self._decode_step(scheme)
                table, lengths = self.sched.decode_view()
                with tr.span("device_step"):
                    logits, self.pool = step_fn(
                        self.params, jnp.asarray(self.pending),
                        self.pool, jnp.asarray(table), jnp.asarray(lengths))
                    jax.block_until_ready(self.pool)
                with tr.span("host_sample"):
                    picks = self._sample_tokens(logits[jnp.asarray(active)],
                                                active)
                    self.sched.advance(picks, step_i)
                for s, t in picks.items():
                    self.pending[s] = t
                self.stats.decode_tokens += len(active)

            u = self.sched.utilization()
            self.stats.util_valid_sum += u["valid_frac"]
            self.stats.util_pool_sum += u["pool_frac"]
            self.stats.util_samples += 1
            # close the wall clock only after the device work dispatched
            # this tick has retired — prefill-only and spec ticks return
            # before the pool write lands otherwise
            self._sync_device()
        self.stats.steps += 1
        dt = time.perf_counter() - t0
        self.stats.wall += dt
        if self.tel.metrics is not None:
            m = self.tel.metrics
            m.histogram("step_ms").record(dt * 1e3)
            m.histogram("pool_occupancy").record(u["pool_frac"])
            m.histogram("pool_allocated_bytes").record(
                u["allocated_blocks"] * self.block_size
                * self.cache_token_bytes)

    # ------------------------------------------------ speculative round ----

    def _spec_round(self, active, step_i: int) -> None:
        """One draft + verify + accept tick over all active slots.

        1. DRAFT: the draft model proposes up to k tokens per slot by
           plain paged decode against its own pool.  Proposals use the
           SAME decision rule as the target — greedy argmax, or
           temperature/top-k with the shared fold(rid, absolute position)
           key stream — so an identity draft proposes exactly what the
           target will sample (100% acceptance, the oracle property)
           under seeded sampling too, not just greedy.  The loop runs
           each slot's full write window (budget-clipped nv[s] = min(k+1,
           remaining)) so the LAST draft's latent is written too — the
           final iteration's proposal is discarded but its write is what
           keeps the draft pool complete when all k drafts are accepted.
           Slots whose window is exhausted freeze (same token re-written
           at the same position — idempotent), so one fixed step shape
           serves ragged windows.
        2. VERIFY: one chunked multi-query forward of the TARGET over
           [pending, d_1 .. d_{nv-1}] (runtime.steps.make_verify_step,
           chunk = k+1): the resident latent prefix streams from HBM once
           for all positions.  The target's own token at every position
           comes from the same greedy argmax / fold(rid, position) key
           stream plain decode uses.
        3. ACCEPT: leading drafts equal to the target's tokens are
           accepted; the round emits the accepted run plus one bonus /
           correction token (exactly what plain decode would have
           produced — runtime.spec.accept_length).  Rejection is a pure
           host-side length rewind: advance_multi moves ``lengths`` past
           the accepted run only; stale latents beyond it are masked by
           every attention path and overwritten before they can become
           visible.  Topology-independent: lengths are host numpy under
           any mesh (PR 4).
        """
        k = self.spec_k
        B = self.sched.max_batch
        tr = self.tel.tracer
        nv = np.zeros((B,), np.int32)
        for s in active:
            nv[s] = self.sched._window(self.sched.slots[s])
        # ---- 1. draft ---------------------------------------------------
        drafts = np.zeros((B, k), np.int32)
        d_pending = self.pending.copy()
        table, lengths = self.sched.decode_view()
        d_lens = lengths.copy()
        bt = jnp.asarray(table)
        d_step = self._draft_step()
        with tr.span("draft"):
            for j in range(int(nv.max())):
                d_logits, self.draft_pool = d_step(
                    self.draft_params, jnp.asarray(d_pending),
                    self.draft_pool, bt, jnp.asarray(d_lens))
                if self.temperature <= 0.0:
                    prop = np.asarray(jnp.argmax(d_logits, axis=-1))
                else:
                    # proposal at absolute position d_lens + 1 draws the
                    # same fold(rid, position) key the target uses to
                    # sample THAT position in verify — identical models
                    # propose identical tokens under seeded sampling
                    live = [s for s in active if j < nv[s] - 1]
                    prop = np.zeros((B,), np.int64)
                    if live:
                        toks = self._sample_rows(
                            d_logits[jnp.asarray(live)],
                            [self.sched.slots[s].rid for s in live],
                            [int(d_lens[s]) + 1 for s in live])
                        for i, s in enumerate(live):
                            prop[s] = toks[i]
                for s in active:
                    if j < nv[s] - 1:
                        drafts[s, j] = prop[s]
                        self.stats.spec_drafted += 1
                    if j + 1 < nv[s]:    # still drafting next iteration
                        d_pending[s] = prop[s]
                        d_lens[s] += 1
        # ---- 2. verify --------------------------------------------------
        tokens_v = np.zeros((B, k + 1), np.int32)
        for s in active:
            tokens_v[s, 0] = self.pending[s]
            tokens_v[s, 1:nv[s]] = drafts[s, :nv[s] - 1]
        scheme = self._pick_scheme(verify_k=k)
        self.stats.schemes_used[scheme] = \
            self.stats.schemes_used.get(scheme, 0) + 1
        with tr.span("verify"):
            logits_v, self.pool = self._verify_step(scheme)(
                self.params, jnp.asarray(tokens_v), self.pool, bt,
                jnp.asarray(lengths), jnp.asarray(nv))
            jax.block_until_ready(self.pool)
        with tr.span("host_sample"):
            if self.temperature <= 0.0:
                target = np.asarray(jnp.argmax(logits_v, axis=-1))  # (B, k+1)
            else:
                flat, rids, poss = [], [], []
                for s in active:
                    req = self.sched.slots[s]
                    base = req.plen + len(req.tokens)  # abs pos, next sample
                    for j in range(int(nv[s])):
                        flat.append((s, j))
                        rids.append(req.rid)
                        poss.append(base + j)
                rows = logits_v[jnp.asarray([s for s, _ in flat]),
                                jnp.asarray([j for _, j in flat])]
                toks = self._sample_rows(rows, rids, poss)
                target = np.zeros((B, k + 1), np.int64)
                for i, (s, j) in enumerate(flat):
                    target[s, j] = toks[i]
            # ---- 3. accept + host-side length rewind --------------------
            emitted = {}
            for s in active:
                t_s = target[s, :nv[s]]
                n_acc = speclib.accept_length(drafts[s, :nv[s] - 1], t_s)
                emitted[s] = [int(t) for t in t_s[:n_acc + 1]]
                self.stats.spec_accepted += n_acc
            self.sched.advance_multi(emitted, step_i)
        for s, toks in emitted.items():
            if self.sched.slots[s] is not None:
                self.pending[s] = toks[-1]
        self.stats.decode_tokens += sum(len(t) for t in emitted.values())
        self.stats.spec_rounds += 1
        self.stats.spec_slot_rounds += len(active)

    def run(self, requests: List[Request], *, max_steps: int = 100_000,
            log_every: int = 0, log=print) -> Dict[str, float]:
        """Drive a request stream to completion — delegates to
        :func:`runtime.loop.drive` (shared with the async engine and the
        HTTP frontend's worker)."""
        from .loop import drive
        return drive(self, requests, max_steps=max_steps,
                     log_every=log_every, log=log)

    def summary(self) -> Dict[str, float]:
        """Engine stats + prefix-cache stats + allocator totals."""
        out = self.stats.summary()
        out.update(self.sched.prefix.summary())
        out["total_blocks_allocated"] = float(
            self.sched.allocator.total_allocs)
        out["fork_groups"] = float(self.sched.fork_groups)
        out["fork_children"] = float(self.sched.forked_children)
        out["prefill_compiles"] = float(self.prefill_compiles)
        out["spec_compiles"] = float(self.spec_compiles)
        out["cache_dtype"] = self.cache_dtype
        out["cache_token_bytes"] = float(self.cache_token_bytes)
        return out


def _chunk_rows(lens, nv) -> Dict[str, str]:
    """Span detail of a chunk step: each row's (context, new tokens) as
    "context:new_tokens;...", the form the decode dispatch span uses."""
    return {"row_tokens": ";".join(f"{lens[i]}:{nv[i]}"
                                   for i in np.nonzero(nv)[0])}


# --------------------------------------------------------- async engine ----


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-unaccounted fused decode step."""
    tokens: object                       # (B,) int32 device array (future)
    entries: List[Tuple[int, Request]]   # (dispatch slot, request)
    deferred: List[Tuple[int, Request]]  # slot released, token value pending
    t_disp: float                        # tracer clock at dispatch
    scheme: str
    fetched: Optional[np.ndarray] = None  # host copy, once someone needed it


class AsyncPagedMLAEngine(PagedMLAEngine):
    """Double-buffered async engine: host work for tick N+1 overlaps the
    device's execution of tick N.

    Per tick (plain decode; ``spec_k > 0`` rounds drain the pipeline and
    run the synchronous :meth:`PagedMLAEngine._spec_round` — accept /
    rewind is value-dependent host work):

      1. *schedule* — while the device still runs the step dispatched
         last tick: requests whose in-flight token is structurally their
         last (``len(tokens) + 1 >= max_new``) release their slot and
         blocks immediately (the token VALUE arrives in step 3); block
         growth, preemption, CoW drain and admission run as usual.  CoW /
         prefill device ops enqueue AFTER the in-flight step in stream
         order, so the device-side op sequence is exactly the synchronous
         engine's.
      2. *prefill* — while rows decode, ONE chunk step over every
         prefilling row (:meth:`PagedMLAEngine._run_chunked_prefill`),
         enqueued behind the in-flight decode step; the next decode step
         enqueues behind it, with no host sync.  Rows whose prompt that
         chunk ended sample their first token at the start of the next
         tick's prefill (the chunk has run by then, or runs while the
         decode step behind it waits) and join that tick's dispatch.
         With no row decoding, chunk steps run back to back until a row
         decodes.
      3. *host_sample* — fetch the in-flight (B,) token array (the only
         device->host transfer; blocks for however much device time the
         host did NOT overlap), emit the retrospective ``device_step``
         span on the device-stream track, and account token values:
         append, stop-sequence checks, deferred finishes, ``pending``.
      4. *dispatch* — launch the fused decode+sample step
         (``make_paged_sample_step``) for the current actives and advance
         ``lengths`` structurally; the host returns without waiting.

    Token identity with the synchronous engine (greedy and seeded) holds
    because sampling keys fold (rid, absolute position) — invariant under
    batch composition and admission timing — and each logits row depends
    only on its own request's tokens/cache.  The one-tick-late accounting
    only shifts WHEN slots free up, never what any request's next token
    is.  Preempted victims with an unaccounted in-flight token fold it
    into their replayed prompt first (:meth:`_fixup_preempted` — the rare
    forced sync), so replay matches the synchronous fold exactly.
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._sample_steps: Dict[str, object] = {}
        self._inflight: Optional[_Inflight] = None
        self.tel.tracer.set_thread_name(PID_ENGINE, TID_DEVICE,
                                        "device stream")

    @property
    def idle(self) -> bool:
        return self.sched.all_done and self._inflight is None

    def _sample_step(self, scheme: str):
        if scheme not in self._sample_steps:
            self._sample_steps[scheme] = make_paged_sample_step(
                self.cfg, self.mesh, compute_dtype=self.compute_dtype,
                impl=self.impl, scheme=scheme, policy=self.shard_policy,
                cache_dtype=self.cache_dtype,
                temperature=self.temperature, top_k=self.top_k,
                sample_seed=self._sample_seed)
        return self._sample_steps[scheme]

    # ------------------------------------------------------------- tick ----

    def step(self) -> None:
        if self.spec_k:
            # drain, then run the synchronous spec tick: double-buffering
            # applies to plain decode; spec rounds are host-interactive.
            self._drain_inflight()
            return super().step()
        t0 = time.perf_counter()
        step_i = self.stats.steps
        self._process_cancels(step_i)
        was_decoding = self.sched.n_active > 0 or self._inflight is not None
        tr = self.tel.tracer

        with tr.span("step"):
            with tr.span("schedule"):
                self._release_structural_finishes()
                preempted = self.sched.ensure_step_capacity()
                self.stats.preemptions += len(preempted)
                if preempted:
                    self._fixup_preempted(preempted, step_i)
                self._drain_cow()
                admitted = self.sched.try_admit(step_i)
                # partial-hit tail copies queued by try_admit must land
                # before prefill gathers/writes touch the pool
                self._drain_cow()
            self._prefill_stage(admitted, step_i, was_decoding)

            self._account(step_i)

            active = self.sched.active_slots
            if active:
                self._dispatch(active)

            u = self.sched.utilization()
            self.stats.util_valid_sum += u["valid_frac"]
            self.stats.util_pool_sum += u["pool_frac"]
            self.stats.util_samples += 1
        self.stats.steps += 1
        dt = time.perf_counter() - t0
        self.stats.wall += dt
        if self.tel.metrics is not None:
            m = self.tel.metrics
            m.histogram("step_ms").record(dt * 1e3)
            m.histogram("pool_occupancy").record(u["pool_frac"])
            m.histogram("pool_allocated_bytes").record(
                u["allocated_blocks"] * self.block_size
                * self.cache_token_bytes)

    # --------------------------------------------------- pipeline stages ---

    def _release_structural_finishes(self) -> None:
        """Free the slots of in-flight requests whose pending token is
        structurally their last (budget-predicted — stop hits cannot be
        predicted and are discovered at account time, one tick later).
        Their blocks become admissible NOW, overlapping the device."""
        inf = self._inflight
        if inf is None:
            return
        keep = []
        for slot, req in inf.entries:
            if req.slot == slot and not req.finish_reason \
                    and len(req.tokens) + 1 >= req.max_new:
                self.sched._release_slot(slot)
                inf.deferred.append((slot, req))
            else:
                keep.append((slot, req))
        inf.entries = keep

    def _fixup_preempted(self, preempted: List[Request],
                         step_i: int) -> None:
        """Recompute-preemption under an unaccounted in-flight token: the
        scheduler already folded ``tokens`` into the victim's prompt; the
        in-flight token must join that fold for the replay to match the
        synchronous engine.  This is the one place the async engine is
        forced to sync early (preemptions are the overloaded-pool path)."""
        inf = self._inflight
        if inf is None:
            return
        victims = {id(r) for r in preempted}
        keep, fix = [], []
        for slot, req in inf.entries:
            (fix if id(req) in victims else keep).append((slot, req))
        inf.entries = keep
        if not fix:
            return
        if inf.fetched is None:
            inf.fetched = np.asarray(inf.tokens)
        for slot, req in fix:
            tok = int(inf.fetched[slot])
            req.prompt = np.concatenate(
                [req.prompt, np.asarray([tok], np.int32)])
            req.max_new -= 1
            self.stats.decode_tokens += 1
            # the folded token may complete a stop sequence — the sync
            # engine would have finished the request instead of
            # preempting it; finish it here (it is back on the waiting
            # queue) so it never replays past its stop.
            if self.sched._check_stop(req):
                self.sched.waiting.remove(req)
                req.finished_step = step_i
                req.finish_t = time.perf_counter()
                self.sched.retire(req)

    def _account(self, step_i: int) -> None:
        """Fetch and account the in-flight step's token values (the only
        device->host sync of a steady-state tick)."""
        inf = self._inflight
        if inf is None:
            return
        self._inflight = None
        tr = self.tel.tracer
        with tr.span("host_sample"):
            toks = inf.fetched if inf.fetched is not None \
                else np.asarray(inf.tokens)
            if tr.enabled:
                tr.complete(
                    "device_step", PID_ENGINE, TID_DEVICE,
                    inf.t_disp, tr.now(),
                    args={"scheme": inf.scheme,
                          "batch": len(inf.entries) + len(inf.deferred)})
            for slot, req in inf.entries:
                if req.finish_reason == "cancelled" or req.slot != slot:
                    continue
                tok = int(toks[slot])
                req.tokens.append(tok)
                self.stats.decode_tokens += 1
                self.sched._check_stop(req)
                if req.done:
                    self.sched._finish(slot, step_i)
                else:
                    self.pending[slot] = tok
            for slot, req in inf.deferred:
                if req.finish_reason == "cancelled":
                    continue
                tok = int(toks[slot])
                req.tokens.append(tok)
                self.stats.decode_tokens += 1
                self.sched._check_stop(req)
                if not req.finish_reason:
                    req.finish_reason = "length"
                req.finished_step = step_i
                req.finish_t = time.perf_counter()
                self.sched.retire(req)

    def _dispatch(self, active: List[int]) -> None:
        """Launch the fused decode+sample step for the current actives and
        return WITHOUT waiting; ``lengths`` advance structurally (the
        step writes each fed token's latent at position lengths[s])."""
        scheme = self._pick_scheme()
        self.stats.schemes_used[scheme] = \
            self.stats.schemes_used.get(scheme, 0) + 1
        step_fn = self._sample_step(scheme)
        B = self.sched.max_batch
        rids = np.zeros((B,), np.uint32)
        poss = np.zeros((B,), np.uint32)
        entries = []
        for s in active:
            req = self.sched.slots[s]
            rids[s] = req.rid
            poss[s] = req.plen + len(req.tokens)
            entries.append((s, req))
        tr = self.tel.tracer
        t_disp = tr.now()
        table, lengths = self.sched.decode_view()
        with tr.span("dispatch", args={"rows": len(active)},
                     detail=lambda: {"row_tokens": ";".join(
                         f"{lengths[s]}:1" for s in active)}):
            # jnp.array copies: the host mutates pending / block_table /
            # lengths while this step is in flight, and on the CPU
            # backend jnp.asarray may alias the numpy buffer
            tokens, self.pool = step_fn(
                self.params, jnp.array(self.pending), self.pool,
                jnp.array(table), jnp.array(lengths),
                jnp.asarray(rids), jnp.asarray(poss))
        self._inflight = _Inflight(
            tokens=tokens, entries=entries, deferred=[], t_disp=t_disp,
            scheme=scheme)
        for s in active:
            self.sched.lengths[s] += 1
            if int(self.sched.lengths[s]) % self.block_size == 0:
                # a generated block just structurally completed; its
                # latent write is in-flight, but any future consumer's
                # gather enqueues AFTER it in stream order
                self.sched.register_decode_blocks(s)

    def _drain_inflight(self) -> None:
        """Account any in-flight step immediately (spec ticks and external
        sync points need the pipeline empty)."""
        self._account(self.stats.steps)
