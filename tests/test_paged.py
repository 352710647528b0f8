"""Paged latent-KV cache + continuous batching: kernel/oracle agreement,
paged-vs-contiguous allclose equivalence across all four execution schemes
at ragged per-request lengths, scheduler unit tests, and end-to-end engine
equivalence (greedy tokens match per-request contiguous decode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
import repro.models as models
from repro.core import cache as cachelib
from repro.core import mla as mlalib
from repro.hwmodel import attention_costs as ac
from repro.hwmodel.platforms import PLATFORMS
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.mla_decode import mla_decode_paged_kernel
from repro.nn import module as nnm
from repro.runtime import (BlockAllocator, ContinuousScheduler,
                           PagedMLAEngine, Request,
                           make_prefill_step, make_serve_step)
from repro.runtime.scheduler import NULL_BLOCK

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}

MCFG = mlalib.MLAConfig(d_model=64, n_heads=4, q_lora_rank=48,
                        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                        v_head_dim=16)


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ----------------------------------------------------------- kernel level --


@pytest.mark.parametrize("B,H,Dl,Dr,bs,nb,N,idx", [
    (1, 4, 32, 8, 16, 2, 4, [0]),             # single block, first token
    (3, 4, 32, 8, 16, 4, 16, [37, 0, -1]),    # ragged + inactive slot
    (2, 8, 64, 16, 32, 3, 8, [95, 17]),       # full + partial
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_vs_oracle(B, H, Dl, Dr, bs, nb, N, idx, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, H, Dl + Dr), dtype)
    ckv = rand(ks[1], (N, bs, Dl), dtype)
    krope = rand(ks[2], (N, bs, Dr), dtype)
    rng = np.random.default_rng(1)
    bt = jnp.asarray(rng.integers(0, N, (B, nb)), jnp.int32)
    idx = jnp.asarray(idx, jnp.int32)
    out = mla_decode_paged_kernel(q, ckv, krope, bt, idx, interpret=True)
    want = ref.mla_decode_paged_ref(q, ckv, krope, bt, idx)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_kernel_matches_contiguous():
    """With an identity-style block table, paged == contiguous kernel."""
    B, H, Dl, Dr, bs, nb = 2, 4, 32, 8, 16, 4
    N = B * nb + 1
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (B, H, Dl + Dr))
    ckv_p = rand(ks[1], (N, bs, Dl))
    krope_p = rand(ks[2], (N, bs, Dr))
    bt = (1 + jnp.arange(B * nb, dtype=jnp.int32)).reshape(B, nb)
    ckv_c = ckv_p[bt].reshape(B, nb * bs, Dl)
    krope_c = krope_p[bt].reshape(B, nb * bs, Dr)
    for index in (0, 13, nb * bs - 1):
        got = mla_decode_paged_kernel(
            q, ckv_p, krope_p, bt, jnp.full((B,), index, jnp.int32),
            interpret=True)
        want = ref.mla_decode_ref(q, ckv_c, krope_c, index)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_paged_kernel_ignores_unreferenced_pages():
    """Poisoning pool blocks outside the table must not change results."""
    B, H, Dl, Dr, bs, nb, N = 1, 4, 32, 8, 8, 2, 6
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (B, H, Dl + Dr))
    ckv = rand(ks[1], (N, bs, Dl))
    krope = rand(ks[2], (N, bs, Dr))
    bt = jnp.asarray([[2, 4]], jnp.int32)
    idx = jnp.asarray([11], jnp.int32)
    out = mla_decode_paged_kernel(q, ckv, krope, bt, idx, interpret=True)
    poisoned = [i for i in range(N) if i not in (2, 4)]
    out_p = mla_decode_paged_kernel(
        q, ckv.at[jnp.asarray(poisoned)].set(1e4),
        krope.at[jnp.asarray(poisoned)].set(1e4), bt, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_p), atol=1e-6)


# ------------------------------------------------------------- core level --


def _filled_caches(params, lengths, S, bs, nb, N, seed=0):
    """Build per-request contiguous caches AND an equivalent paged pool
    with a scrambled block table from the same token history."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    hist = jnp.asarray(rng.standard_normal((B, S, MCFG.d_model)) * 0.1,
                       jnp.float32)
    pool = cachelib.paged_latent_cache(N, bs, MCFG.kv_lora_rank,
                                       MCFG.qk_rope_dim, jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, N))[:B * nb].reshape(B, nb),
                     jnp.int32)
    caches = []
    for b in range(B):
        c = cachelib.latent_cache(1, S, MCFG.kv_lora_rank, MCFG.qk_rope_dim,
                                  jnp.float32)
        L = int(lengths[b])
        if L:
            pos = jnp.arange(L)[None]
            ckv, krope = mlalib._kv_latent(params, MCFG, hist[b:b + 1, :L],
                                           pos)
            c = cachelib.update_latent(c, ckv, krope, 0)
            for t in range(L):
                pool = cachelib.update_latent_paged(
                    pool, bt[b:b + 1], jnp.asarray([t], jnp.int32),
                    ckv[:, t], krope[:, t])
        caches.append(c)
    return caches, pool, bt


@pytest.mark.parametrize("scheme", mlalib.SCHEMES)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_mla_decode_paged_equals_contiguous(scheme, use_kernel):
    """The acceptance criterion: paged decode allclose-equal to the
    contiguous path for naive/seq/rc/ru at ragged per-request lengths."""
    if scheme == "naive" and use_kernel:
        pytest.skip("naive has no kernel path (paper's strawman)")
    bs, nb, N = 8, 4, 20
    S = bs * nb
    lengths = np.asarray([5, 17, 0, S - 1], np.int32)
    B = len(lengths)
    params = nnm.init_params(jax.random.PRNGKey(0), mlalib.mla_defs(MCFG),
                             jnp.float32)
    params = mlalib.prepare_serving(params, MCFG, "ru")
    caches, pool, bt = _filled_caches(params, lengths, S, bs, nb, N)
    x_t = rand(jax.random.PRNGKey(7), (B, MCFG.d_model)) * 0.1

    want, caches_after = [], []
    for b in range(B):
        o, c2 = mlalib.mla_decode(params, MCFG, x_t[b:b + 1],
                                  dict(caches[b]), int(lengths[b]),
                                  scheme=scheme)
        want.append(np.asarray(o[0]))
        caches_after.append(c2)

    decode_kernel = None
    if use_kernel:
        def decode_kernel(q_full, ckv, krope, tables, idx, softmax_scale):
            return kops.mla_decode_paged_attention(
                q_full, ckv, krope, tables, idx, impl="kernel",
                softmax_scale=softmax_scale)
    got, pool2 = mlalib.mla_decode_paged(params, MCFG, x_t, pool, bt,
                                         lengths, scheme=scheme,
                                         decode_kernel=decode_kernel)
    np.testing.assert_allclose(np.asarray(got), np.stack(want),
                               atol=2e-5, rtol=2e-5)
    # and the new token landed at the right (page, slot), matching the
    # contiguous cache write
    for b in range(B):
        L = int(lengths[b])
        page = int(bt[b, L // bs])
        np.testing.assert_allclose(
            np.asarray(pool2["ckv"][page, L % bs]),
            np.asarray(caches_after[b]["ckv"][0, L]), atol=2e-6)
        np.testing.assert_allclose(
            np.asarray(pool2["krope"][page, L % bs]),
            np.asarray(caches_after[b]["krope"][0, L]), atol=2e-6)


def test_gather_scatter_roundtrip():
    pool = cachelib.paged_latent_cache(8, 4, 16, 8, jnp.float32)
    bt = jnp.asarray([[3, 1], [5, 2]], jnp.int32)
    for t in range(6):
        pool = cachelib.update_latent_paged(
            pool, bt, jnp.asarray([t, t], jnp.int32),
            jnp.full((2, 16), float(t)), jnp.full((2, 8), float(-t)))
    ckv, krope = cachelib.gather_latent_paged(pool, bt)
    for t in range(6):
        np.testing.assert_allclose(np.asarray(ckv[:, t]), float(t))
        np.testing.assert_allclose(np.asarray(krope[:, t]), float(-t))


# -------------------------------------------------------------- scheduler --


def test_allocator_reserves_null_and_refuses_overdraw():
    a = BlockAllocator(5)
    got = a.alloc(4)
    assert sorted(got) == [1, 2, 3, 4]       # block 0 never handed out
    assert NULL_BLOCK not in got
    assert a.alloc(1) is None                # overdraw refused, no change
    a.free([2, 3])
    assert a.num_free == 2
    with pytest.raises(ValueError):
        a.free([2])                          # double free detected
    with pytest.raises(ValueError):
        a.free([0])                          # null block is unfreeable


def test_scheduler_admission_refusal_and_reuse():
    # pool: 4 usable blocks of 4 tokens; each request needs 2 blocks
    s = ContinuousScheduler(num_blocks=5, block_size=4, max_batch=3)
    reqs = [Request(rid=i, prompt=np.arange(5, dtype=np.int32), max_new=3)
            for i in range(3)]
    for r in reqs:
        s.submit(r)
    admitted = s.try_admit()
    # 5-token prompt + 1 => 2 blocks each => only 2 of 3 fit
    assert [r.rid for _, r in admitted] == [0, 1]
    assert s.allocator.num_free == 0
    assert len(s.waiting) == 1               # head refused, stays queued
    # finishing request 0 frees its blocks; request 2 reuses them
    slot0 = admitted[0][0]
    blocks0 = set(s.blocks_of[slot0])
    s.slots[slot0].tokens = [1, 2]
    s.advance({slot0: 9})                    # third token -> done
    assert s.slots[slot0] is None
    assert (s.block_table[slot0] == NULL_BLOCK).all()
    assert s.lengths[slot0] == 0
    admitted2 = s.try_admit()
    assert [r.rid for _, r in admitted2] == [2]
    assert set(s.blocks_of[slot0]) == blocks0      # block reuse
    assert not s.waiting


def test_scheduler_grows_blocks_and_preempts():
    s = ContinuousScheduler(num_blocks=4, block_size=2, max_batch=2)
    a = Request(rid=0, prompt=np.zeros(1, np.int32), max_new=8)
    b = Request(rid=1, prompt=np.zeros(1, np.int32), max_new=8)
    s.submit(a), s.submit(b)
    assert len(s.try_admit()) == 2           # 1 block each, 1 spare
    s.record_prefill_sample(0, 5)
    s.record_prefill_sample(1, 5)
    s.advance({0: 5})                        # only a crosses the boundary
    assert int(s.lengths[0]) == 2 and int(s.lengths[1]) == 1
    pre = s.ensure_step_capacity()
    assert pre == [] and len(s.blocks_of[0]) == 2   # grew from the spare
    # now b crosses too; the pool is dry -> youngest (b) is preempted
    s.advance({0: 5, 1: 5})
    pre = s.ensure_step_capacity()
    assert [r.rid for r in pre] == [1]
    assert s.slots[1] is None and len(s.waiting) == 1
    w = s.waiting[0]
    assert w.n_preempted == 1 and w.tokens == []
    assert w.plen == 3                       # 1 prompt + 2 generated folded
    assert w.max_new == 8 - 2
    # the oldest request kept its blocks and keeps making progress
    assert len(s.blocks_of[0]) == 2 and s.slots[0] is a


def test_scheduler_prefill_sample_finishes_max_new_1():
    s = ContinuousScheduler(num_blocks=4, block_size=4, max_batch=1)
    s.submit(Request(rid=0, prompt=np.zeros(2, np.int32), max_new=1))
    (slot, req), = s.try_admit()
    done = s.record_prefill_sample(slot, 7)
    assert done is req and req.output == [7]
    assert s.all_done and s.allocator.num_free == 3


# ----------------------------------------------------- model/engine level --


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke("deepseek-v2-236b")
    params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                             jnp.float32)
    return cfg, params


def _contiguous_greedy(cfg, params, prompt, max_new):
    """Per-request contiguous prefill+decode (the pre-PR serving path)."""
    from repro.launch.serve import _prepare_mla
    params = _prepare_mla(params, cfg, "seq")
    capacity = len(prompt) + max_new + 1
    prefill = make_prefill_step(cfg, None, batch=1, capacity=capacity,
                                compute_dtype=jnp.float32, scheme="seq")
    step = make_serve_step(cfg, None, compute_dtype=jnp.float32,
                           scheme="seq")
    logits, cache = prefill(params, jnp.asarray(prompt, jnp.int32)[None])
    out = [int(jnp.argmax(logits[0]))]
    for i in range(max_new - 1):
        logits, cache = step(params, jnp.asarray(out[-1:], jnp.int32),
                             cache, len(prompt) + i)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_engine_tokens_match_contiguous(smoke_model):
    """End-to-end: ragged requests admitted mid-generation through the
    paged engine produce exactly the greedy tokens of the per-request
    contiguous path."""
    cfg, params = smoke_model
    rng = np.random.default_rng(3)
    specs = [(8, 5, 0), (12, 3, 1), (4, 7, 4)]    # (plen, gen, arrival)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (p,)).astype(np.int32),
                    max_new=g, arrival=a)
            for i, (p, g, a) in enumerate(specs)]
    eng = PagedMLAEngine(cfg, params, num_blocks=20, block_size=8,
                         max_batch=2, compute_dtype=jnp.float32,
                         scheme="seq")
    summary = eng.run([Request(rid=r.rid, prompt=r.prompt.copy(),
                               max_new=r.max_new, arrival=r.arrival)
                       for r in reqs])
    assert len(eng.sched.finished) == len(reqs)
    assert summary["mid_gen_admissions"] >= 1     # continuous batching
    by_rid = {r.rid: r for r in eng.sched.finished}
    for r in reqs:
        want = _contiguous_greedy(cfg, params, r.prompt, r.max_new)
        assert by_rid[r.rid].output == want, f"request {r.rid}"


def test_engine_auto_dispatch_runs(smoke_model):
    cfg, params = smoke_model
    rng = np.random.default_rng(5)
    eng = PagedMLAEngine(cfg, params, num_blocks=16, block_size=8,
                         max_batch=2, compute_dtype=jnp.float32,
                         scheme="auto", platform=PLATFORMS["tpu_v5e"])
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (8,)).astype(np.int32),
                    max_new=3, arrival=0) for i in range(2)]
    summary = eng.run(reqs)
    used = sum(summary["schemes_used"].values())
    assert 0 < used <= summary["steps"]
    assert set(summary["schemes_used"]) <= {"seq", "rc", "ru"}


def _run_outputs(cfg, params, reqs, *, num_blocks=40, **kw):
    eng = PagedMLAEngine(cfg, params, num_blocks=num_blocks, block_size=4,
                         max_batch=2, compute_dtype=jnp.float32,
                         scheme="seq", prefill_chunk=5, **kw)
    eng.run([Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new,
                     arrival=r.arrival) for r in reqs])
    return eng, {r.rid: r.output for r in eng.sched.finished}


def test_engine_pallas_prefill_token_identical(smoke_model):
    """End-to-end drive of the Pallas chunked-prefill path: the engine
    with impl='pallas' (kernel prefill AND kernel decode) produces
    token-identical outputs to the reference gather path, under greedy
    and under seeded temperature/top-k sampling."""
    cfg, params = smoke_model
    rng = np.random.default_rng(17)
    pre = rng.integers(0, cfg.vocab, (6,)).astype(np.int32)
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [pre, rng.integers(0, cfg.vocab, (p,)).astype(np.int32)]),
                    max_new=g, arrival=2 * i)
            for i, (p, g) in enumerate([(5, 4), (9, 3), (3, 5)])]
    _, outs_ref = _run_outputs(cfg, params, reqs, prefill_impl="gather")
    eng, outs_pal = _run_outputs(cfg, params, reqs, impl="pallas")
    assert outs_pal == outs_ref
    assert eng.stats.prefill_chunks > 0 and eng.prefill_compiles == 1
    # seeded sampling: same PRNG stream regardless of the prefill impl
    kw = dict(temperature=0.8, top_k=5, sample_seed=3)
    _, s_ref = _run_outputs(cfg, params, reqs, prefill_impl="gather", **kw)
    _, s_pal = _run_outputs(cfg, params, reqs, prefill_impl="pallas", **kw)
    assert s_pal == s_ref


def test_engine_pallas_prefill_survives_preemption_replay(smoke_model):
    """Recompute-preemption replay re-prefills through the Pallas kernel
    (the replayed prompt re-hits the prefix cache): outputs must match a
    preemption-free run exactly, under seeded sampling."""
    cfg, params = smoke_model
    rng = np.random.default_rng(19)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (6,)).astype(np.int32),
                    max_new=10) for i in range(2)]
    kw = dict(prefill_impl="pallas", temperature=0.7, top_k=8, sample_seed=1)
    _, big = _run_outputs(cfg, params, reqs, num_blocks=40, **kw)
    # 6 usable blocks of 4 tokens cannot hold 2 x (6 prompt + 10 gen):
    # the youngest request must be preempted and replayed
    small_eng, small = _run_outputs(cfg, params, reqs, num_blocks=7, **kw)
    assert small_eng.stats.preemptions > 0
    assert small == big


# ---------------------------------------------------------------- hwmodel --


def test_paged_cost_terms():
    base = ac.mla_decode_cost(ac.DSV3_MLA, scheme="seq", cache_len=1000,
                              batch=4)
    paged = ac.mla_decode_cost(ac.DSV3_MLA, scheme="seq", cache_len=1000,
                               batch=4, paged_block=128)
    assert "B:block_table" in paged.breakdown
    # whole-block reads: 1000 rounds up to 8 blocks x 128 = 1024 tokens
    ratio = paged.breakdown["B:cache_read"] / base.breakdown["B:cache_read"]
    assert ratio == pytest.approx(1024 / 1000)
    assert paged.bytes > base.bytes
    assert paged.flops == base.flops          # paging is a bytes-only term


def test_auto_dispatch_accepts_paged_block():
    from repro.core.schemes import auto_dispatch
    s = auto_dispatch(ac.DSV3_MLA, PLATFORMS["tpu_v5e"], cache_len=4096,
                      batch=8, paged_block=64)
    assert s in ("seq", "rc", "ru")


def test_auto_dispatch_prices_the_attached_tpu_by_device_kind(monkeypatch):
    from types import SimpleNamespace
    from repro.hwmodel import platforms as plat
    assert plat.device_platform(
        SimpleNamespace(device_kind="TPU v5 lite")) is PLATFORMS["tpu_v5e"]
    with pytest.raises(KeyError, match="TPU v9"):
        plat.device_platform(SimpleNamespace(device_kind="TPU v9"))
    # a named point is what-if pricing, on any backend
    assert plat.resolve_platform("h100") is PLATFORMS["h100"]
    assert plat.resolve_platform() is PLATFORMS["tpu_v5e"]     # CPU host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="TPU v4")])
    assert plat.resolve_platform() is PLATFORMS["tpu_v4"]
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="TPU v9")])
    with pytest.raises(KeyError):
        plat.resolve_platform()


# ------------------------------------------------ interleaved prefill ----


def _interleave_engine(cls, cfg, params, *, max_batch=3, num_blocks=96,
                       **kw):
    return cls(cfg, params, num_blocks=num_blocks, block_size=8,
               max_batch=max_batch, max_blocks_per_req=24,
               compute_dtype=jnp.float32, scheme="seq", prefill_chunk=16,
               **kw)


def _req(rid, prompt, max_tokens, n=1):
    from repro.runtime import SamplingParams
    return Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                   sampling=SamplingParams(max_tokens=max_tokens, n=n))


def _decoding(eng, reqs):
    """Tick until every request in ``reqs`` decodes."""
    for r in reqs:
        eng.submit(r)
    while not all(r.slot in eng.sched.active_slots for r in reqs):
        eng.step()


@pytest.mark.parametrize("engine", ["sync", "async"])
def test_prefill_interleaves_one_chunk_per_tick_while_rows_decode(
        smoke_model, engine):
    """With rows decoding, a 128-token prompt at prefill_chunk=16 takes 8
    ticks of one chunk step each, and every running row gains one token
    per tick meanwhile; the row samples its first token and decodes from
    the ninth tick on."""
    from repro.runtime import AsyncPagedMLAEngine
    cls = {"sync": PagedMLAEngine, "async": AsyncPagedMLAEngine}[engine]
    cfg, params = smoke_model
    rng = np.random.default_rng(23)
    eng = _interleave_engine(cls, cfg, params)
    running = [_req(i, rng.integers(0, cfg.vocab, 8), 40) for i in range(2)]
    _decoding(eng, running)
    eng.step()                                    # steady state (async)
    long = _req(9, rng.integers(0, cfg.vocab, 128), 4)
    eng.submit(long)
    chunks0 = eng.stats.prefill_chunks
    for tick in range(1, 10):
        before = [len(r.tokens) for r in running]
        eng.step()
        assert [len(r.tokens) for r in running] == [b + 1 for b in before]
        assert eng.stats.prefill_chunks - chunks0 == min(tick, 8)
        if tick <= 8:
            assert eng.sched.prefilling == {long.slot: 16 * tick}
            assert long.slot not in eng.sched.active_slots
            assert long.tokens == []
    assert eng.sched.prefilling == {}
    assert long.slot in eng.sched.active_slots and long.tokens
    assert eng.stats.interleaved_chunks == 8
    assert eng.prefill_compiles == 1


def test_prefill_runs_to_its_end_in_one_tick_when_no_row_decodes(
        smoke_model):
    cfg, params = smoke_model
    rng = np.random.default_rng(29)
    eng = _interleave_engine(PagedMLAEngine, cfg, params)
    req = _req(0, rng.integers(0, cfg.vocab, 128), 4)
    eng.submit(req)
    eng.step()
    assert eng.stats.prefill_chunks == 8 and eng.stats.steps == 1
    assert eng.stats.interleaved_chunks == 0
    assert eng.sched.prefilling == {}
    assert eng.sched.active_slots == [req.slot]
    assert len(req.tokens) == 2           # prefill sample + this tick's decode


def test_request_admitted_mid_prefill_joins_the_ongoing_chunk_steps(
        smoke_model):
    cfg, params = smoke_model
    rng = np.random.default_rng(31)
    eng = _interleave_engine(PagedMLAEngine, cfg, params, max_batch=4)
    running = [_req(0, rng.integers(0, cfg.vocab, 8), 40)]
    _decoding(eng, running)
    first = _req(1, rng.integers(0, cfg.vocab, 128), 4)
    eng.submit(first)
    for _ in range(3):
        eng.step()
    assert eng.sched.prefilling == {first.slot: 48}
    late = _req(2, rng.integers(0, cfg.vocab, 40), 4)
    eng.submit(late)
    chunks0 = eng.stats.prefill_chunks
    eng.step()
    # one chunk step served both rows
    assert eng.stats.prefill_chunks == chunks0 + 1
    assert eng.sched.prefilling == {first.slot: 64, late.slot: 16}
    eng.step(), eng.step()
    assert eng.sched.prefilling == {first.slot: 96, late.slot: 40}
    eng.step()
    assert late.slot in eng.sched.active_slots         # 40 tokens: 3 chunks
    assert eng.sched.prefilling == {first.slot: 112}
    assert eng.stats.prefill_chunks == chunks0 + 4
    while eng.sched.prefilling:
        eng.step()
    assert eng.stats.prefill_chunks == chunks0 + 5     # 128 = 8 chunks
    # the interleaved requests read exactly what they read served alone
    eng.run([])
    for r in (first, late):
        alone = _interleave_engine(PagedMLAEngine, cfg, params)
        alone.run([_req(r.rid, r.prompt, 4)])
        out, = alone.sched.finished
        assert r.output == out.output


@pytest.mark.parametrize("ticks", [2, 8], ids=["mid-prompt", "prompt-done"])
def test_cancelled_prefilling_row_frees_blocks_and_caches_nothing(
        smoke_model, ticks):
    """Cancelled part-way through its prompt, or after its last chunk and
    before it sampled its first token (the next tick's work)."""
    cfg, params = smoke_model
    rng = np.random.default_rng(37)
    eng = _interleave_engine(PagedMLAEngine, cfg, params)
    running = _req(0, rng.integers(0, cfg.vocab, 8), 40)
    _decoding(eng, [running])
    long = _req(1, rng.integers(0, cfg.vocab, 128), 4)
    eng.submit(long)
    for _ in range(ticks):
        eng.step()
    slot = long.slot
    blocks = list(eng.sched.blocks_of[slot])
    assert eng.sched.prefilling == {slot: 16 * ticks}
    eng.request_cancel(long.rid)
    eng.step()
    assert long.finish_reason == "cancelled" and long.slot == -1
    assert long.tokens == []
    assert eng.sched.prefilling == {} and eng.sched.slots[slot] is None
    assert all(b not in eng.sched.allocator.refcount for b in blocks)
    assert eng.sched.prefix.lookup_len(long.prompt) == 0
    eng.run([])
    assert running.finish_reason == "length"


@pytest.mark.parametrize("n", [1, 2])
def test_preempted_prefilling_row_frees_blocks_and_caches_nothing(n):
    """Scheduler level: the pool runs dry while a row (with its seated
    fork child, for n=2) is part-way through its prompt; the row is the
    youngest, so it is preempted, releases every block and registers
    nothing, and is admitted again (children and all) later."""
    s = ContinuousScheduler(num_blocks=10, block_size=2, max_batch=3)
    a = _req(0, np.arange(2), 40)
    s.submit(a)
    (sa, _), = s.try_admit()
    s.commit_prefill(sa)
    s.record_prefill_sample(sa, 1)
    b = _req(1, np.arange(10, 20), 2, n=n)
    s.submit(b)
    (sb, _), = s.try_admit()
    held = [sb] + [c.slot for c in b.fork_children]
    held_blocks = [blk for sl in held for blk in s.blocks_of[sl]]
    assert s.active_slots == [sa] and set(s._held()) == set(held)
    tokens, lens, nv, finishing = s.next_chunk(4)
    assert nv[sb] == 4 and finishing == [] and s.prefilling == {sb: 4}
    # a decodes until its growth finds the pool dry
    for _ in range(20):
        pre = s.ensure_step_capacity()
        if pre:
            break
        s.advance({sa: 1})
    assert pre == [b] and s.waiting[0] is b and b.tokens == []
    assert s.prefilling == {} and all(s.slots[sl] is None for sl in held)
    # every block b's group held is free again or grew a
    assert set(held_blocks) <= s.allocator._free_set | set(s.blocks_of[sa])
    assert s.prefix.lookup_len(b.prompt) == 0
    assert s.active_slots == [sa]
    assert all(c.slot == -1 for c in b.fork_children)
    s.cancel(a.rid)
    (sb2, again), = s.try_admit()
    assert again is b and s.prefilling == {sb2: 0}
    assert all(c.slot >= 0 for c in b.fork_children)


def test_decode_view_hides_rows_that_do_not_decode_yet():
    s = ContinuousScheduler(num_blocks=16, block_size=2, max_batch=3)
    s.submit(_req(0, np.arange(3), 4))
    (sa, _), = s.try_admit()
    s.commit_prefill(sa)
    s.submit(_req(1, np.arange(5), 4, n=2))
    (sb, b), = s.try_admit()
    sc = b.fork_children[0].slot
    table, lengths = s.decode_view()
    assert (table[[sb, sc]] == NULL_BLOCK).all() and (lengths[[sb, sc]] == 0).all()
    assert (table[sa] == s.block_table[sa]).all() and lengths[sa] == 3
    assert s.block_table[sb, 0] != NULL_BLOCK and s.lengths[sb] == 5
    s.commit_prefill(sb)
    s.fork_group(sb)
    assert s.active_slots == [sa, sb, sc]
    table, lengths = s.decode_view()
    assert table is s.block_table and lengths is s.lengths
