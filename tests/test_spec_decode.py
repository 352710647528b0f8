"""Speculative decoding on the paged MLA runtime.

The load-bearing claim (ISSUE 5 acceptance): spec-decode emits tokens
IDENTICAL to plain paged decode under greedy AND seeded sampling — the
target samples its own token at every verify position with the same
fold(rid, absolute position) keys plain decode uses, and drafts are
accepted only on exact match (runtime.spec.accept_length), so draft
quality moves throughput, never tokens.  Sharded parity lives in
tests/test_mesh_paged.py-style subprocess drivers here under the ``mesh``
marker.

Coverage:
  * accept_length unit semantics; shallow_draft layer slicing (params
    shared by reference, plan-consistent reassembly);
  * engine greedy + seeded parity vs plain decode for the identity draft
    (the oracle: acceptance MUST be 100%) and a shallow self-speculation
    draft (rejections exercised), across schemes, k, and the Pallas
    kernel path;
  * budget clipping (max_new < k + 1 requests), recompute-preemption
    replay mid-draft, and the radix prefix cache: rejected drafts must
    never leave stale blocks registered in the trie (every registered
    path is a prompt prefix; refcounts match live references);
  * scheduler decode_window reservations + advance_multi guards;
  * hwmodel mla_verify_cost: k = 0 degrades to the decode cost,
    amortization terms, break-even, and verify-aware auto_dispatch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
import repro.models as models
from repro.core.schemes import auto_dispatch, step_time, verify_time
from repro.hwmodel import attention_costs as ac
from repro.hwmodel.platforms import PLATFORMS
from repro.nn import module as nnm
from repro.runtime import (ContinuousScheduler, PagedMLAEngine, Request,
                           accept_length, identity_draft, shallow_draft)

MLA = ac.DSV3_MLA


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke("deepseek-v2-236b")
    params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                             jnp.float32)
    return cfg, params


def _mkreqs(seed=7, vocab=256, shared_prefix=0,
            specs=((6, 7, 0), (9, 5, 1), (5, 9, 3))):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, vocab, (shared_prefix,)).astype(np.int32)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [pre, rng.integers(0, vocab, (p,)).astype(np.int32)]),
                    max_new=g, arrival=a)
            for i, (p, g, a) in enumerate(specs)]


def _run(cfg, params, reqs, *, spec_k=0, draft=None, num_blocks=40,
         block_size=4, max_batch=2, scheme="seq", **kw):
    dcfg = dparams = None
    if draft == "self":
        dcfg, dparams = identity_draft(cfg, params)
    elif draft is not None:
        dcfg, dparams = shallow_draft(cfg, params, draft)
    eng = PagedMLAEngine(cfg, params, num_blocks=num_blocks,
                         block_size=block_size, max_batch=max_batch,
                         compute_dtype=jnp.float32, scheme=scheme,
                         platform=PLATFORMS["tpu_v5e"], prefill_chunk=5,
                         spec_k=spec_k, draft_cfg=dcfg,
                         draft_params=dparams, **kw)
    eng.run([Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new,
                     arrival=r.arrival) for r in reqs])
    return eng, {r.rid: r.output for r in eng.sched.finished}


# ------------------------------------------------------------- unit level --


def test_accept_length_semantics():
    t = np.asarray([5, 6, 7, 8])
    assert accept_length(np.asarray([5, 6, 7]), t) == 3   # all accepted
    assert accept_length(np.asarray([5, 6, 9]), t) == 2   # first mismatch
    assert accept_length(np.asarray([9, 6, 7]), t) == 0
    assert accept_length(np.asarray([], np.int32), t[:1]) == 0  # k = 0
    # targets shorter than drafts (budget-clipped window): capped
    assert accept_length(np.asarray([5, 6, 7]), t[:2]) == 1


def test_shallow_draft_slices_target_layers(smoke_model):
    cfg, params = smoke_model
    dcfg, dparams = shallow_draft(cfg, params, 2)
    assert dcfg.n_layers == 2 and dcfg.vocab == cfg.vocab
    # embed / final norm shared by reference, not copied
    assert dparams["embed"] is params["embed"]
    assert dparams["ln_f"] is params["ln_f"]
    # layer 0 (the first_dense prefix layer) shared by reference; the
    # fixed layer_plan keeps it in the plan even below one full period
    assert dparams["prefix"]["l0"] is params["prefix"]["l0"]
    # layer 1 == period slice 0 of the target, leaf for leaf
    got = jax.tree.leaves(dparams["prefix"]["l1"])
    want = jax.tree.leaves(jax.tree.map(lambda a: a[0],
                                        params["period"]["s0"]))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the draft tree matches the draft config's own param defs
    ref = jax.eval_shape(lambda: nnm.init_params(
        jax.random.PRNGKey(0), models.model_defs(dcfg), jnp.float32))
    assert jax.tree.structure(ref) == jax.tree.structure(dparams)
    with pytest.raises(ValueError):
        shallow_draft(cfg, params, cfg.n_layers)


def test_engine_validates_spec_arguments(smoke_model):
    cfg, params = smoke_model
    kw = dict(num_blocks=8, block_size=4, max_batch=1,
              compute_dtype=jnp.float32, scheme="seq")
    with pytest.raises(ValueError):
        PagedMLAEngine(cfg, params, spec_k=2, **kw)    # no draft
    with pytest.raises(NotImplementedError):
        PagedMLAEngine(cfg, params, spec_k=2, draft_cfg=cfg,
                       draft_params=params, prefill_mode="per_request",
                       prefill_chunk=4, **kw)


# -------------------------------------------------------- scheduler level --


def test_scheduler_window_reserves_verify_blocks():
    s = ContinuousScheduler(num_blocks=16, block_size=4, max_batch=1,
                            decode_window=4)
    s.submit(Request(rid=0, prompt=np.zeros(5, np.int32), max_new=10))
    [(slot, req)] = s.try_admit()
    # admission reserves plen + window = 9 tokens -> 3 blocks (plain
    # decode would reserve blocks_for(6) = 2)
    assert len(s.blocks_of[slot]) == 3
    req.tokens.append(1)                      # prefill sample
    s.lengths[slot] = 5
    s.ensure_step_capacity()                  # window 4 -> 9 tokens: holds
    assert len(s.blocks_of[slot]) == 3
    s.advance_multi({slot: [2, 3, 4, 5]})     # full window accepted
    assert int(s.lengths[slot]) == 9 and req.tokens == [1, 2, 3, 4, 5]
    s.ensure_step_capacity()                  # 9 + window(4) -> 13: grow
    assert len(s.blocks_of[slot]) == 4


def test_scheduler_window_clips_to_budget_and_guards_overflow():
    s = ContinuousScheduler(num_blocks=16, block_size=4, max_batch=1,
                            decode_window=4)
    s.submit(Request(rid=0, prompt=np.zeros(5, np.int32), max_new=2))
    [(slot, req)] = s.try_admit()
    # window clipped to the remaining budget: plen + 2 -> 2 blocks
    assert len(s.blocks_of[slot]) == 2
    req.tokens.append(1)
    s.lengths[slot] = 5
    with pytest.raises(ValueError):           # 2 emitted > window 1
        s.advance_multi({slot: [2, 3]})
    done = s.advance_multi({slot: [2]})
    assert done and done[0].output == [1, 2]


# ----------------------------------------------------------- engine level --


@pytest.mark.parametrize("k", [1, 2, 4])
def test_spec_identity_draft_is_token_identical_and_fully_accepted(
        smoke_model, k):
    """Draft == target: every draft must be accepted (the machinery
    oracle), and outputs must equal plain paged decode exactly."""
    cfg, params = smoke_model
    reqs = _mkreqs()
    _, plain = _run(cfg, params, reqs)
    eng, out = _run(cfg, params, reqs, spec_k=k, draft="self")
    assert out == plain
    assert eng.stats.spec_drafted > 0
    assert eng.stats.spec_accepted == eng.stats.spec_drafted
    assert eng.stats.spec_rounds < sum(r.max_new for r in reqs)
    s = eng.summary()
    assert s["spec_accept_rate"] == 1.0 and s["spec_mean_emitted"] > 1.0


@pytest.mark.parametrize("scheme", ["seq", "rc", "ru", "auto"])
def test_spec_shallow_draft_greedy_parity_across_schemes(smoke_model,
                                                         scheme):
    cfg, params = smoke_model
    reqs = _mkreqs()
    _, plain = _run(cfg, params, reqs, scheme=scheme)
    eng, out = _run(cfg, params, reqs, spec_k=2, draft=2, scheme=scheme)
    assert out == plain
    # shallow drafts on this config do get rejections — the rewind path
    # is actually exercised (if this ever goes flaky, lower the seed's
    # agreement, not the assert)
    assert eng.stats.spec_accepted < eng.stats.spec_drafted


def test_spec_seeded_sampling_parity(smoke_model):
    """Temperature/top-k: the verify positions consume the same
    fold(rid, position) key stream as plain decode."""
    cfg, params = smoke_model
    reqs = _mkreqs()
    kw = dict(temperature=0.8, top_k=5, sample_seed=3)
    _, plain = _run(cfg, params, reqs, **kw)
    eng_i, out_i = _run(cfg, params, reqs, spec_k=2, draft="self", **kw)
    eng_s, out_s = _run(cfg, params, reqs, spec_k=3, draft=2, **kw)
    assert out_i == plain and out_s == plain
    assert eng_i.stats.spec_accepted == eng_i.stats.spec_drafted


@pytest.mark.kernel
def test_spec_parity_on_pallas_kernel_path(smoke_model):
    """Verify + prefill through the fused paged kernels (decode kernel +
    multi-query prefill kernel in interpret mode on CPU)."""
    cfg, params = smoke_model
    reqs = _mkreqs()
    _, plain = _run(cfg, params, reqs)
    _, out = _run(cfg, params, reqs, spec_k=2, draft=2, impl="kernel",
                  prefill_impl="pallas")
    assert out == plain


def test_spec_budget_clipping_short_requests(smoke_model):
    """max_new < k + 1: the verify window clips to the remaining budget,
    outputs stay identical and never overshoot max_new."""
    cfg, params = smoke_model
    reqs = _mkreqs(specs=((6, 1, 0), (7, 2, 0), (5, 5, 1)))
    _, plain = _run(cfg, params, reqs)
    eng, out = _run(cfg, params, reqs, spec_k=3, draft="self")
    assert out == plain
    assert all(len(out[r.rid]) == r.max_new for r in reqs)


def test_spec_preemption_replay_identical(smoke_model):
    """A request preempted mid-generation under spec decoding replays to
    the same tokens (position-keyed sampling + window-aware growth)."""
    cfg, params = smoke_model
    rng = np.random.default_rng(19)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (6,)).astype(np.int32),
                    max_new=10) for i in range(2)]
    kw = dict(temperature=0.7, top_k=8, sample_seed=1)
    _, big = _run(cfg, params, reqs, num_blocks=40, spec_k=2, draft=2, **kw)
    _, plain = _run(cfg, params, reqs, num_blocks=40, **kw)
    assert big == plain
    small_eng, small = _run(cfg, params, reqs, num_blocks=7, spec_k=2,
                            draft=2, **kw)
    assert small_eng.stats.preemptions > 0
    assert small == plain


def _trie_paths(node, acc=()):
    out = []
    for key, child in node.children.items():
        path = acc + key
        out.append((child.block, path))
        out.extend(_trie_paths(child, path))
    return out


def test_spec_rejections_leave_no_stale_prefix_blocks(smoke_model):
    """Rejected drafts must never surface through the radix cache: every
    registered trie path is a prefix of some request's COMMITTED stream
    (prompt + emitted output — decode-filled blocks are trie-registered
    at block boundaries, but drafts are only ever written past
    ``lengths`` and never committed), refcounts match live block tables,
    and a second wave re-hitting the shared preamble still decodes
    token-identically."""
    cfg, params = smoke_model
    reqs = _mkreqs(shared_prefix=8,
                   specs=((6, 7, 0), (9, 5, 1), (5, 9, 3), (7, 6, 30),
                          (6, 8, 31)))
    _, plain = _run(cfg, params, reqs)
    eng, out = _run(cfg, params, reqs, spec_k=2, draft=2)
    assert out == plain
    assert eng.stats.spec_accepted < eng.stats.spec_drafted  # rejections
    assert eng.summary()["prefix_hit_rate"] > 0               # cache used
    streams = [list(r.prompt) + [int(t) for t in r.output]
               for r in eng.sched.finished]
    for block, path in _trie_paths(eng.sched.prefix.root):
        assert any(list(path) == s[:len(path)] for s in streams), \
            f"block {block} caches tokens that were never committed"
    live = {}
    for slot, blocks in eng.sched.blocks_of.items():
        for b in blocks:
            live[b] = live.get(b, 0) + 1
    eng.sched.prefix.check_invariants(live)


def test_spec_draft_pool_stays_consistent_under_cow(smoke_model):
    """CoW block copies are applied to BOTH pools; the draft pool mirrors
    the target stream, so acceptance of the identity draft stays 100%
    even with prefix sharing + second-wave re-admission."""
    cfg, params = smoke_model
    reqs = _mkreqs(shared_prefix=8,
                   specs=((6, 5, 0), (6, 5, 1), (6, 5, 20), (6, 5, 21)))
    _, plain = _run(cfg, params, reqs)
    eng, out = _run(cfg, params, reqs, spec_k=2, draft="self")
    assert out == plain
    assert eng.stats.spec_accepted == eng.stats.spec_drafted


# ---------------------------------------------------------------- hwmodel --


def test_verify_cost_k0_degrades_to_decode():
    kw = dict(scheme="seq", batch=4, paged_block=128, dp_shards=2)
    dec = ac.mla_decode_cost(MLA, cache_len=1024, **kw)
    ver = ac.mla_verify_cost(MLA, cache_len=1023, k=0, **kw)
    for term in ("B:w_common", "B:w_scheme", "B:cache_read",
                 "B:block_table", "q_down", "kv_down", "attn_scores",
                 "attn_out", "v_up", "o_proj", "q_up", "q_latent"):
        assert ver.breakdown[term] == pytest.approx(dec.breakdown[term]), term
    assert ver.breakdown["B:cache_write"] == dec.breakdown["B:cache_write"]


@pytest.mark.parametrize("scheme", ["seq", "rc", "ru", "naive"])
def test_verify_cost_amortizes_shared_streams(scheme):
    """Bytes per window token fall with k (weights + cache read are paid
    once per round); per-query FLOPs scale ~linearly with the window."""
    kw = dict(scheme=scheme, cache_len=4096, batch=8, paged_block=128)
    costs = [ac.mla_verify_cost(MLA, k=k, **kw) for k in (0, 2, 4, 8)]
    per_tok = [c.bytes / (k + 1) for c, k in zip(costs, (0, 2, 4, 8))]
    assert per_tok == sorted(per_tok, reverse=True)
    if scheme != "naive":     # naive spills the up-projected cache: bytes
        assert per_tok[-1] < 0.25 * per_tok[0]   # scale with the window
    assert all(a.flops < b.flops for a, b in zip(costs, costs[1:]))
    if scheme in ("seq", "ru"):
        # every FLOP term is per-query here, so work scales ~(k + 1);
        # rc amortizes its batch-shared absorb recompute and naive its
        # cache up-projection, so their ratios are deliberately smaller
        assert costs[-1].flops > 5 * costs[0].flops


def test_spec_break_even_and_verify_dispatch():
    be = ac.spec_break_even(MLA, scheme="seq", cache_len=4096, k=4,
                            batch=8, paged_block=128)
    # one verify round costs barely more than one decode step in bytes ->
    # break-even expected accepted length is close to (and >=) 1
    assert 1.0 <= be["break_even_emitted"] < 2.0
    assert be["amortization_at_full_accept"] > 2.0
    assert be["bytes_per_token_best"] < be["decode_bytes"]
    # draft overhead shifts the break-even up
    be_d = ac.spec_break_even(MLA, scheme="seq", cache_len=4096, k=4,
                              batch=8, paged_block=128,
                              draft_bytes_frac=0.25)
    assert be_d["break_even_emitted"] > be["break_even_emitted"]
    with pytest.raises(ValueError):
        ac.mla_verify_cost(MLA, scheme="seq", cache_len=16, k=-1)
    # verify-aware dispatch returns a sane scheme and differs from the
    # plain path only through the verify cost model
    plat = PLATFORMS["tpu_v5e"]
    s = auto_dispatch(MLA, plat, cache_len=4096, batch=8, paged_block=128,
                      verify_k=4)
    assert s in ("seq", "rc", "ru")
    assert verify_time(s, MLA, plat, 4096, 4, 8, paged_block=128) \
        <= verify_time("naive", MLA, plat, 4096, 4, 8, paged_block=128)
    # k-token amortization on the time axis too: a verify round is far
    # cheaper than k + 1 decode steps at the bandwidth-bound point
    t_dec = step_time(s, MLA, plat, 4096, 8, paged_block=128)
    t_ver = verify_time(s, MLA, plat, 4096, 4, 8, paged_block=128)
    assert t_ver < 2.5 * t_dec < 5 * t_dec


# ------------------------------------------------------------------- mesh --

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro import configs, models
from repro.launch.mesh import make_mesh
from repro.nn import module as nnm
from repro.runtime import PagedMLAEngine, Request, shallow_draft
from repro.hwmodel.platforms import PLATFORMS
import dataclasses

# drop-free routing: MoE capacity drops depend on how many tokens a step
# carries (k + 1 per row in verify, 1 in decode), which is capacity
# semantics, not a spec-decode bug
cfg = dataclasses.replace(configs.smoke("deepseek-v2-236b"),
                          capacity_factor=64.0)
params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                         jnp.float32)
rng = np.random.default_rng(7)
reqs = [Request(rid=i,
                prompt=rng.integers(0, cfg.vocab, (p,)).astype(np.int32),
                max_new=g, arrival=a)
        for i, (p, g, a) in enumerate([(6, 7, 0), (9, 5, 1), (5, 9, 3)])]


def run(mesh, spec_k, temperature):
    dcfg = dparams = (None, None) if not spec_k else \
        shallow_draft(cfg, params, 2)
    eng = PagedMLAEngine(cfg, params, num_blocks=40, block_size=4,
                         max_batch=2, compute_dtype=jnp.float32,
                         scheme="seq", platform=PLATFORMS["tpu_v5e"],
                         prefill_chunk=5, spec_k=spec_k,
                         draft_cfg=dcfg[0] if spec_k else None,
                         draft_params=dcfg[1] if spec_k else None,
                         temperature=temperature, top_k=5, sample_seed=3,
                         mesh=mesh)
    eng.run([Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new,
                     arrival=r.arrival) for r in reqs])
    return eng, {str(r.rid): [int(t) for t in r.output]
                 for r in eng.sched.finished}

mesh = make_mesh((2, 2), ("data", "model"))
out = {}
for temp, name in ((0.0, "greedy"), (0.8, "seeded")):
    _, plain = run(None, 0, temp)
    eng_m, spec_m = run(mesh, 3, temp)
    _, spec_1 = run(None, 3, temp)
    out[name] = {"plain": plain, "spec_mesh": spec_m,
                 "spec_single": spec_1,
                 "accepted": eng_m.stats.spec_accepted,
                 "drafted": eng_m.stats.spec_drafted,
                 "spec_compiles": eng_m.spec_compiles,
                 # shared leaves must reuse the target's committed device
                 # buffers, not a second device_put copy
                 "embed_shared": all(
                     a is b for a, b in zip(
                         jax.tree.leaves(eng_m.draft_params["embed"]),
                         jax.tree.leaves(eng_m.params["embed"])))}
print("RESULT" + json.dumps(out))
"""


@pytest.mark.mesh
def test_spec_decode_mesh_parity():
    """spec-decode on a (dp=2, model=2) mesh emits the same tokens as
    BOTH plain decode and single-host spec decode, greedy and seeded
    (the ISSUE 5 acceptance gate).  Subprocess forces the device count
    before jax init, so this executes under plain `make test` too."""
    import os
    import subprocess
    import sys
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-4000:]
    import json
    payload = [ln for ln in res.stdout.splitlines()
               if ln.startswith("RESULT")][0]
    out = json.loads(payload[len("RESULT"):])
    for name in ("greedy", "seeded"):
        r = out[name]
        assert r["spec_mesh"] == r["plain"], name
        assert r["spec_mesh"] == r["spec_single"], name
        assert 0 < r["accepted"] <= r["drafted"], name
        assert r["spec_compiles"] <= 2, name     # 1 verify + 1 draft step
        assert r["embed_shared"], name  # no duplicate embed on device
