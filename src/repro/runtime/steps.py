"""Jitted step builders: train_step / prefill_step / serve_step.

These are the three entry points the dry-run lowers for every (arch x
shape) cell.  All builders are mesh-aware: given (mesh, rules) they attach
NamedShardings for params, optimizer state, inputs and decode caches, and
jit with donation so cache/opt-state updates are in-place on device.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from .. import models
from ..models.common import ModelConfig
from ..nn import sharding as shd
from ..optim import AdamWConfig, adamw_update


# ------------------------------------------------------------------ loss ---


def _ce_terms(logits, labels):
    """Σ masked CE and Σ mask over a (B, L, V) block (f32)."""
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    labels_safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask), jnp.sum(mask)


def lm_loss(params, cfg: ModelConfig, tokens, labels, *, embeds=None,
            compute_dtype=jnp.bfloat16, impl: str = "ref", mesh=None,
            scheme: str = "seq", loss_chunk: int = 0) -> Tuple[jax.Array, Dict]:
    """Causal-LM cross entropy (+ MoE aux losses). labels = next tokens,
    -100 entries are masked.  With a modality prefix (embeds: (B,P,D)),
    only the text positions are scored.

    ``loss_chunk > 0``: vocab-chunked CE — the (B, L, V) logits tensor is
    never materialized; the final hidden states are unembedded and scored
    ``loss_chunk`` positions at a time under a rematerialized scan (peak
    live logits = B x loss_chunk x V).  Required at gemma3 scale
    (V=262144) and a net memory win for every 4k+ train shape."""
    if loss_chunk:
        x, aux = models.forward(params, cfg, tokens, embeds=embeds,
                                compute_dtype=compute_dtype, impl=impl,
                                mesh=mesh, scheme=scheme, return_hidden=True)
        P = x.shape[1] - labels.shape[1]
        if P > 0:
            x = x[:, P:]
        B, L, D = x.shape
        c = min(loss_chunk, L)
        pad = -L % c
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-100)
        n = x.shape[1] // c
        xs = x.reshape(B, n, c, D).swapaxes(0, 1)
        ls = labels.reshape(B, n, c).swapaxes(0, 1)

        def body(carry, inp):
            xc, lc = inp
            from ..nn import layers as nl
            logits_c = nl.unembed(params_embed, xc)
            s, m = _ce_terms(logits_c, lc)
            return (carry[0] + s, carry[1] + m), ()

        params_embed = params["embed"]
        body = jax.checkpoint(body)
        (ce_sum, n_tok), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                          (xs, ls))
        ce = ce_sum / jnp.maximum(n_tok, 1.0)
    else:
        logits, aux = models.forward(params, cfg, tokens, embeds=embeds,
                                     compute_dtype=compute_dtype, impl=impl,
                                     mesh=mesh, scheme=scheme)
        P = logits.shape[1] - labels.shape[1]
        if P > 0:
            logits = logits[:, P:]
        ce_sum, n_tok = _ce_terms(logits, labels)
        ce = ce_sum / jnp.maximum(n_tok, 1.0)
    loss = ce
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * aux["balance"] + 1e-3 * aux["z_loss"]
    metrics = {"loss": loss, "ce": ce, **{k: jnp.asarray(v) for k, v in aux.items()}}
    return loss, metrics


# ------------------------------------------------------------ train step ---


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    microbatches: int = 1           # grad accumulation
    compute_dtype: Any = jnp.bfloat16
    impl: str = "ref"
    scheme: str = "seq"
    loss_chunk: int = 0             # vocab-chunked CE (0 = dense logits)
    remat_policy: str = "default"


def make_train_step(cfg: ModelConfig, mesh: Optional[Mesh], opt_cfg: AdamWConfig,
                    ts: TrainStepConfig = TrainStepConfig(),
                    policy: str = "train"):
    """Returns (step_fn, shardings) — step_fn(params, opt_state, batch) ->
    (params, opt_state, metrics).  batch: {tokens, labels[, embeds]}.
    policy='dp' replicates weights (small models; see nn.sharding)."""
    rules = shd.make_rules(mesh, mode=policy, cfg=cfg) if mesh is not None else None
    defs = models.model_defs(cfg)

    def grads_of(params, batch):
        fn = functools.partial(lm_loss, cfg=cfg,
                               compute_dtype=ts.compute_dtype, impl=ts.impl,
                               mesh=mesh, scheme=ts.scheme,
                               loss_chunk=ts.loss_chunk)
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: fn(p, tokens=batch["tokens"], labels=batch["labels"],
                         embeds=batch.get("embeds")), has_aux=True)(params)
        return grads, metrics

    def step(params, opt_state, batch):
        if ts.microbatches > 1:
            mb = ts.microbatches
            split = jax.tree.map(
                lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]), batch)

            def accum(carry, mbatch):
                g_sum, m_sum = carry
                g, m = grads_of(params, mbatch)
                return (jax.tree.map(jnp.add, g_sum, g),
                        jax.tree.map(jnp.add, m_sum, m)), ()

            zeros_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            zeros_m = {"loss": 0.0, "ce": 0.0, "balance": 0.0, "z_loss": 0.0,
                       "dropped_frac": 0.0}
            zeros_m = jax.tree.map(jnp.float32, zeros_m)
            (g, m), _ = jax.lax.scan(accum, (zeros_g, zeros_m), split)
            grads = jax.tree.map(lambda x: x / mb, g)
            metrics = jax.tree.map(lambda x: x / mb, m)
        else:
            grads, metrics = grads_of(params, batch)
        params, opt_state, opt_metrics = adamw_update(opt_cfg, params, grads, opt_state)
        return params, opt_state, {**metrics, **opt_metrics}

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1)), None

    pspecs = shd.param_specs(defs, rules)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    opt_shard = {"step": NamedSharding(mesh, PS()), "mu": p_shard, "nu": p_shard}
    dp = rules["batch"]
    batch_shard = {
        "tokens": NamedSharding(mesh, PS(dp, None)),
        "labels": NamedSharding(mesh, PS(dp, None)),
    }
    if cfg.family in ("vlm", "encdec"):    # stub modality prefix
        batch_shard["embeds"] = NamedSharding(mesh, PS(dp, None, None))
    step_fn = jax.jit(
        step,
        in_shardings=(p_shard, opt_shard, batch_shard),
        out_shardings=(p_shard, opt_shard, None),
        donate_argnums=(0, 1),
    )
    return step_fn, {"params": p_shard, "opt": opt_shard, "batch": batch_shard}


# ------------------------------------------------------- serve/prefill -----


def cache_pspecs(cache_tree, rules, *, family: str = "dense",
                 batch_spec=None, seq_spec=None, seq_len: int = 0,
                 paged: bool = False):
    """PartitionSpec tree for a decode cache.

    Path-aware: leaves named 'kv'/'k'/'v' carry a sequence dim right after
    the batch dim; SSM/conv/xLSTM states do not.  Stacked (scan) caches
    ('period' subtree; all of whisper's) have a leading layer dim.

    batch_spec — mesh axes for the batch dim (None to replicate, e.g.
                 batch=1 long-decode).
    seq_spec   — mesh axis for the cache SEQ dim (distributed flash-decode:
                 each shard scores its cache span; GSPMD combines the
                 partial softmax with small all-reduces).  Applied only to
                 leaves whose seq dim equals ``seq_len`` (whisper's cross
                 cache keeps its n_frames dim whole).
    paged      — the tree is a PAGED latent block pool (init_paged_cache):
                 leaves are (num_blocks, block_size, D) — there is no batch
                 dim to shard.  The pool replicates over 'model' exactly
                 like the contiguous latent cache (the MQA structure of
                 absorbed MLA: head shards re-read the same compact pool)
                 AND over the DP axes, because per-request block tables map
                 any slot to any pool block — a DP shard of the batch may
                 read/write anywhere in the pool.  The compact latent
                 layout is what makes full replication affordable (the
                 paper's ~16x bytes/token saving); what DP buys is
                 per-device TRAFFIC, not capacity: each device only
                 streams the blocks its local batch rows reference (see
                 hwmodel.attention_costs.mla_decode_cost(dp_shards=)).
    """
    from jax.tree_util import DictKey, tree_map_with_path
    if paged:
        return jax.tree.map(lambda _: PS(), cache_tree)
    seq_leaves = {"kv", "k", "v", "ckv", "krope"}

    def spec_of(path, a):
        keys = [p.key for p in path if isinstance(p, DictKey)]
        stacked = (keys and keys[0] in ("period", "self", "cross")) \
            or family == "encdec"
        b_ax = 1 if stacked else 0
        nd = a.ndim
        axes = [None] * nd
        if nd > b_ax:
            axes[b_ax] = batch_spec
        if seq_spec and keys and keys[-1] in seq_leaves and nd > b_ax + 1 \
                and (not seq_len or a.shape[b_ax + 1] == seq_len):
            axes[b_ax + 1] = seq_spec
        return PS(*axes)

    return tree_map_with_path(spec_of, cache_tree)


def _dp_size(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in ("pod", "data"):
        n *= sizes.get(a, 1)
    return n


def _batch_spec(mesh: Mesh, rules, batch: int):
    """DP spec for the batch dim, or None when not divisible (batch=1)."""
    return rules["batch"] if batch % _dp_size(mesh) == 0 else None


def make_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh],
                      *, batch: int, capacity: int, compute_dtype=jnp.bfloat16,
                      impl: str = "ref", scheme: str = "seq",
                      policy: str = "serve", params_template=None):
    """Returns jitted fn(params, tokens[, embeds]) -> (last_logits, cache).

    ``params_template``: pass the ACTUAL params tree when it carries
    engine-attached ``w_absorb`` leaves (scheme 'ru'; see
    :func:`paged_param_shardings`) so the mesh in_shardings match it."""
    rules = shd.make_rules(mesh, mode=policy, cfg=cfg) if mesh is not None else None

    def run(params, tokens, embeds=None):
        return models.prefill(params, cfg, tokens, embeds=embeds,
                              capacity=capacity, compute_dtype=compute_dtype,
                              impl=impl, mesh=mesh, scheme=scheme,
                              shard_mode=policy)

    if mesh is None:
        return jax.jit(run)
    defs = models.model_defs(cfg)
    p_shard = paged_param_shardings(params_template, cfg, mesh, rules) \
        if params_template is not None else \
        jax.tree.map(lambda s: NamedSharding(mesh, s),
                     shd.param_specs(defs, rules))
    dp = _batch_spec(mesh, rules, batch)
    in_sh = [p_shard, NamedSharding(mesh, PS(dp, None))]
    if cfg.family in ("vlm", "encdec"):
        in_sh.append(NamedSharding(mesh, PS(dp, None, None)))
    # cache out_shardings must match what make_serve_step expects, so the
    # prefill->decode handoff needs no resharding copy.
    cache_t = jax.eval_shape(
        lambda: models.init_cache(cfg, batch, capacity, compute_dtype))
    cspecs = cache_pspecs(cache_t, rules, family=cfg.family, batch_spec=dp,
                          seq_spec=rules.get("cache_seq"), seq_len=capacity)
    c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs)
    return jax.jit(run, in_shardings=tuple(in_sh),
                   out_shardings=(None, c_shard))


def make_serve_step(cfg: ModelConfig, mesh: Optional[Mesh],
                    *, compute_dtype=jnp.bfloat16, impl: str = "ref",
                    scheme: str = "seq", shard_cache_seq: bool = False,
                    policy: str = "serve", params_template=None):
    """One-token decode step:  fn(params, token, cache, index) ->
    (logits, cache).  Cache is donated (updated in place on device).

    With a mesh this returns ``jit_with_cache(cache_template, batch) ->
    step_fn`` (the cache pytree's shardings depend on its structure);
    ``params_template`` as in :func:`make_prefill_step`.

    policy='serve_2dtp' additionally shards the cache SEQ dim over 'model'
    (rules['cache_seq']) — distributed flash-decode; 'shard_cache_seq'
    forces seq sharding over 'data' for batch=1 long decode."""
    rules = shd.make_rules(mesh, mode=policy, cfg=cfg) if mesh is not None else None

    def run(params, token, cache, index):
        return models.decode_step(params, cfg, token, cache, index,
                                  compute_dtype=compute_dtype, impl=impl,
                                  mesh=mesh, scheme=scheme, shard_mode=policy)

    if mesh is None:
        return jax.jit(run, donate_argnums=(2,))

    defs = models.model_defs(cfg)
    p_shard = paged_param_shardings(params_template, cfg, mesh, rules) \
        if params_template is not None else \
        jax.tree.map(lambda s: NamedSharding(mesh, s),
                     shd.param_specs(defs, rules))

    def jit_with_cache(cache_template, batch: int, seq_len: int = 0):
        dp = _batch_spec(mesh, rules, batch)
        seq_spec = rules.get("cache_seq")
        if shard_cache_seq and dp is None and seq_spec is None:
            seq_spec = "data"
        cspecs = cache_pspecs(cache_template, rules, family=cfg.family,
                              batch_spec=dp, seq_spec=seq_spec,
                              seq_len=seq_len)
        c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs)
        return jax.jit(
            run,
            in_shardings=(p_shard, NamedSharding(mesh, PS(dp)), c_shard,
                          NamedSharding(mesh, PS())),
            out_shardings=(None, c_shard),
            donate_argnums=(2,),
        )

    return jit_with_cache


# ------------------------------------------------------- paged serving -----


def commit_params(params, cfg: ModelConfig, mesh: Mesh,
                  policy: str = "serve"):
    """Commit a (possibly absorb-carrying) param tree to ``policy``'s
    layout once, so jitted steps that leave the params slot unspecified
    inherit the placement with no per-call resharding.  The single source
    of truth for the engine and the serve CLI."""
    rules = shd.make_rules(mesh, mode=policy, cfg=cfg)
    return jax.device_put(params,
                          paged_param_shardings(params, cfg, mesh, rules))


def commit_draft_params(draft_params, draft_cfg: ModelConfig, mesh: Mesh,
                        policy: str = "serve", *, target_host=None,
                        target_committed=None):
    """Commit a DRAFT model's param tree to the mesh, reusing the
    target's already-committed device buffers for every leaf the draft
    shares (by object identity) with the target's HOST tree.

    Shallow self-speculation drafts (runtime.spec.shallow_draft) alias
    the target's embed / final norm / first-N layer dicts by reference;
    committing them independently would duplicate those weights on every
    device — the (vocab x d_model) embedding twice per replica.  Reuse is
    sharding-sound because identically-named weights take identical rule
    specs under the same (mesh, policy).  Leaves the draft owns privately
    (the re-stacked scan periods) are device_put under the draft's own
    rules like :func:`commit_params` would."""
    rules = shd.make_rules(mesh, mode=policy, cfg=draft_cfg)
    shardings = paged_param_shardings(draft_params, draft_cfg, mesh, rules)
    reuse = {}
    if target_host is not None and target_committed is not None:
        from jax.tree_util import tree_flatten_with_path
        for path, leaf in tree_flatten_with_path(target_host)[0]:
            node = target_committed
            try:
                for key in path:
                    node = node[key.key]
            except (KeyError, TypeError, AttributeError):
                continue        # structure diverged: just re-commit
            reuse[id(leaf)] = node

    def commit(leaf, sh):
        return reuse[id(leaf)] if id(leaf) in reuse \
            else jax.device_put(leaf, sh)

    return jax.tree.map(commit, draft_params, shardings)


def paged_param_shardings(params, cfg: ModelConfig, mesh: Mesh, rules):
    """NamedSharding tree matching ``params``' ACTUAL structure.

    The engine attaches precomputed ``w_absorb`` leaves (core.mla
    .attach_absorbed_tree) that model_defs does not know about, so the
    defs-driven spec tree cannot be handed to device_put directly.  Walk
    the params tree: defs-declared weights take their rule spec, absorbed
    leaves shard over heads ('model') like the factors they absorb."""
    specs = shd.param_specs(models.model_defs(cfg), rules)
    heads = rules.get("heads")

    def graft(spec_node, param_node):
        if isinstance(param_node, dict):
            out = {}
            for k, v in param_node.items():
                if k == "w_absorb":
                    # (H, Q, K) or stacked (layers, H, Q, K)
                    lead = (None,) * (v.ndim - 3)
                    out[k] = PS(*lead, heads, None, None)
                else:
                    out[k] = graft(spec_node[k], v)
            return out
        return spec_node

    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        graft(specs, params))


def _paged_pool_shardings(cfg: ModelConfig, mesh: Mesh, rules,
                          compute_dtype, cache_dtype=None):
    """Replicated NamedSharding tree for the paged latent pool.  Only the
    tree STRUCTURE matters (every leaf is PS()), so a dummy-sized
    eval_shape stands in for the real pool.  ``cache_dtype`` must match
    the engine's pool (quantized pools carry extra scale leaves)."""
    pool_t = jax.eval_shape(
        lambda: models.init_paged_cache(cfg, 2, 1, compute_dtype,
                                        cache_dtype=cache_dtype))
    cspecs = cache_pspecs(pool_t, rules, family=cfg.family, paged=True)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs)


def _tag_obs(fn, *, kind: str, scheme: str, impl: str):
    """Annotate a jitted step with its dispatch identity (step kind,
    attention scheme, attention impl) so telemetry and debugging tools
    can label spans from the function object itself instead
    of threading extra arguments.  Plain setattr: jitted callables carry
    attributes fine and ``.lower()`` (the hot-path auditor's entry) is
    unaffected."""
    fn.obs_kind = kind
    fn.obs_scheme = scheme
    fn.obs_impl = impl
    return fn


def make_paged_serve_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                          *, compute_dtype=jnp.bfloat16, impl: str = "ref",
                          scheme: str = "seq", policy: str = "serve",
                          cache_dtype: Optional[str] = None):
    """Continuous-batching decode step over the paged latent pool:

        fn(params, token (B,), pool_tree, block_tables (B, nb),
           lengths (B,)) -> (logits (B, V), pool_tree)

    ``lengths`` is ragged per request; inactive slots carry length 0 and a
    null block table (their logits are garbage the scheduler discards).
    The pool is donated — in-place scatter of the B new latent entries.

    With a mesh the batch dim — token, block tables, lengths — shards over
    the DP axes (``rules['batch']``; B must be a DP multiple: the engine
    pads ``max_batch`` up, which is free because inactive rows carry
    length 0 and null tables) while the pool replicates over EVERY mesh
    axis (see :func:`cache_pspecs` ``paged=``): block tables are
    host-global, so any DP shard may address any pool block, and the
    compact latent layout keeps n_model x n_dp replicas affordable —
    per-device cache TRAFFIC still shrinks by the DP factor because each
    device only streams the blocks its local rows reference.
    ``impl='kernel'``/'pallas' routes through the shard_map kernel path
    (kernels.ops.mla_decode_paged_attention: batch over DP, heads over
    'model', pool replicated); 'ref' lets GSPMD partition the gather
    reference.  ``policy`` picks the weight-sharding rules
    (nn.sharding.make_rules mode; params should be device_put with
    :func:`paged_param_shardings` for these same rules).
    """
    if cfg.attn_kind != "mla":
        raise NotImplementedError("paged serving requires attn_kind='mla'")

    def run(params, token, pool, block_tables, lengths):
        return models.decode_step(params, cfg, token, pool, None,
                                  compute_dtype=compute_dtype, impl=impl,
                                  mesh=mesh, scheme=scheme,
                                  shard_mode=policy,
                                  block_tables=block_tables,
                                  lengths=lengths)

    if mesh is None:
        return _tag_obs(jax.jit(run, donate_argnums=(2,)),
                        kind="decode", scheme=scheme, impl=impl)
    rules = shd.make_rules(mesh, mode=policy, cfg=cfg)
    dp = rules["batch"]
    pool_shard = _paged_pool_shardings(cfg, mesh, rules, compute_dtype,
                                       cache_dtype)
    return _tag_obs(jax.jit(
        run,
        # params slot is UNSPECIFIED: committed shardings (device_put via
        # paged_param_shardings) propagate, and the same jitted step
        # serves trees with or without attached w_absorb leaves.
        in_shardings=(None, NamedSharding(mesh, PS(dp)), pool_shard,
                      NamedSharding(mesh, PS(dp, None)),
                      NamedSharding(mesh, PS(dp))),
        out_shardings=(None, pool_shard),
        donate_argnums=(2,),
    ), kind="decode", scheme=scheme, impl=impl)


def make_paged_sample_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                           *, compute_dtype=jnp.bfloat16, impl: str = "ref",
                           scheme: str = "seq", policy: str = "serve",
                           cache_dtype: Optional[str] = None,
                           temperature: float = 0.0, top_k: int = 0,
                           sample_seed: int = 0):
    """Decode step with sampling FOLDED INTO the compiled program:

        fn(params, token (B,), pool_tree, block_tables (B, nb),
           lengths (B,), rids (B,) u32, poss (B,) u32)
          -> (next_token (B,) int32, pool_tree)

    The double-buffered engine's step: only the (B,) sampled tokens ever
    sync back to the host — the (B, V) logits stay on device — so the
    host can prepare tick N+1 while the device still runs tick N and the
    eventual host read is one small transfer, not a vocab-wide one.

    Sampling matches the host path (``PagedMLAEngine._sample_fn``)
    bit-for-bit: greedy argmax at ``temperature <= 0``, else temperature /
    top-k categorical under fold(fold(seed, rid), position) keys — rows
    are independent, so sampling every slot (inactive rows draw garbage
    the scheduler discards) emits the same token per live row as the host
    path's gathered subset.  Under a mesh the logits (and the rid /
    position rows) are constrained to full replication before any random
    op: under the pre-0.5 jax default (threefry_partitionable=False) a
    random op lowered over a sharded operand draws different bits than
    unsharded, and replication keeps the stream topology-invariant —
    the same reason the host path gathers rows before sampling.
    """
    if cfg.attn_kind != "mla":
        raise NotImplementedError("paged serving requires attn_kind='mla'")
    base = jax.random.PRNGKey(sample_seed)

    def sample(logits, rids, poss):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if mesh is not None:
            repl = lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, PS()))
            logits, rids, poss = repl(logits), repl(rids), repl(poss)
        keys = jax.vmap(lambda r, p: jax.random.fold_in(
            jax.random.fold_in(base, r), p))(rids, poss)
        rows = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            kth = jnp.sort(rows, axis=-1)[:, -top_k]
            rows = jnp.where(rows >= kth[:, None], rows, -jnp.inf)
        return jax.vmap(jax.random.categorical)(keys, rows).astype(jnp.int32)

    def run(params, token, pool, block_tables, lengths, rids, poss):
        logits, pool = models.decode_step(params, cfg, token, pool, None,
                                          compute_dtype=compute_dtype,
                                          impl=impl, mesh=mesh, scheme=scheme,
                                          shard_mode=policy,
                                          block_tables=block_tables,
                                          lengths=lengths)
        return sample(logits, rids, poss), pool

    if mesh is None:
        return _tag_obs(jax.jit(run, donate_argnums=(2,)),
                        kind="decode", scheme=scheme, impl=impl)
    rules = shd.make_rules(mesh, mode=policy, cfg=cfg)
    dp = rules["batch"]
    pool_shard = _paged_pool_shardings(cfg, mesh, rules, compute_dtype,
                                       cache_dtype)
    return _tag_obs(jax.jit(
        run,
        in_shardings=(None, NamedSharding(mesh, PS(dp)), pool_shard,
                      NamedSharding(mesh, PS(dp, None)),
                      NamedSharding(mesh, PS(dp)),
                      NamedSharding(mesh, PS(dp)),
                      NamedSharding(mesh, PS(dp))),
        # tokens replicate (the host reads all B of them); pool stays put
        out_shardings=(NamedSharding(mesh, PS()), pool_shard),
        donate_argnums=(2,),
    ), kind="decode", scheme=scheme, impl=impl)


def make_chunked_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                              *, compute_dtype=jnp.bfloat16,
                              impl: str = "ref", scheme: str = "seq",
                              policy: str = "serve",
                              cache_dtype: Optional[str] = None):
    """Batched chunked prefill straight into the paged pool:

        fn(params, tokens (B, C), pool_tree, block_tables (B, nb),
           lengths (B,), n_valid (B,)) -> (last_valid_logits (B, V),
                                           pool_tree)

    Row b prefills its request's next ``n_valid[b]`` prompt tokens at
    absolute positions lengths[b].., attending the already-resident
    prefix (prefix-cache hits + earlier chunks) THROUGH the block table;
    idle rows carry n_valid 0.  The pool is donated (in-place scatter).

    ``impl`` selects the chunk-attention path: 'ref' runs the gather
    reference (materializes the (B, S) block-table view each chunk);
    'kernel' / 'pallas' runs the fused paged Pallas prefill kernel
    (kernels.mla_prefill) which walks the block table in place.
    ``scheme`` picks the query-absorption ordering (seq/rc/ru — all
    compute the same function; 'naive' falls back to the gather view).

    With a mesh the batch dim — tokens, block tables, lengths, n_valid —
    shards over the DP axes and the pool replicates over every axis,
    exactly like :func:`make_paged_serve_step` (idle rows make the DP
    padding free); ``impl='kernel'``/'pallas' routes through the
    shard_map prefill-kernel path in kernels.ops.

    This replaces the per-request contiguous prefill + scatter detour:
    one compiled step shape per (batch, chunk) pair — NOT one retrace per
    prompt length — and every admitted request prefills as a batch.
    """
    if cfg.attn_kind != "mla":
        raise NotImplementedError("paged serving requires attn_kind='mla'")

    def run(params, tokens, pool, block_tables, lengths, n_valid):
        return models.prefill_chunk_paged(params, cfg, tokens, pool,
                                          block_tables, lengths, n_valid,
                                          compute_dtype=compute_dtype,
                                          impl=impl, mesh=mesh,
                                          scheme=scheme, shard_mode=policy)

    if mesh is None:
        return _tag_obs(jax.jit(run, donate_argnums=(2,)),
                        kind="prefill", scheme=scheme, impl=impl)
    rules = shd.make_rules(mesh, mode=policy, cfg=cfg)
    dp = rules["batch"]
    pool_shard = _paged_pool_shardings(cfg, mesh, rules, compute_dtype,
                                       cache_dtype)
    return _tag_obs(jax.jit(
        run,
        in_shardings=(None, NamedSharding(mesh, PS(dp, None)), pool_shard,
                      NamedSharding(mesh, PS(dp, None)),
                      NamedSharding(mesh, PS(dp)),
                      NamedSharding(mesh, PS(dp))),
        out_shardings=(None, pool_shard),
        donate_argnums=(2,),
    ), kind="prefill", scheme=scheme, impl=impl)


def make_verify_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                     *, compute_dtype=jnp.bfloat16, impl: str = "ref",
                     scheme: str = "seq", policy: str = "serve",
                     cache_dtype: Optional[str] = None):
    """Speculative-decode verify step over the paged latent pool:

        fn(params, tokens (B, C), pool_tree, block_tables (B, nb),
           lengths (B,), n_valid (B,)) -> (logits (B, C, V), pool_tree)

    The multi-token sibling of :func:`make_paged_serve_step` built on the
    chunked-prefill machinery with C = k + 1: row b scores its last
    sampled token plus ``n_valid[b] - 1`` draft tokens against its
    resident latent prefix in ONE batched forward — the prefix streams
    from HBM once for all k + 1 query positions instead of once per token
    (the amortization hwmodel.attention_costs.mla_verify_cost prices).
    Unlike the prefill step it returns logits for EVERY position, so the
    engine can sample the target's token at each verify position and
    accept/reject drafts host-side.  ``impl``/``scheme``/``mesh`` behave
    exactly as in :func:`make_chunked_prefill_step` (same shardings:
    batch rows over DP, heads over 'model', pool replicated + donated);
    the (B, C, V) logits are left unspecified for GSPMD — the engine
    host-gathers the few rows it samples anyway.
    """
    if cfg.attn_kind != "mla":
        raise NotImplementedError("paged serving requires attn_kind='mla'")

    def run(params, tokens, pool, block_tables, lengths, n_valid):
        return models.verify_chunk_paged(params, cfg, tokens, pool,
                                         block_tables, lengths, n_valid,
                                         compute_dtype=compute_dtype,
                                         impl=impl, mesh=mesh,
                                         scheme=scheme, shard_mode=policy)

    if mesh is None:
        return _tag_obs(jax.jit(run, donate_argnums=(2,)),
                        kind="verify", scheme=scheme, impl=impl)
    rules = shd.make_rules(mesh, mode=policy, cfg=cfg)
    dp = rules["batch"]
    pool_shard = _paged_pool_shardings(cfg, mesh, rules, compute_dtype,
                                       cache_dtype)
    return _tag_obs(jax.jit(
        run,
        in_shardings=(None, NamedSharding(mesh, PS(dp, None)), pool_shard,
                      NamedSharding(mesh, PS(dp, None)),
                      NamedSharding(mesh, PS(dp)),
                      NamedSharding(mesh, PS(dp))),
        out_shardings=(None, pool_shard),
        donate_argnums=(2,),
    ), kind="verify", scheme=scheme, impl=impl)


def _scatter_entries(pool_leaf, contig_leaf, pages, block_size: int):
    """One cache leaf of the prefill->paged handoff.  contig_leaf:
    (1, cap, D) or stacked (layers, 1, cap, D); pages: (n_pg,) pool block
    ids (null-padded — garbage written to block 0 is never read)."""
    from ..core import cache as cachelib
    cap = contig_leaf.shape[-2]
    n_pg = -(-cap // block_size)
    pad = n_pg * block_size - cap
    squeezed = contig_leaf[:, 0] if contig_leaf.ndim == 4 else contig_leaf[0]
    if pad:
        width = [(0, 0)] * squeezed.ndim
        width[-2] = (0, pad)
        squeezed = jnp.pad(squeezed, width)
    D = squeezed.shape[-1]
    if squeezed.ndim == 3:      # stacked (layers, cap_pad, D)
        vals = squeezed.reshape(squeezed.shape[0], n_pg, block_size, D)
    else:
        vals = squeezed.reshape(n_pg, block_size, D)
    return cachelib.write_blocks_paged(pool_leaf, pages[:n_pg], vals)


def _tree_has_quantized_pool(tree) -> bool:
    if isinstance(tree, dict):
        return "ckv_scale" in tree \
            or any(_tree_has_quantized_pool(v) for v in tree.values())
    return False


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_prefill_to_paged(pool_tree, entries_tree, pages):
    """Scatter one request's contiguous prefill cache (batch dim 1) into
    the paged pool at its allocated ``pages`` ((max_blocks,) int32, padded
    with the null block).  Whole blocks are written; the tail garbage
    inside the last block is masked at attention time.

    Quantized pools are not supported on this legacy per-request path (the
    contiguous prefill cache carries no scales) — use chunked prefill,
    whose scatter quantizes on write."""
    if _tree_has_quantized_pool(pool_tree):
        raise NotImplementedError(
            "scatter_prefill_to_paged does not support quantized pools; "
            "use prefill_mode='chunked'")
    pages = jnp.asarray(pages, jnp.int32)

    def leaf(pool_leaf, contig_leaf):
        bs = pool_leaf.shape[-2]
        return _scatter_entries(pool_leaf, contig_leaf, pages, bs)

    return jax.tree.map(leaf, pool_tree, entries_tree)
