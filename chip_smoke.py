#!/usr/bin/env python
"""Bring-up check: serve DeepSeek-V2 at its published widths on one TPU.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --mesh 2x2     # four chips: sharded engine only

One process, no fallback: it exits non-zero unless JAX's first device is a
TPU.  With no options it runs these phases, each a hard check:

  device    print jax.devices(), platform, device_kind and count.
  model     one chip's share of ``deepseek-v2-236b`` with every width as
            published: the first dense layer plus 4 MoE layers, 16 of the
            160 routed experts (one chip of a 10-way expert split), the
            full vocabulary.  bf16 weights drawn from ``--seed``.
  kernels   the Pallas paged decode and prefill kernels alone at these
            widths against their oracles in ``kernels/ref.py``.
  load      8 requests (prompts of 256 to 2048 tokens, two sharing a
            1536-token prefix, one with n=2) through ``PagedMLAEngine``
            (impl='kernel': Pallas decode AND prefill, scheme='auto',
            bf16) and again through ``AsyncPagedMLAEngine``; the tokens
            must be identical.
  logits    the kernel path's tokens teacher-forced through the kernel and
            the gather path (impl='ref') on the same weights; the logits
            must agree within LOGIT_BOUNDS.
  compiled  the compiled decode and prefill steps must hold
            ``tpu_custom_call`` (the kernels ran through Mosaic).
  http      ``launch.server.Frontend`` on an ephemeral localhost port; a
            blocking, an SSE and an n=2 request to /v1/generate must
            answer the engine's own tokens.

``--mesh 2x2`` runs only the sharded paged engine (serve policy) on a
2x2 mesh and the one-chip engine on the same cut and prompts, checks that
weights and pool span all four devices and that the compiled step holds
collectives, and compares the two.

Times printed here are bring-up observations from a single cold run
(compilation included), not benchmark numbers.  The last line of stdout
is one JSON object: {"ok": true, "device": {platform, kind, count}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

BLOCK = 16          # pool block (tokens)
CHUNK = 32          # prefill chunk (PagedMLAEngine's default)
MAX_BATCH = 8       # decode slots
# Kernel vs gather path, teacher-forced on the kernel path's tokens, both
# in bf16.  rms_rel = ||kernel - gather|| / ||gather|| over every scored
# position; top1 = share of positions whose argmax agrees.  Random
# weights give near-N(0, 1) logits over 102400 tokens, whose top two sit
# ~0.2 apart on average, so bf16 noise of 1% flips a few percent of the
# argmaxes; a broken kernel puts rms_rel near 1 and top1 near 0.
LOGIT_BOUNDS = {"rms_rel": 0.05, "top1": 0.80}
KERNEL_TOL = 2e-2   # bf16 kernel output vs its f32 oracle (atol = rtol)

# (prompt tokens, max_tokens, n, arrival step); the last two prompts open
# with the same SHARED_PREFIX tokens.
LOAD = [(256, 32, 1, 0), (384, 24, 1, 0), (512, 32, 2, 1), (640, 40, 1, 2),
        (1024, 32, 1, 3), (1280, 24, 1, 4), (1792, 32, 1, 5),
        (2048, 40, 1, 6)]
SHARED_PREFIX = 1536
MESH_LOAD = [(256, 16, 1, 0), (512, 16, 1, 0), (768, 16, 1, 1),
             (1024, 16, 1, 2)]


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds the XLA backend spends compiling, read from JAX's own
    monitoring events (tracing is not counted: nested traces overlap)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.total += secs


class Phase:
    """Wall and compile seconds of one phase, and the devices' peak memory
    so far, printed at its end."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            wall = time.perf_counter() - self.t0
            comp = self.clock.total - self.c0
            import jax
            log(f"{self.name}: {wall:.1f} s wall, of which {comp:.1f} s "
                f"XLA compile; {peak_memory(jax.devices())}")


# ------------------------------------------------------------- phases ----


def check_device():
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"jax.devices(): {devs}")
    log(f"platform={d.platform} device_kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is {d.platform!r}, "
                         "not a TPU; nothing was run")
    return devs


def one_chip_cut():
    """deepseek-v2-236b at published widths, cut to one chip's share."""
    from repro import configs
    full = configs.full("deepseek-v2-236b")
    cut = dataclasses.replace(
        full, n_layers=5, n_experts=16,
        # no token is dropped: an expert holds every token of a step, so a
        # token's output never depends on the rest of its batch
        capacity_factor=16 / full.top_k)
    reduced = {
        "n_layers": f"{full.n_layers} -> 5 (the dense layer + 4 MoE)",
        "n_experts": f"{full.n_experts} -> 16 routed (one chip of a 10-way "
                     "expert split; top-6 routing among them)",
    }
    settings = {"capacity_factor": f"{full.capacity_factor} -> "
                                   "16/6 (drop-free routing)"}
    return cut, reduced, settings


def init_weights(cfg, seed: int, dtype):
    """Every leaf drawn on the device in ``dtype`` from ``seed``.  The
    ``rbg`` generator (XLA's RngBitGenerator) compiles this init for a v5e
    in about a third of the time threefry takes at these widths."""
    import jax
    from repro import models
    from repro.nn import module as nnm
    defs = models.model_defs(cfg)
    return jax.jit(lambda k: nnm.init_params(k, defs, dtype))(
        jax.random.key(seed, impl="rbg"))


def make_load(vocab: int, seed: int, load=LOAD, shared=SHARED_PREFIX,
              scale: int = 1):
    """Prompts of ``load`` (lengths divided by ``scale``).  Returns a list
    of (rid, prompt, max_tokens, n, arrival); rids step by 2 so an n=2
    group's child takes rid + 1."""
    rng = np.random.default_rng(seed + 1)
    prefix = rng.integers(0, vocab, shared // scale).astype(np.int32)
    out = []
    for i, (plen, gen, n, arr) in enumerate(load):
        plen //= scale
        if shared and i >= len(load) - 2:
            tail = rng.integers(0, vocab, plen - len(prefix))
            prompt = np.concatenate([prefix, tail.astype(np.int32)])
        else:
            prompt = rng.integers(0, vocab, plen).astype(np.int32)
        out.append((2 * i, prompt, max(2, gen // scale), n, arr))
    return out


def pool_geometry(load):
    from repro.runtime import blocks_for
    per = [blocks_for(len(p) + g + 1, BLOCK) * n for _, p, g, n, _ in load]
    nb = max(blocks_for(len(p) + g + 1, BLOCK) for _, p, g, _, _ in load)
    return 1 + sum(per), nb


def make_engine(cls, cfg, params, load, *, dtype, platform, mesh=None):
    """A paged engine with Pallas decode and prefill and a pool sized for
    ``load``."""
    num_blocks, nb = pool_geometry(load)
    return cls(cfg, params, num_blocks=num_blocks, block_size=BLOCK,
               max_batch=MAX_BATCH, max_blocks_per_req=nb,
               compute_dtype=dtype, impl="kernel", scheme="auto",
               platform=platform, prefill_chunk=CHUNK, mesh=mesh,
               cache_dtype="bf16")


def run_engine(cls, cfg, params, load, **kw):
    """Drive ``load`` to completion; returns (engine, {rid: tokens})."""
    from repro.runtime import Request, SamplingParams
    eng = make_engine(cls, cfg, params, load, **kw)
    reqs = [Request(rid=rid, prompt=p.copy(), arrival=arr,
                    sampling=SamplingParams(max_tokens=g, n=n))
            for rid, p, g, n, arr in load]
    eng.run(reqs)
    out = {r.rid: [int(t) for t in r.output] for r in eng.sched.finished}
    return eng, out


def sequences(load, tokens):
    """(prompt, generated) pairs for every finished sequence, fork
    children included (they share their parent's prompt)."""
    prompts = {}
    for rid, p, _, n, _ in load:
        for c in range(n):
            prompts[rid + c] = p
    return [(prompts[rid], tokens[rid]) for rid in sorted(tokens)]


def teacher_forced(cfg, params, seqs, *, impl, scheme, dtype, num_blocks,
                   nb, mesh=None):
    """Logits of every generated position of ``seqs`` with the generated
    tokens fed back (teacher forcing), through the same step factories the
    engine uses.  Returns ([(gen, V) float32 per sequence], steps, args)
    where ``args`` are shape stand-ins of the last call of each step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS
    from repro import models
    from repro.runtime.steps import (make_chunked_prefill_step,
                                     make_paged_serve_step)
    prefill = make_chunked_prefill_step(cfg, mesh, compute_dtype=dtype,
                                        impl=impl, scheme="seq",
                                        cache_dtype="bf16")
    decode = make_paged_serve_step(cfg, mesh, compute_dtype=dtype, impl=impl,
                                   scheme=scheme, cache_dtype="bf16")
    shape_of = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding", None)),
        tree)
    out, last = [], {}
    for g0 in range(0, len(seqs), MAX_BATCH):
        group = seqs[g0:g0 + MAX_BATCH]
        pool = models.init_paged_cache(cfg, num_blocks, BLOCK, dtype,
                                       cache_dtype="bf16")
        if mesh is not None:
            pool = jax.device_put(pool, NamedSharding(mesh, PS()))
        tables = np.zeros((MAX_BATCH, nb), np.int32)
        nxt = 1
        for i, (p, o) in enumerate(group):
            need = -(-(len(p) + len(o) + 1) // BLOCK)
            tables[i, :need] = np.arange(nxt, nxt + need)
            nxt += need
        rows = [[] for _ in group]
        fill = [0] * len(group)
        while any(f < len(p) for f, (p, _) in zip(fill, group)):
            tok = np.zeros((MAX_BATCH, CHUNK), np.int32)
            lens = np.zeros((MAX_BATCH,), np.int32)
            nv = np.zeros((MAX_BATCH,), np.int32)
            done = []
            for i, (p, _) in enumerate(group):
                take = min(len(p) - fill[i], CHUNK)
                if take > 0:
                    tok[i, :take] = p[fill[i]:fill[i] + take]
                    lens[i], nv[i] = fill[i], take
                    fill[i] += take
                    if fill[i] == len(p):
                        done.append(i)
            args = (params, tok, pool, tables, lens, nv)
            last["prefill"] = shape_of(args)
            logits, pool = prefill(*args)
            if done:
                lg = np.asarray(logits.astype(jnp.float32))
                for i in done:
                    rows[i].append(lg[i])
        for t in range(max(len(o) for _, o in group) - 1):
            tok = np.zeros((MAX_BATCH,), np.int32)
            lens = np.zeros((MAX_BATCH,), np.int32)
            tbl = np.zeros_like(tables)
            live = [i for i, (_, o) in enumerate(group) if t < len(o) - 1]
            for i in live:
                p, o = group[i]
                tok[i], lens[i], tbl[i] = o[t], len(p) + t, tables[i]
            args = (params, tok, pool, tbl, lens)
            last["decode"] = shape_of(args)
            logits, pool = decode(*args)
            lg = np.asarray(logits.astype(jnp.float32))
            for i in live:
                rows[i].append(lg[i])
        out += [np.stack(r) for r in rows]
    return out, {"prefill": prefill, "decode": decode}, last


def compare_logits(got, want):
    num = sum(float(((a - b) ** 2).sum()) for a, b in zip(got, want))
    den = sum(float((b ** 2).sum()) for b in want)
    agree = np.concatenate([a.argmax(-1) == b.argmax(-1)
                            for a, b in zip(got, want)])
    return {"rms_rel": (num / den) ** 0.5,
            "max_abs": max(float(np.abs(a - b).max())
                           for a, b in zip(got, want)),
            "logit_absmax": max(float(np.abs(b).max()) for b in want),
            "top1": float(agree.mean()), "positions": int(agree.size)}


def within_bounds(stats) -> bool:
    return (stats["rms_rel"] <= LOGIT_BOUNDS["rms_rel"]
            and stats["top1"] >= LOGIT_BOUNDS["top1"])


def check_kernels(cfg, dtype, seed: int, *, B: int = 4, nb: int = 64):
    """Each Pallas paged kernel alone at the model's MLA widths against
    its f32 oracle.  Returns {kernel: max abs error}."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.mla_decode import mla_decode_paged_kernel
    from repro.kernels.mla_prefill import mla_prefill_paged_kernel
    H, Dl, Dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    N = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 4)
    ckv = jax.random.normal(ks[0], (N, BLOCK, Dl), dtype)
    krope = jax.random.normal(ks[1], (N, BLOCK, Dr), dtype)
    tables = jnp.asarray(1 + np.random.default_rng(seed).permutation(
        B * nb).reshape(B, nb), jnp.int32)
    span = nb * BLOCK
    idx = jnp.asarray(np.linspace(span // 7, span - CHUNK - 1, B), jnp.int32)
    nv = jnp.asarray([CHUNK, CHUNK // 2, 1, 0][:B] + [CHUNK] * (B - 4),
                     jnp.int32)
    q_dec = jax.random.normal(ks[2], (B, H, Dl + Dr), dtype)
    q_pre = jax.random.normal(ks[3], (B, CHUNK, H, Dl + Dr), dtype)
    got = {
        "mla_decode_paged_kernel": jax.jit(mla_decode_paged_kernel)(
            q_dec, ckv, krope, tables, idx),
        "mla_prefill_paged_kernel": jax.jit(mla_prefill_paged_kernel)(
            q_pre, ckv, krope, tables, idx, nv),
    }
    with jax.default_matmul_precision("float32"):
        want = {
            "mla_decode_paged_kernel": ref.mla_decode_paged_ref(
                q_dec, ckv, krope, tables, idx),
            "mla_prefill_paged_kernel": ref.mla_prefill_paged_ref(
                q_pre, ckv, krope, tables, idx, nv),
        }
    errs = {}
    for name in got:
        g = np.asarray(got[name].astype(jnp.float32))
        w = np.asarray(want[name].astype(jnp.float32))
        if not np.isfinite(g).all():
            raise SystemExit(f"chip_smoke: {name} returned non-finite values")
        errs[name] = float(np.abs(g - w).max())
        bad = np.abs(g - w) > KERNEL_TOL * (1 + np.abs(w))
        if bad.any():
            raise SystemExit(f"chip_smoke: {name} breaches its oracle: max "
                             f"abs err {errs[name]:.3g} (tol {KERNEL_TOL})")
    return errs


def compiled_text(step, shapes) -> str:
    return step.lower(*shapes).compile().as_text()


def mosaic_calls(text: str) -> int:
    """tpu_custom_call sites (Mosaic kernels) in a compiled program."""
    return text.count('custom_call_target="tpu_custom_call"')


def check_http(engine, load, tokens):
    """Serve a blocking, an SSE and an n=2 request through the HTTP
    frontend; each answer must equal the engine's own tokens."""
    import urllib.request
    from repro.launch.server import Frontend
    # localhost only: bypass any proxy the environment names
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    fe = Frontend(engine, host="127.0.0.1", port=0).start()
    url = f"http://127.0.0.1:{fe.port}/v1/generate"

    def post(body):
        req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                     headers={"Content-Type":
                                              "application/json"})
        return opener.open(req, timeout=600)

    try:
        single = [e for e in load if e[3] == 1]
        group = next(e for e in load if e[3] > 1)
        checks = []
        rid, p, g, _, _ = single[0]
        body = json.load(post({"prompt": p.tolist(), "max_tokens": g}))
        checks.append(("blocking", body["output"], tokens[rid]))
        rid, p, g, _, _ = single[-1]
        streamed, event = [], None
        with post({"prompt": p.tolist(), "max_tokens": g,
                   "stream": True}) as resp:
            for raw in resp:
                line = raw.decode().strip()
                if line.startswith("event:"):
                    event = line.split(":", 1)[1].strip()
                elif line.startswith("data:") and event == "token":
                    streamed.append(json.loads(line[5:])["token"])
        checks.append(("sse", streamed, tokens[rid]))
        rid, p, g, n, _ = group
        body = json.load(post({"prompt": p.tolist(), "max_tokens": g,
                               "n": n}))
        for c, choice in enumerate(body["choices"]):
            checks.append((f"n={n} choice {c}", choice["tokens"],
                           tokens[rid + c]))
    finally:
        fe.stop()
    for name, got, want in checks:
        log(f"http {name}: {len(got)} tokens, equal to the engine's: "
            f"{got == want}")
        if got != want:
            raise SystemExit(f"chip_smoke: HTTP {name} answer differs from "
                             "the engine's tokens")


def peak_memory(devs) -> str:
    parts = []
    for d in devs:
        stats = d.memory_stats() or {}
        parts.append(f"{d.id}: peak {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                     f" GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    return "; ".join(parts)


# --------------------------------------------------------------- runs ----


def run_one_chip(devs, cfg, load, platform, seed: int,
                 clock: CompileClock) -> None:
    import jax.numpy as jnp
    from repro.runtime import AsyncPagedMLAEngine, PagedMLAEngine
    dtype = jnp.bfloat16
    with Phase("weights", clock):
        params = init_weights(cfg, seed, dtype)
    log(f"weights: bf16, seed {seed}")

    with Phase("kernels", clock):
        errs = check_kernels(cfg, dtype, seed)
    for name, e in errs.items():
        log(f"kernel {name} vs oracle at H={cfg.n_heads} "
            f"Dl={cfg.kv_lora_rank} Dr={cfg.qk_rope_dim}: max abs err "
            f"{e:.3g} (tol {KERNEL_TOL} x (1 + |oracle|))")

    log("load: " + ", ".join(f"{len(p)}+{g}" + (f" n={n}" if n > 1 else "")
                             for _, p, g, n, _ in load) + " tokens")
    with Phase("sync engine", clock):
        eng, sync_tok = run_engine(PagedMLAEngine, cfg, params, load,
                                   dtype=dtype, platform=platform)
    s = eng.summary()
    log(f"sync engine: {s['decode_tokens']:.0f} decode tokens, "
        f"{s['prefill_tokens']:.0f} prefilled, prefix hits "
        f"{s['prefix_hit_tokens']:.0f}, fork groups {s['fork_groups']:.0f}, "
        f"schemes {s['schemes_used']}")
    scheme = max(s["schemes_used"], key=s["schemes_used"].get)
    eng_params = eng.params          # absorbed leaves attached once
    del eng
    with Phase("async engine", clock):
        _, async_tok = run_engine(AsyncPagedMLAEngine, cfg, params, load,
                                  dtype=dtype, platform=platform)
    same = async_tok == sync_tok
    log(f"sync/async token identity over {len(sync_tok)} sequences: {same}")
    if not same:
        for r in sync_tok:
            a, b = sync_tok[r], async_tok.get(r, [])
            if a != b:
                i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                         min(len(a), len(b)))
                log(f"rid {r}: first difference at token {i}: sync "
                    f"{a[i:i + 4]} async {b[i:i + 4]}")
        raise SystemExit("chip_smoke: sync and async tokens differ")

    seqs = sequences(load, sync_tok)
    num_blocks, nb = pool_geometry(load)
    tf = {}
    for impl in ("kernel", "ref"):
        with Phase(f"teacher-forced {impl}", clock):
            tf[impl] = teacher_forced(cfg, eng_params, seqs, impl=impl,
                                      scheme=scheme, dtype=dtype,
                                      num_blocks=num_blocks, nb=nb)
    stats = compare_logits(tf["kernel"][0], tf["ref"][0])
    replay = np.mean(np.concatenate([
        lg.argmax(-1) == np.asarray(o) for lg, (_, o) in
        zip(tf["kernel"][0], seqs)]))
    log(f"logits kernel vs gather (teacher-forced, scheme {scheme}): "
        + json.dumps(stats) + f"; bounds {json.dumps(LOGIT_BOUNDS)}")
    log(f"teacher-forced kernel argmax reproduces the engine's tokens at "
        f"{replay:.4f} of positions")
    if not within_bounds(stats):
        raise SystemExit("chip_smoke: kernel vs gather logits outside bounds")

    steps, shapes = tf["kernel"][1], tf["kernel"][2]
    for kind in ("decode", "prefill"):
        n = mosaic_calls(compiled_text(steps[kind], shapes[kind]))
        log(f"compiled {kind} step (impl=kernel): {n} tpu_custom_call")
        if n == 0:
            raise SystemExit(f"chip_smoke: the compiled {kind} step holds "
                             "no Mosaic kernel")
    del tf

    with Phase("http", clock):
        check_http(make_engine(AsyncPagedMLAEngine, cfg, params, load,
                               dtype=dtype, platform=platform),
                   load, sync_tok)
    log(f"device memory: {peak_memory(devs[:1])}")


def run_mesh(devs, spec: str, cfg, load, platform, seed: int,
             clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.runtime import PagedMLAEngine
    dp, mp = (int(x) for x in spec.lower().split("x"))
    if len(devs) < dp * mp:
        raise SystemExit(f"chip_smoke: --mesh {spec} needs {dp * mp} "
                         f"devices, found {len(devs)}")
    mesh = make_mesh((dp, mp), ("data", "model"), devices=devs[:dp * mp])
    dtype = jnp.bfloat16
    with Phase("weights", clock):
        params = init_weights(cfg, seed, dtype)
    num_blocks, nb = pool_geometry(load)
    with Phase("one-chip engine", clock):
        eng1, tok1 = run_engine(PagedMLAEngine, cfg, params, load,
                                dtype=dtype, platform=platform)
    s1 = eng1.summary()["schemes_used"]
    scheme = max(s1, key=s1.get)
    p1 = eng1.params
    del eng1
    with Phase(f"mesh {spec} engine", clock):
        engm, tokm = run_engine(PagedMLAEngine, cfg, params, load,
                                dtype=dtype, platform=platform, mesh=mesh)
    mesh_devs = set(mesh.devices.flat)
    leaves = jax.tree.leaves(engm.params)
    spread = all(set(x.sharding.device_set) == mesh_devs for x in leaves)
    sharded = sum(not x.sharding.is_fully_replicated for x in leaves)
    pool_ok = all(set(x.sharding.device_set) == mesh_devs
                  for x in jax.tree.leaves(engm.pool))
    held = {d: 0 for d in mesh_devs}
    for x in leaves:
        for sh in x.addressable_shards:
            held[sh.device] += sh.data.nbytes
    log(f"placement: every weight leaf on all {len(mesh_devs)} devices: "
        f"{spread}; {sharded}/{len(leaves)} leaves sharded; pool on all "
        f"devices: {pool_ok}; weight GiB per device: "
        + ", ".join(f"{d.id}: {held[d] / 2**30:.2f}"
                    for d in sorted(held, key=lambda d: d.id)))
    if not (spread and sharded and pool_ok and min(held.values()) > 0):
        raise SystemExit("chip_smoke: weights or pool do not span the mesh")
    mp_params = engm.params
    del engm
    seqs = sequences(load, tok1)
    with Phase("teacher-forced one-chip", clock):
        ref_logits = teacher_forced(cfg, p1, seqs, impl="kernel",
                                    scheme=scheme, dtype=dtype,
                                    num_blocks=num_blocks, nb=nb)[0]
    with Phase(f"teacher-forced mesh {spec}", clock):
        got, steps, shapes = teacher_forced(
            cfg, mp_params, seqs, impl="kernel", scheme=scheme, dtype=dtype,
            num_blocks=num_blocks, nb=nb, mesh=mesh)
    text = compiled_text(steps["decode"], shapes["decode"])
    coll = {op: text.count(op) for op in
            ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")}
    kernels = mosaic_calls(text)
    log(f"compiled mesh decode step: collectives {json.dumps(coll)}, "
        f"{kernels} tpu_custom_call")
    if not coll["all-reduce"] or not kernels:
        raise SystemExit("chip_smoke: the mesh decode step lacks its "
                         "all-reduce or its kernel")
    stats = compare_logits(got, ref_logits)
    same = tokm == tok1
    log(f"mesh vs one chip: tokens identical {same}; logits "
        f"(teacher-forced on the one-chip tokens) {json.dumps(stats)}; "
        f"bounds {json.dumps(LOGIT_BOUNDS)}")
    if not within_bounds(stats):
        raise SystemExit("chip_smoke: mesh logits outside bounds")
    log(f"device memory: {peak_memory(devs)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the prompts")
    ap.add_argument("--mesh", default="",
                    help="'DPxMP' (e.g. 2x2): run only the sharded engine "
                         "on that mesh against the one-chip engine")
    args = ap.parse_args(argv)
    devs = check_device()
    from repro import models
    from repro.hwmodel.platforms import resolve_platform
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    cfg, reduced, settings = one_chip_cut()
    log("model: " + json.dumps({
        "config": "deepseek-v2-236b",
        "widths": {k: getattr(cfg, k) for k in (
            "d_model", "n_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_dim", "qk_rope_dim", "v_head_dim", "moe_d_ff", "top_k",
            "n_shared_experts", "first_dense_d_ff", "vocab")},
        "reduced": reduced, "settings": settings,
        "params": models.param_count(cfg)}))
    platform = resolve_platform()
    log(f"auto dispatch prices against {platform.name} "
        f"({platform.peak_flops / 1e12:.0f} TFLOP/s, "
        f"{platform.hbm_bw / 1e9:.0f} GB/s)")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.mesh:
        load = make_load(cfg.vocab, args.seed, MESH_LOAD, shared=0)
        run_mesh(devs, args.mesh, cfg, load, platform, args.seed, clock)
    else:
        load = make_load(cfg.vocab, args.seed)
        run_one_chip(devs, cfg, load, platform, args.seed, clock)
    log(f"total: {time.perf_counter() - t0:.1f} s wall, {clock.total:.1f} s "
        "XLA compile")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
