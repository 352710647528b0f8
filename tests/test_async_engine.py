"""Async double-buffered engine == synchronous engine, token for token.

The PR-9 acceptance suite: AsyncPagedMLAEngine dispatches the fused
decode+sample step for tick N and schedules tick N+1 (admission, block
growth, CoW drain) before the token ids ever reach the host.  Because
the sampling PRNG folds (request id, absolute position) — never batch
composition or wall-clock — the reordering must be invisible in the
tokens:

  * greedy and seeded temperature/top-k parity vs PagedMLAEngine on
    staggered-arrival streams, WITH recompute preemption forced (the
    in-flight token of a preempted victim is folded into its replayed
    prompt — the fix-up path is exercised, not mocked);
  * stop sequences truncate token-identically in both engines, and the
    sync engine's stop output equals its own no-stop output truncated
    at the match (token-exact semantics, not just parity);
  * spec_k > 0 delegates to the synchronous draft/verify tick and stays
    token-identical;
  * the async trace nests cleanly (validate_trace) AND shows the
    overlap that is the point of the refactor: a device_step span on
    the device-stream track wall-overlapping a host schedule span;
  * a `mesh` marked subprocess parity run (forced host device count).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
import repro.models as models
from repro.nn import module as nnm
from repro.obs import Telemetry
from repro.obs.trace import PID_ENGINE, validate_trace
from repro.runtime import (AsyncPagedMLAEngine, PagedMLAEngine, Request,
                           blocks_for)
from repro.runtime.engine import TID_DEVICE
from repro.runtime.spec import parse_draft_spec

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke("deepseek-v2-236b")
    params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                             jnp.float32)
    return cfg, params


def _mkreqs(cfg, specs, *, seed=3, stop=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (p,)).astype(np.int32),
                    max_new=g, arrival=a,
                    stop=[list(map(int, s)) for s in (stop or [])])
            for i, (p, g, a) in enumerate(specs)]


def _run(engine_cls, cfg, params, specs, *, num_blocks=24, stop=None,
         seed=3, scheme="seq", telemetry=None, **kw):
    reqs = _mkreqs(cfg, specs, seed=seed, stop=stop)
    per = max(blocks_for(r.plen + r.max_new + 1, 8) for r in reqs)
    eng = engine_cls(cfg, params, num_blocks=num_blocks, block_size=8,
                     max_batch=2, max_blocks_per_req=per,
                     compute_dtype=jnp.float32, scheme=scheme,
                     prefill_chunk=8, telemetry=telemetry, **kw)
    eng.run(reqs)
    assert len(eng.sched.finished) == len(specs)
    return eng, {r.rid: (tuple(r.output), r.finish_reason)
                 for r in eng.sched.finished}


SPECS = [(12, 9, 0), (9, 7, 0), (17, 8, 1), (8, 10, 2)]
# long generations + tiny pool: forces recompute preemption mid-stream
TIGHT = dict(specs=[(10, 30, 0), (10, 30, 0), (10, 26, 4)], num_blocks=9)


# ------------------------------------------------------------- parity ----


def test_async_greedy_parity(smoke_model):
    cfg, params = smoke_model
    _, sync = _run(PagedMLAEngine, cfg, params, SPECS)
    _, async_ = _run(AsyncPagedMLAEngine, cfg, params, SPECS)
    assert sync == async_


def test_async_seeded_sampling_parity(smoke_model):
    cfg, params = smoke_model
    kw = dict(temperature=0.8, top_k=5, sample_seed=7)
    _, sync = _run(PagedMLAEngine, cfg, params, SPECS, **kw)
    _, async_ = _run(AsyncPagedMLAEngine, cfg, params, SPECS, **kw)
    assert sync == async_


@pytest.mark.parametrize("kw", [
    {},                                             # greedy
    dict(temperature=0.9, top_k=7, sample_seed=5),  # seeded
], ids=["greedy", "seeded"])
def test_async_preemption_parity(smoke_model, kw):
    cfg, params = smoke_model
    es, sync = _run(PagedMLAEngine, cfg, params, **TIGHT, **kw)
    ea, async_ = _run(AsyncPagedMLAEngine, cfg, params, **TIGHT, **kw)
    # the claim is about the REPLAY path: both engines must actually
    # preempt, and the async fix-up (fold the in-flight token into the
    # victim's prompt) must reproduce the sync tokens exactly
    assert es.stats.preemptions > 0
    assert ea.stats.preemptions == es.stats.preemptions
    assert sync == async_


def test_async_spec_decode_parity(smoke_model):
    cfg, params = smoke_model
    dcfg, dparams = parse_draft_spec("self", cfg, params)
    kw = dict(num_blocks=14, spec_k=2, draft_cfg=dcfg, draft_params=dparams)
    es, sync = _run(PagedMLAEngine, cfg, params, SPECS, **kw)
    ea, async_ = _run(AsyncPagedMLAEngine, cfg, params, SPECS, **kw)
    assert es.stats.spec_rounds > 0
    assert sync == async_


# ------------------------------------------------------- stop sequences ----


def _truncate_at(seq, stop):
    """Reference semantics: cut at the FIRST completed stop match."""
    for i in range(len(seq) - len(stop) + 1):
        if list(seq[i:i + len(stop)]) == list(stop):
            return tuple(seq[:i])
    return tuple(seq)


def test_stop_sequences_token_exact(smoke_model):
    cfg, params = smoke_model
    specs = [(12, 8, 0), (9, 8, 1)]
    _, free = _run(PagedMLAEngine, cfg, params, specs)
    stop = [list(free[0][0][2:4])]   # 2-gram from rid 0's own stream
    _, sync = _run(PagedMLAEngine, cfg, params, specs, stop=stop)
    _, async_ = _run(AsyncPagedMLAEngine, cfg, params, specs, stop=stop)
    assert sync == async_
    # token-exact semantics: the stopped output IS the free-running
    # output truncated at the FIRST match, and the match itself is hidden
    assert sync[0][1] == "stop"
    assert sync[0][0] == _truncate_at(free[0][0], stop[0])


def test_stop_sequence_across_spec_rounds(smoke_model):
    cfg, params = smoke_model
    specs = [(12, 10, 0), (9, 8, 1)]
    dcfg, dparams = parse_draft_spec("self", cfg, params)
    kw = dict(num_blocks=20, spec_k=2, draft_cfg=dcfg, draft_params=dparams)
    _, free = _run(PagedMLAEngine, cfg, params, specs, **kw)
    stop = [list(free[0][0][3:5])]
    _, sync = _run(PagedMLAEngine, cfg, params, specs, stop=stop, **kw)
    _, async_ = _run(AsyncPagedMLAEngine, cfg, params, specs, stop=stop, **kw)
    assert sync == async_
    # a spec round may emit several tokens past the match in one tick;
    # everything after the stop must be discarded, match hidden
    assert sync[0][1] == "stop"
    assert sync[0][0] == _truncate_at(free[0][0], stop[0])


@pytest.mark.parametrize("scheme", ["seq", "rc", "ru"])
def test_stop_sequences_across_schemes(smoke_model, scheme):
    cfg, params = smoke_model
    specs = [(12, 8, 0), (9, 8, 1)]
    _, free = _run(PagedMLAEngine, cfg, params, specs, scheme=scheme)
    stop = [list(free[0][0][2:4])]
    _, sync = _run(PagedMLAEngine, cfg, params, specs, scheme=scheme,
                   stop=stop)
    _, async_ = _run(AsyncPagedMLAEngine, cfg, params, specs, scheme=scheme,
                     stop=stop)
    assert sync == async_ and sync[0][1] == "stop"


# --------------------------------------------------------------- trace ----


def test_async_trace_nests_and_overlaps(smoke_model):
    cfg, params = smoke_model
    tel = Telemetry.on(trace=True, metrics=False)
    _run(AsyncPagedMLAEngine, cfg, params, SPECS, telemetry=tel)
    trace = tel.trace_dict()
    assert validate_trace(trace) == []
    evs = [e for e in trace["traceEvents"]
           if e["ph"] == "X" and e["pid"] == PID_ENGINE]
    device = [e for e in evs
              if e["tid"] == TID_DEVICE and e["name"] == "device_step"]
    sched = [e for e in evs if e["tid"] == 0 and e["name"] == "schedule"]
    assert device and sched
    # the point of the refactor: device execution overlaps host
    # scheduling in wall time (they live on different tracks, so the
    # nesting validator above cannot be what makes this pass)
    assert any(d["ts"] < s["ts"] + s["dur"] and s["ts"] < d["ts"] + d["dur"]
               for d in device for s in sched)


# ---------------------------------------------------------------- mesh ----


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro import configs, models
from repro.launch.mesh import make_mesh
from repro.nn import module as nnm
from repro.runtime import (AsyncPagedMLAEngine, PagedMLAEngine, Request,
                           blocks_for)

cfg = configs.smoke("deepseek-v2-236b")
params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                         jnp.float32)
mesh = make_mesh((2, 1), ("data", "model"))

def run(cls, kw):
    rng = np.random.default_rng(3)
    specs = [(12, 9, 0), (9, 7, 0), (17, 8, 1), (8, 10, 2)]
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, (p,)).astype(np.int32),
                    max_new=g, arrival=a)
            for i, (p, g, a) in enumerate(specs)]
    per = max(blocks_for(r.plen + r.max_new + 1, 8) for r in reqs)
    eng = cls(cfg, params, num_blocks=24, block_size=8, max_batch=2,
              max_blocks_per_req=per, compute_dtype=jnp.float32,
              scheme="seq", prefill_chunk=8, mesh=mesh, **kw)
    eng.run(reqs)
    return {r.rid: list(map(int, r.output)) for r in eng.sched.finished}

out = {}
for label, kw in (("greedy", {}),
                  ("seeded", dict(temperature=0.8, top_k=5, sample_seed=7))):
    out[label] = (run(PagedMLAEngine, kw), run(AsyncPagedMLAEngine, kw))
print(json.dumps(out))
"""


@pytest.mark.mesh
def test_async_mesh_parity_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)   # the script sets the forced device count
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for label, (sync, async_) in out.items():
        assert sync == async_, f"{label}: mesh async diverged"
        assert len(sync) == 4


# ------------------------------------------------ interleaved prefill ----


def _interleaved(engine_cls, cfg, params, **kw):
    """Long prompts (6-9 chunk steps of 8) arriving while other rows
    decode, two of them n=2 groups: prefill interleaves with decode."""
    from repro.runtime import SamplingParams
    rng = np.random.default_rng(41)
    specs = [(8, 24, 0, 1), (50, 6, 2, 2), (64, 9, 3, 1), (12, 12, 5, 1),
             (72, 5, 9, 2), (40, 7, 12, 1)]
    reqs, rid = [], 0
    for p, g, a, n in specs:
        reqs.append(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab, (p,)).astype(np.int32),
            arrival=a, sampling=SamplingParams(max_tokens=g, n=n)))
        rid += n
    eng = engine_cls(cfg, params, num_blocks=96, block_size=8, max_batch=4,
                     max_blocks_per_req=12, compute_dtype=jnp.float32,
                     scheme="seq", prefill_chunk=8, **kw)
    eng.run(reqs)
    assert len(eng.sched.finished) == rid
    return eng, {r.rid: (tuple(r.output), r.finish_reason)
                 for r in eng.sched.finished}


@pytest.mark.parametrize("kw", [
    {},                                             # greedy
    dict(temperature=0.9, top_k=7, sample_seed=5),  # seeded
], ids=["greedy", "seeded"])
def test_async_interleaved_prefill_parity(smoke_model, kw):
    cfg, params = smoke_model
    es, sync = _interleaved(PagedMLAEngine, cfg, params, **kw)
    ea, async_ = _interleaved(AsyncPagedMLAEngine, cfg, params, **kw)
    for eng in (es, ea):
        assert eng.stats.interleaved_chunks > 0
        assert eng.summary()["fork_groups"] == 2.0
        assert eng.prefill_compiles == 1
    assert sync == async_
