"""The while-loop-aware HLO cost parser: exactness on controlled programs."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo as hloa


def compile_fn(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_flops_exact_vs_xla_undercount():
    W = jnp.zeros((10, 128, 128), jnp.float32)
    x0 = jnp.zeros((128, 128), jnp.float32)

    def f(x0, W):
        def body(x, w):
            return jnp.tanh(x @ w), ()
        return jax.lax.scan(body, x0, W)[0]

    comp = compile_fn(f, x0, W)
    expected = 10 * 2 * 128 ** 3
    got = hloa.analyze(comp.as_text()).flops
    assert got == pytest.approx(expected, rel=0.01)
    # and XLA's own cost_analysis undercounts the loop (the reason this
    # module exists) — if XLA ever fixes this, we can drop the parser.
    xla = comp.cost_analysis().get("flops", 0)
    assert xla < expected


def test_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ c2), ()
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, ()
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    comp = compile_fn(f, jnp.zeros((64, 64), jnp.float32))
    expected = 3 * 4 * 2 * 64 ** 3
    got = hloa.analyze(comp.as_text()).flops
    assert got == pytest.approx(expected, rel=0.02)


def test_plain_matmul_flops():
    a = jnp.zeros((256, 512), jnp.float32)
    b = jnp.zeros((512, 128), jnp.float32)
    comp = compile_fn(lambda a, b: a @ b, a, b)
    got = hloa.analyze(comp.as_text()).flops
    assert got == pytest.approx(2 * 256 * 512 * 128, rel=0.01)


def test_dus_charged_at_slice_size():
    """A scan writing small slices into a big buffer must not be billed
    full-buffer traffic per step."""
    buf = jnp.zeros((512, 1024), jnp.float32)   # 2 MB

    def f(buf):
        def body(b, i):
            return jax.lax.dynamic_update_slice_in_dim(
                b, jnp.ones((1, 1024)), i, axis=0), ()
        return jax.lax.scan(body, buf, jnp.arange(512))[0]

    comp = compile_fn(f, buf)
    got = hloa.analyze(comp.as_text()).bytes
    # slice traffic = 512 iters * 2 * 4KB = 4 MB; full-buffer billing
    # would be 512 * 2 * 2 MB = 2 GB.  Allow generous slack for loop
    # bookkeeping, assert we are orders below full-buffer.
    assert got < 100e6


def test_collective_factors():
    txt = """
HloModule m

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%p), replica_groups=[1,8]<=[8], to_apply=%add
}
"""
    cost = hloa.analyze(txt, num_partitions=8)
    # all-reduce ring traffic = 2*(G-1)/G * bytes = 2*7/8*4096
    assert cost.collective_bytes == pytest.approx(2 * 7 / 8 * 4096)


def test_trip_count_from_backend_config():
    txt = """
HloModule m

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[8]{0} get-tuple-element(%t), index=1
  %d = f32[8]{0} dot(%x, %x), lhs_contracting_dims={}, rhs_contracting_dims={}
  %c1 = s32[] constant(1)
  %ip = s32[] add(%i, %c1)
  ROOT %r = (s32[], f32[8]) tuple(%ip, %d)
}

%cond (t: (s32[], f32[8])) -> pred[] {
  %t = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %w = (s32[], f32[8]) while(%p), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
}
"""
    cost = hloa.analyze(txt)
    assert cost.while_trip_counts.get("w") == 7


def test_unknown_trip_count_warns_and_defaults_to_one():
    """A while whose condition has no static s32 limit (data-dependent
    loop) must degrade to trip=1 WITH a warning — silent undercounting is
    the exact failure mode this parser exists to prevent."""
    txt = """
HloModule m

%body (t: (f32[], f32[8])) -> (f32[], f32[8]) {
  %t = (f32[], f32[8]) parameter(0)
  %l = f32[] get-tuple-element(%t), index=0
  %x = f32[8]{0} get-tuple-element(%t), index=1
  ROOT %r = (f32[], f32[8]) tuple(%l, %x)
}

%cond (t: (f32[], f32[8])) -> pred[] {
  %t = (f32[], f32[8]) parameter(0)
  %l = f32[] get-tuple-element(%t), index=0
  %z = f32[] get-tuple-element(%t), index=0
  ROOT %lt = pred[] compare(%l, %z), direction=LT
}

ENTRY %main (p: (f32[], f32[8])) -> (f32[], f32[8]) {
  %p = (f32[], f32[8]) parameter(0)
  ROOT %w = (f32[], f32[8]) while(%p), condition=%cond, body=%body
}
"""
    cost = hloa.analyze(txt)
    assert cost.while_trip_counts.get("w") == 1
    assert any("unknown trip count" in w for w in cost.warnings)


def test_tuple_typed_op_bytes_sum_components():
    """Tuple-typed results (with the /*index=N*/ comments real HLO puts
    inside them) must parse and bill the SUM of the component shapes."""
    assert hloa._shape_bytes("(s32[], /*index=1*/f32[8]{0}, bf16[4,4]{1,0})") \
        == 4 + 32 + 32
    txt = """
HloModule m

ENTRY %main (p: f32[8]) -> (s32[], f32[8]) {
  %p = f32[8]{0} parameter(0)
  ROOT %cc = (s32[], /*index=1*/f32[8]{0}) custom-call(%p), custom_call_target="topk"
}
"""
    comps = hloa.parse_computations(txt)
    entry = comps["main"]
    cc = entry.ops[-1]
    assert cc.opcode == "custom-call" and cc.operands == ["p"]
    # custom-call bytes = tuple output (4 + 32) + f32[8] operand (32)
    assert hloa.analyze(txt).bytes == 68


def test_scatter_charged_at_update_size():
    """scatter moves 2x the UPDATE operand (read+write in place), never
    the full indexed buffer."""
    txt = """
HloModule m

ENTRY %main (p: f32[128,64]) -> f32[128,64] {
  %p = f32[128,64]{1,0} parameter(0)
  %i = s32[4,1]{1,0} parameter(1)
  %u = f32[4,64]{1,0} parameter(2)
  ROOT %sc = f32[128,64]{1,0} scatter(%p, %i, %u), update_window_dims={1}, to_apply=%missing
}
"""
    # 2 * |update| = 2 * 4*64*4 B, NOT 2 * 128*64*4 B
    assert hloa.analyze(txt).bytes == 2 * 4 * 64 * 4


def test_pad_charged_at_output_not_operand_free():
    txt = """
HloModule m

ENTRY %main (p: f32[128,64]) -> f32[132,64] {
  %p = f32[128,64]{1,0} parameter(0)
  %z = f32[] constant(0)
  ROOT %pd = f32[132,64]{1,0} pad(%p, %z), padding=2_2x0_0
}
"""
    assert hloa.analyze(txt).bytes == 2 * 132 * 64 * 4


def test_effective_shapes_resolve_convert_chains():
    """Converts are CPU float-normalization artifacts: an op consuming a
    convert (even a chain of them) is billed at the pre-convert size."""
    txt = """
HloModule m

ENTRY %main (p: bf16[64,64]) -> f64[64,64] {
  %p = bf16[64,64]{1,0} parameter(0)
  %c1 = f32[64,64]{1,0} convert(%p)
  ROOT %c2 = f64[64,64]{1,0} convert(%c1)
}
"""
    comps = hloa.parse_computations(txt)
    entry = comps["main"]
    eff = hloa._EffectiveShapes(entry, comps, hloa._transparent_comps(comps))
    # both hops resolve back to the bf16 source: 64*64*2 bytes, not *4/*8
    assert eff.bytes_of("c1") == 64 * 64 * 2
    assert eff.bytes_of("c2") == 64 * 64 * 2
    # and converts themselves are free, so the module bills zero traffic
    assert hloa.analyze(txt).bytes == 0


def test_transparent_fusion_shim_is_free():
    """A fusion whose body only converts/reshapes is a dtype shim — its
    scheduled-op traffic must be zero."""
    txt = """
HloModule m

%shim (a: bf16[32,32]) -> f32[32,32] {
  %a = bf16[32,32]{1,0} parameter(0)
  ROOT %cv = f32[32,32]{1,0} convert(%a)
}

ENTRY %main (p: bf16[32,32]) -> f32[32,32] {
  %p = bf16[32,32]{1,0} parameter(0)
  ROOT %f = f32[32,32]{1,0} fusion(%p), kind=kLoop, calls=%shim
}
"""
    assert hloa.analyze(txt).bytes == 0
