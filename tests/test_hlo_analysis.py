"""The structural HLO analyzer must agree with hand-computed FLOPs and
collective bytes — including inside scanned loops, where XLA:CPU's own
cost_analysis undercounts (while bodies counted once)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import HloAnalysis, _shape_info


def _analyze(fn, *args):
    comp = jax.jit(fn).lower(*args).compile()
    return HloAnalysis(comp.as_text())


def test_plain_matmul_flops():
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    h = _analyze(lambda x, y: x @ y, a, b)
    assert h.dot_flops == 2 * 128 * 256 * 64


def test_scanned_matmul_flops_scales_with_trip_count():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)

    def make(n):
        def f(w, x):
            def body(c, _):
                return jnp.dot(c, w), ()
            y, _ = jax.lax.scan(body, x, None, length=n)
            return y
        return f

    h3 = _analyze(make(3), w, x)
    h9 = _analyze(make(9), w, x)
    per_iter = 2 * 64 * 128 * 128
    assert h3.dot_flops == 3 * per_iter
    assert h9.dot_flops == 9 * per_iter


def test_scanned_equals_unrolled():
    """The whole point: scanned and unrolled programs report the same flops."""
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)

    def scanned(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    def unrolled(w, x):
        for _ in range(5):
            x = jnp.tanh(x @ w)
        return x

    hs, hu = _analyze(scanned, w, x), _analyze(unrolled, w, x)
    assert hs.dot_flops == hu.dot_flops == 5 * 2 * 32 * 64 * 64


def test_nested_scan_multipliers():
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)

    def f(w, x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, ()
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, ()
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    h = _analyze(f, w, x)
    assert h.dot_flops == 3 * 4 * 2 * 8 * 32 * 32


def test_batched_dot_contracting_dims():
    a = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 32, 8), jnp.float32)
    h = _analyze(lambda x, y: jnp.einsum("bij,bjk->bik", x, y), a, b)
    assert h.dot_flops == 2 * 4 * 16 * 32 * 8


def test_shape_info_tuple_and_comments():
    n, b, dims = _shape_info("(s32[], bf16[1,256]{1,0}, /*index=5*/f32[4,8]{1,0})")
    assert n == 1 + 256 + 32
    assert b == 4 + 512 + 128


HLO_FIXTURE = """\
HloModule fixture, is_scheduled=true

ENTRY %main_spmd (p0: f32[64,128]) -> f32[64,128] {
  %p0 = f32[64,128]{1,0} parameter(0)
  %ag = f32[64,256]{1,0} all-gather(%p0), channel_id=1, replica_groups=[2,2]<=[4], dimensions={1}
  %sl = f32[64,128]{1,0} slice(%ag), slice={[0:64],[0:128]}
  %ar = f32[64,128]{1,0} all-reduce(%sl), channel_id=2, replica_groups=[2,2]<=[4], to_apply=%add
  %rs = f32[32,128]{1,0} reduce-scatter(%ar), channel_id=3, replica_groups=[2,2]<=[4], dimensions={0}
  ROOT %cp = f32[64,128]{1,0} collective-permute(%ar), channel_id=4, source_target_pairs={{0,1},{1,0}}
}
"""


def test_collective_bytes_semantics():
    h = HloAnalysis(HLO_FIXTURE)
    s = h.summary()
    # all-gather at gathered size; all-reduce/reduce-scatter/permute at operand
    assert s["collective_bytes"]["all-gather"] == 64 * 256 * 4
    assert s["collective_bytes"]["all-reduce"] == 64 * 128 * 4
    assert s["collective_bytes"]["reduce-scatter"] == 64 * 128 * 4
    assert s["collective_bytes"]["collective-permute"] == 64 * 128 * 4


def test_real_collectives_on_sharded_program():
    """End-to-end: psum over 1-device mesh emits no cross-device traffic, but
    the analyzer still parses the module without error."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("d",), axis_types=(AxisType.Auto,))
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32,
                             sharding=NamedSharding(mesh, P("d")))
    h = _analyze(lambda a: (a @ a.T).sum(), x)
    assert h.flops > 0


def test_dus_charged_at_region_size():
    buf = jax.ShapeDtypeStruct((1024, 128), jnp.float32)
    upd = jax.ShapeDtypeStruct((1, 128), jnp.float32)

    def f(b, u):
        def body(c, i):
            return jax.lax.dynamic_update_slice(c, u, (i, 0)), ()
        y, _ = jax.lax.scan(body, b, jnp.arange(8))
        return y

    h = _analyze(f, buf, upd)
    # 8 updates of one row — must NOT charge 8 full-buffer copies
    assert h.bytes_accessed < 1024 * 128 * 4 * 4
