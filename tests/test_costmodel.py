"""Validate the jaxpr cost model against fully-unrolled XLA cost analysis."""

import jax
import jax.numpy as jnp

from repro.launch.costmodel import analyze_lowered


def test_scan_flops_match_unrolled_xla():
    d, L = 128, 10
    x = jnp.zeros((d, d))
    w = jnp.zeros((L, d, d))

    def rolled(x, w):
        out, _ = jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)
        return out

    def unrolled(x, w):
        out, _ = jax.lax.scan(lambda c, wi: (c @ wi, None), x, w, unroll=L)
        return out

    xla = jax.jit(unrolled).lower(x, w).compile().cost_analysis()["flops"]
    ours = analyze_lowered(rolled, (x, w), {}).flops
    # elementwise accounting adds O(d^2); dot flops are O(L d^3)
    assert abs(ours - xla) / xla < 0.02, (ours, xla)


def test_nested_scan_trip_counts():
    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    x = jnp.zeros((64, 64))
    costs = analyze_lowered(f, (x,), {})
    expect = 15 * 2 * 64**3  # 5*3 matmuls
    assert abs(costs.flops - expect) / expect < 0.05


def test_grad_includes_backward_flops():
    w = jnp.zeros((64, 64))
    x = jnp.zeros((8, 64))

    def loss(w, x):
        return jnp.sum((x @ w) ** 2)

    fwd = analyze_lowered(loss, (w, x), {}).flops
    both = analyze_lowered(jax.grad(loss), (w, x), {}).flops
    assert both > 1.8 * fwd  # fwd matmul + dw backward matmul


def test_remat_counted_as_recompute():
    w = jnp.zeros((16, 64, 64))
    x = jnp.zeros((8, 64))

    def net(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        out, _ = jax.lax.scan(body, x, w)
        return jnp.sum(out)

    def net_remat(w, x):
        def body(c, wi):
            return jax.checkpoint(lambda cc, ww: jnp.tanh(cc @ ww))(c, wi), None
        out, _ = jax.lax.scan(body, x, w)
        return jnp.sum(out)

    plain = analyze_lowered(jax.grad(net), (w, x), {}).flops
    remat = analyze_lowered(jax.grad(net_remat), (w, x), {}).flops
    assert remat > plain  # recompute shows up


def test_collective_bytes_with_axis_sizes():
    mesh_axes = {"data": 8}

    def f(x):
        y = jax.lax.psum(x, "data")
        z = jax.lax.ppermute(y, "data", [(i, (i + 1) % 8) for i in range(8)])
        return z

    # trace with an abstract mesh context via shard_map
    mesh = jax.make_mesh((1,), ("data",))  # sizes come from axis_sizes arg

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    x = jnp.zeros((1024,), jnp.float32)  # 4 KiB
    sm = jax.shard_map(f, mesh=jax.make_mesh((1,), ("data",)),
                   in_specs=P(), out_specs=P(), check_vma=False)
    costs = analyze_lowered(sm, (x,), mesh_axes)
    nbytes = 1024 * 4
    expect = 2 * (7 / 8) * nbytes + nbytes  # all-reduce + permute
    assert abs(costs.collective_bytes - expect) / expect < 1e-6
    assert costs.collective_counts["all-reduce"] == 1
    assert costs.collective_counts["collective-permute"] == 1


def test_collectives_inside_scan_are_multiplied():
    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "data"), None
        out, _ = jax.lax.scan(body, x, None, length=6)
        return out

    from jax.sharding import PartitionSpec as P

    x = jnp.zeros((256,), jnp.float32)
    sm = jax.shard_map(f, mesh=jax.make_mesh((1,), ("data",)),
                   in_specs=P(), out_specs=P(), check_vma=False)
    costs = analyze_lowered(sm, (x,), {"data": 4})
    expect = 6 * 2 * (3 / 4) * 256 * 4
    assert abs(costs.collective_bytes - expect) / expect < 1e-6
