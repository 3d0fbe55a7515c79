"""Sharding rules + small-mesh distributed correctness (subprocess: the
forced-device-count flag must not leak into other tests)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.models.sharding import ShardCtx, spec_for_param

pytestmark = pytest.mark.slow  # subprocess XLA dry-runs: ~1 min on CPU

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def ctx16():
    return ShardCtx(mesh=None)  # spec building only needs sizes via mesh


def test_spec_rules_paths():
    import types
    mesh = FakeMesh({"data": 16, "model": 16})
    ctx = ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    # column parallel default
    assert spec_for_param(ctx, "groups/b0/attn/wq", (4096, 4096)) == P("data", "model")
    # row parallel
    assert spec_for_param(ctx, "groups/b0/attn/wo", (4096, 4096)) == P("model", "data")
    assert spec_for_param(ctx, "groups/b0/ffn/w_down", (14336, 4096)) == P("model", "data")
    # embeddings vocab-sharded
    assert spec_for_param(ctx, "embed", (128512, 4096)) == P("model", "data")
    # MoE experts dim on tp
    s = spec_for_param(ctx, "groups/b0/moe/experts/w1", (128, 4096, 1536))
    assert s == P("model", "data", None)
    s2 = spec_for_param(ctx, "groups/b0/moe/experts/w2", (128, 1536, 4096))
    assert s2 == P("model", None, "data")
    # divisibility guard: head dim 7168/16 ok but 56 heads as dim would not be
    assert spec_for_param(ctx, "x/wq", (100, 100)) == P(None, None)
    # 1D params replicated
    assert spec_for_param(ctx, "norm1/scale", (4096,)) == P(None)


def test_guard_replicates_indivisible():
    mesh = FakeMesh({"data": 16, "model": 16})
    ctx = ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    assert ctx.spec(["dp", None], (1, 5)) == P(None, None)      # batch=1
    assert ctx.spec(["dp", "tp"], (32, 48)) == P("data", "model")
    assert ctx.spec([None, "tp"], (8, 40)) == P(None, None)     # 40 % 16 != 0


DISTRIBUTED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.models import init_params, synth_inputs, make_loss_fn
    from repro.models.sharding import ShardCtx, tree_shardings

    cfg = get_arch("{arch}").reduced()
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ctx = ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    shape = ShapeConfig("t", "train", 64, 4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = synth_inputs(cfg, shape)

    loss_sharded, _ = jax.jit(lambda p, b: make_loss_fn(cfg, shape, ctx)(p, b))(
        jax.device_put(params, tree_shardings(ctx, params)), batch)
    loss_single, _ = jax.jit(lambda p, b: make_loss_fn(cfg, shape)(p, b))(params, batch)
    print(json.dumps({{"sharded": float(loss_sharded), "single": float(loss_single)}}))
""")


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "olmoe-1b-7b", "rwkv6-3b"])
def test_sharded_loss_matches_single_device(arch):
    """8 fake devices: distributed loss == single-device loss (same math)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", DISTRIBUTED_SCRIPT.format(arch=arch)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    vals = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(vals["sharded"] - vals["single"]) < 0.05, vals


def test_hlo_analyzer_counts_trip_counts():
    from repro.launch.hlo_analysis import analyze_compiled

    def f(x, w):
        def body(c, _):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, None, length=4)
        return y

    x = jnp.zeros((128, 128)); w = jnp.zeros((128, 128))
    s = analyze_compiled(jax.jit(f).lower(x, w).compile())
    assert s.dot_flops == pytest.approx(4 * 2 * 128**3)


def test_hlo_analyzer_collectives_small_mesh():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze_compiled
        mesh = jax.make_mesh((4,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        def f(x, w):
            return x @ w
        xs = jax.ShapeDtypeStruct((128, 256), jnp.float32,
                                  sharding=NamedSharding(mesh, P(None, "model")))
        ws = jax.ShapeDtypeStruct((256, 128), jnp.float32,
                                  sharding=NamedSharding(mesh, P("model", None)))
        c = jax.jit(f).lower(xs, ws).compile()
        s = analyze_compiled(c)
        print(json.dumps({"coll": s.total_collective_bytes,
                          "kinds": list(s.collective_bytes)}))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    vals = json.loads(out.stdout.strip().splitlines()[-1])
    # contracting-dim sharded matmul must produce a reduction collective
    assert vals["coll"] > 0 and vals["kinds"]


MOE_EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.moe import moe_ffn, moe_ffn_sharded, moe_init
    from repro.models.sharding import ShardCtx

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ctx = ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    D, F, E, K = 32, 64, 8, 2
    B, S = 4, 16
    p = moe_init(jax.random.PRNGKey(0), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32) * 0.1

    # capacity_factor high enough that no tokens drop in either layout
    kw = dict(n_experts=E, top_k=K, capacity_factor=8.0)
    dense, aux_d = moe_ffn(p, x.reshape(B * S, D), ctx=ShardCtx(), **kw)
    with mesh:
        smap, aux_s = jax.jit(
            lambda pp, xx: moe_ffn_sharded(pp, xx, ctx=ctx, **kw)
        )(p, x)
    err = float(np.abs(np.asarray(smap.reshape(B * S, D), np.float32)
                       - np.asarray(dense, np.float32)).max())
    print(json.dumps({"err": err, "aux_d": float(aux_d), "aux_s": float(aux_s)}))
""")


def test_moe_sharded_matches_dense():
    """shard_map row x column EP == plain dispatch when nothing drops."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", MOE_EQUIV_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    vals = json.loads(out.stdout.strip().splitlines()[-1])
    assert vals["err"] < 2e-2, vals          # bf16 expert weights
    # aux: per-dp-row f_e estimator (pmean'd) vs global — close, not equal
    assert abs(vals["aux_d"] - vals["aux_s"]) < 2e-2, vals
