"""Unit tests for the HLO cost analyzer — the §Roofline numbers stand on
this module, so its core behaviors are pinned here against a program with
hand-computable costs (and against XLA's own body-once undercount)."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.hlo import (_group_size, _shape_bytes, _shape_dims,
                                analyze_hlo, roofline_terms, HloCost,
                                TPU_V5E)


def test_shape_parsing():
    assert _shape_bytes("f32[64,256]{1,0}") == 64 * 256 * 4
    assert _shape_bytes("bf16[8,128]") == 8 * 128 * 2
    assert _shape_bytes("(s32[], f32[4,4]{1,0})") == 4 + 64
    assert _shape_bytes("pred[7]") == 7
    assert _shape_dims("f32[2,3,4]{2,1,0}") == [2, 3, 4]
    assert _shape_bytes("token[]") == 0


def test_replica_group_parsing():
    assert _group_size("replica_groups={{0,1,2,3},{4,5,6,7}}") == 4
    assert _group_size("replica_groups=[2,4]<=[8]") == 4
    assert _group_size("replica_groups=[4,2]<=[2,4]T(1,0)") == 2
    assert _group_size("") == 1


def test_roofline_terms_math():
    c = HloCost(flops=197e12, bytes_hbm=819e9, coll_bytes=25e9)
    rl = roofline_terms(c, TPU_V5E, model_flops_per_device=197e12 / 2)
    assert abs(rl["compute_s"] - 1.0) < 1e-9
    assert abs(rl["memory_s"] - 1.0) < 1e-9
    assert abs(rl["collective_s"] - 0.5) < 1e-9
    assert rl["bottleneck"] in ("compute", "memory")
    assert abs(rl["useful_flops_ratio"] - 0.5) < 1e-9
    assert abs(rl["mfu_bound"] - 0.5) < 1e-9


PROBE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.analysis import analyze_hlo
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    def f(x, ws):
        def body(c, w):
            y = c @ w
            y = lax.with_sharding_constraint(
                y, NamedSharding(mesh, P("data", None)))
            return jnp.tanh(y) @ w.T, None
        y, _ = lax.scan(body, x, ws)
        return y
    ws = jax.ShapeDtypeStruct((12, 256, 256), jnp.float32)
    sx = NamedSharding(mesh, P("data", None))
    sw = NamedSharding(mesh, P(None, None, "model"))
    co = jax.jit(f, in_shardings=(sx, sw),
                 out_shardings=sx).lower(x, ws).compile()
    c = analyze_hlo(co.as_text())
    # hand-computed per-device: 12 trips x (dot1: 2*64*256*256 over the
    # gathered w + dot2: 2*64*256*64) ; AG out [256,256]f32 x 3/4 ; AR in
    # [64,256]f32 x 2 x 3/4
    assert c.flops == 12 * (2*64*256*256 + 2*64*256*64), c.flops
    assert c.coll_bytes == 12 * (262144 * 3/4 + 2 * 65536 * 3/4), c.coll_bytes
    assert c.unknown_trip_whiles == 0
    assert set(c.coll_by_kind) == {"all-gather", "all-reduce"}
    # XLA's own cost_analysis counts the body ONCE (the undercount this
    # module exists to fix)
    xla = co.cost_analysis()["flops"]
    assert xla < c.flops / 6, (xla, c.flops)
    print("ANALYSIS_OK")
""")


def test_analyzer_trip_counts_and_collectives_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", PROBE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "ANALYSIS_OK" in r.stdout, r.stdout + r.stderr[-2000:]


def test_model_flops_sanity():
    from repro.analysis.model_flops import model_flops
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES

    cfg = get_config("deepseek_7b")
    mf_train = model_flops(cfg, SHAPES["train_4k"])
    n = cfg.n_active_params()
    tokens = 256 * 4096
    assert mf_train >= 6 * n * tokens                 # 6ND floor
    assert mf_train < 6 * n * tokens * 1.6            # attention adds < 60%
    mf_dec = model_flops(cfg, SHAPES["decode_32k"])
    assert mf_dec < mf_train / 1000                   # one token vs 4k

    moe = get_config("mixtral_8x7b")
    assert moe.n_active_params() < 0.35 * moe.n_params()   # top-2 of 8
