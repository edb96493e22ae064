"""Roofline HLO analyzer: trip-count awareness + collective accounting."""
import subprocess
import sys
import textwrap

import pytest

from repro.roofline.hlo_analysis import (CollectiveRecord, analyze,
                                         shape_bytes, shape_dims, shape_elems)


def test_shape_parsing():
    assert shape_bytes("f32[4,8]{1,0}") == 128
    assert shape_bytes("bf16[10]") == 20
    assert shape_bytes("(f32[2], s32[4])") == 24
    assert shape_bytes("pred[]") == 1
    assert shape_elems("f32[3,5]{1,0}") == 15
    assert shape_dims("bf16[2,3,4]") == [2, 3, 4]


def test_collective_traffic_model():
    ar = CollectiveRecord("all-reduce", 100.0, 4, 2.0)
    assert ar.traffic_bytes == pytest.approx(2 * 100 * 0.75 * 2)
    ag = CollectiveRecord("all-gather", 100.0, 4, 1.0)
    assert ag.traffic_bytes == pytest.approx(75.0)
    rs = CollectiveRecord("reduce-scatter", 25.0, 4, 1.0)
    assert rs.traffic_bytes == pytest.approx(25 * 4 * 0.75)


@pytest.mark.slow
def test_trip_count_awareness_subprocess():
    """flops(scan of 10 matmuls) ~ 10x flops(single matmul)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, json
        from repro.roofline.hlo_analysis import analyze

        def one(w, x):
            return jnp.sum(x @ w[0])

        def scan10(w, x):
            def body(c, wi):
                return c @ wi, None
            y, _ = jax.lax.scan(body, x, w)
            return jnp.sum(y)

        W = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
        X = jax.ShapeDtypeStruct((32, 128), jnp.float32)
        f1 = analyze(jax.jit(one).lower(W, X).compile().as_text(), 1)
        f10 = analyze(jax.jit(scan10).lower(W, X).compile().as_text(), 1)
        ratio = f10["flops_per_device"] / f1["flops_per_device"]
        print("RATIO", ratio)
        assert 8.0 < ratio < 12.5, ratio
        print("TRIPS_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_env())
    assert "TRIPS_OK" in r.stdout, (r.stdout, r.stderr[-2000:])


@pytest.mark.slow
def test_collectives_detected_subprocess():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.roofline.hlo_analysis import analyze

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        W = jax.ShapeDtypeStruct((256, 512), jnp.float32)
        X = jax.ShapeDtypeStruct((64, 256), jnp.float32)
        f = lambda w, x: jnp.sum((x @ w)**2)
        c = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", "model")),
                                     NamedSharding(mesh, P("data", None)))
                    ).lower(W, X).compile()
        a = analyze(c.as_text(), 4)
        assert a["collective_traffic_per_device"] > 0
        kinds = set(a["collective_traffic_by_kind"])
        assert "all-gather" in kinds or "all-reduce" in kinds, kinds
        print("COLL_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_env())
    assert "COLL_OK" in r.stdout, (r.stdout, r.stderr[-2000:])


def _env():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return env
