"""CPU rehearsal of chip_smoke.py: its phases at cpu_config(256), the same
functions the chip run calls at paper_config size."""
import dataclasses
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs.bit1 import cpu_config

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.update(extra)
    return env


def test_smoke_refuses_cpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=_env(),
                       cwd=ROOT, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "JAX found no TPU" in r.stderr


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmpdir_path, from_env):
    """Entries land in `JAX_COMPILATION_CACHE_DIR` when it is set; else the
    cache is the fixed directory in the checkout (not compiled into here)."""
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmpdir_path / "cache")
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        d = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(d), d
        if {from_env}:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()
        print(d)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=tmpdir_path, timeout=120)
    assert r.returncode == 0, r.stderr[-3000:]
    got = pathlib.Path(r.stdout.strip().splitlines()[-1])
    if from_env:
        assert got == tmpdir_path / "cache"
        assert any(p.name.startswith("jit_") for p in got.iterdir())
    else:
        assert got == ROOT / ".jax_cache"


def test_smoke_phases_on_cpu(smoke, tmpdir_path):
    from repro.pic.simulation import diagnostics, init_sim
    cfg = cpu_config(256)
    log = smoke.CompileLog()
    try:
        state = init_sim(cfg, jax.random.PRNGKey(smoke.SEED))
        smoke.phase_reference(state, cfg)
        before = smoke.particle_totals(state)
        state, diag_last, t = smoke.phase_pic(
            state, cfg, tmpdir_path / "diag.bp4", n_chunks=3,
            steps_per_chunk=3)
        assert len(t["chunk_walls_s"]) == 3
        host = jax.device_get(state)
        smoke.check_physics(before, smoke.particle_totals(host),
                            int(host.total_ionizations))
        smoke.phase_readback(tmpdir_path / "diag.bp4", host, diag_last,
                             diagnostics(state, cfg))
        smoke.phase_device_payload(state)
        ck = smoke.phase_checkpoint(state, cfg, tmpdir_path / "ckpt", host,
                                    steps_per_chunk=3)
        assert ck["COMPRESS_DEVICE_BYTES"] > 0
        # one compile for the loop, the restored run and the live run
        assert log.count["jit(pic_run_chunk)"] == 1
    finally:
        log.close()


def test_smoke_readback_catches_a_changed_record(smoke, tmpdir_path):
    cfg = cpu_config(1024)
    from repro.pic.simulation import diagnostics, init_sim
    state, diag_last, _ = smoke.phase_pic(
        init_sim(cfg, jax.random.PRNGKey(1)), cfg, tmpdir_path / "d.bp4",
        n_chunks=1, steps_per_chunk=1)
    host = jax.device_get(state)
    x = host.ions.x.copy()
    x[7] += 1.0
    host = host._replace(ions=dataclasses.replace(host.ions, x=x))
    with pytest.raises(smoke.SmokeFailure, match="particles/D_plus/position"):
        smoke.phase_readback(tmpdir_path / "d.bp4", host, diag_last,
                             diagnostics(state, cfg))


def test_smoke_sharded_phase_on_four_cpu_devices(tmpdir_path):
    script = tmpdir_path / "sharded.py"
    script.write_text(textwrap.dedent(f"""
        import importlib.util, json, jax
        from repro.configs.bit1 import cpu_config

        if __name__ == "__main__":
            spec = importlib.util.spec_from_file_location(
                "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
            smoke = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(smoke)
            out = smoke.phase_sharded(cpu_config(256),
                                      {str(tmpdir_path / "ckpt")!r},
                                      jax.devices())
            print(json.dumps(out))
    """))
    r = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        cwd=tmpdir_path, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["COMPRESS_DEVICE_BYTES"] > 0
