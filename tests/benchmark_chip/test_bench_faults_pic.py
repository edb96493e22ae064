"""Whole BIT1 runs on the CPU with the timed path broken underneath:
each fault has to turn `correct` false. The sound run is correct."""
import dataclasses

import jax.numpy as jnp
import pytest

from faults import broken, small_run
from repro.pic import simulation

CELLS = ["bit1_paper_share4.dump_every_chunk", "bit1_paper_share4.diag_only"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, tmp_path):
    r = small_run(workload, tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0


def unchanged(state, cfg, n_steps):
    """A step that returns its state unchanged (the step count moves)."""
    return state._replace(step=state.step + n_steps)


def half_the_batch(real):
    def run_chunk(state, cfg, n_steps):
        out = real(state, cfg, n_steps)

        def mix(new, old):
            # every other slot keeps its old record: half the particles
            odd = (jnp.arange(new.shape[0]) % 2).astype(bool)
            return jnp.where(odd.reshape((-1,) + (1,) * (new.ndim - 1)),
                             old, new)

        def species(new, old):
            return dataclasses.replace(
                new, **{f: mix(getattr(new, f), getattr(old, f))
                        for f in ("x", "v", "w", "alive")})
        return out._replace(electrons=species(out.electrons, state.electrons),
                            ions=species(out.ions, state.ions),
                            neutrals=species(out.neutrals, state.neutrals))
    return run_chunk


def altered_mesh(real):
    def diagnostics(state, cfg, **kw):
        out = real(state, cfg, **kw)
        out["density/e"] = out["density/e"] * 1.01
        return out
    return diagnostics


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_the_batch",
                                   "altered_mesh"])
def test_fault_makes_the_run_incorrect(workload, fault, tmp_path,
                                       monkeypatch):
    if fault == "unchanged":
        monkeypatch.setattr(simulation, "pic_run_chunk", unchanged)
    elif fault == "half_the_batch":
        monkeypatch.setattr(simulation, "pic_run_chunk",
                            half_the_batch(simulation.pic_run_chunk))
    else:
        monkeypatch.setattr(simulation, "diagnostics",
                            altered_mesh(simulation.diagnostics))
    r = small_run(workload, tmp_path)
    assert not r["correct"]
    assert "mesh_gap" in broken(r)


def test_altered_dump_makes_the_run_incorrect(tmp_path, monkeypatch):
    real = simulation.write_particle_dump_openpmd

    def write(series, state, cfg, **kw):
        e = state.electrons
        moved = dataclasses.replace(e, x=e.x.at[0].set((e.x[0] + cfg.L / 3)
                                                       % cfg.L))
        return real(series, state._replace(electrons=moved), cfg, **kw)
    monkeypatch.setattr(simulation, "write_particle_dump_openpmd", write)
    r = small_run(CELLS[0], tmp_path)
    assert not r["correct"]
    assert {"dump_off", "pos_gap_cells"} <= set(broken(r))
