"""The cutoff deployment on the CPU: its scenario, its work count, its
reference's search, and a rehearsal of a run with each fault planted
underneath.

The configuration ``cutoff-262k`` runs the program's nlist cell-list
path on the upstream's random cube with forces truncated at 5e10 m. It
has no cell in ``BENCHMARK.json`` yet: over a full window at 262,144
bodies the cap the program derives overflows and the program leaves the
truncated law (``PERF.md`` section 7). Its parts are rehearsed here at
2,048 bodies (the cube keeps its published bounds, so a body has ~4.5
neighbours within the radius), through a rehearsal cell that names the
configuration: the run has to read ``correct``, and each fault the
deployment can have, planted under the Simulator, has to read not
correct: the truncation ignored (full gravity), the radius halved, half
the sources left out, one body's force x1.5, the state left unchanged,
a cell list too small for its cells (the program's overflow fallback);
and the control (the reference with bfloat16 pair arithmetic in the
program's place) has to fail the limit.
"""

import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, ics, registry
from chipbench.reference import cutoff as cref
from chipbench.reference import window as ref

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chip_control  # noqa: E402

CONFIG = "cutoff-262k"
N = 2048
SEED = 2**33 + 7
# One window of two blocks at the tiny size, 16 sampled bodies, and the
# limit the overflow readings at 262,144 bodies call for (PERF.md).
REHEARSAL = {"config": CONFIG, "traffic": "long_run", "chips": 1,
             "block_steps": 10, "nominal_step_s": 1.0,
             "sample_targets": 16, "limits": {"state_gap": 1e-3}}
TINY = {"n": N}


def _cell():
    return dict(REHEARSAL, name=CONFIG + ".rehearsal", base=registry.HERE,
                config_data=registry._load("configs", CONFIG),
                traffic_data=registry._load("traffic", "long_run"))


def _run(tmp, **extra):
    cell = _cell()
    return harness.run_cell(
        cell, seed=SEED, seconds=2 * cell["block_steps"], trace=False,
        t0=time.perf_counter(), sim_overrides={**TINY, **extra},
        out_dir=str(tmp / "out"))


def _config():
    return registry._load("configs", CONFIG)


def _ics(n, seed=SEED):
    cfg = _config()
    return [np.asarray(a) for a in ics.make(
        dict(cfg, simulation=dict(cfg["simulation"], n=n)), seed)]


def test_cube_is_drawn_from_the_seed_inside_its_bounds():
    cfg = _config()
    x, v, m = _ics(4096)
    again = _ics(4096)
    assert all(np.array_equal(a, b) for a, b in zip((x, v, m), again))
    other = _ics(4096, seed=SEED + 1)
    assert not np.array_equal(x, other[0])
    assert x.dtype == v.dtype == m.dtype == np.float32
    assert x.shape == v.shape == (4096, 3) and m.shape == (4096,)
    assert np.abs(x).max() <= cfg["pos_bound"]
    assert np.abs(v).max() <= cfg["vel_bound"]
    assert cfg["mass_low"] <= m.min() and m.max() <= cfg["mass_high"]
    # Spread over the whole box, not a corner of it.
    assert np.abs(x).max() > 0.99 * cfg["pos_bound"]
    assert np.abs(x.mean(0)).max() < 0.05 * cfg["pos_bound"]


@pytest.mark.parametrize("scale", [1.0, 0.25])
def test_cutoff_count_is_the_brute_force_count(scale):
    """``work/cutoff.py`` counts the ordered pairs within the radius
    exactly: the same integer as an O(n^2) count at 2,048 bodies (in
    the cube, and in a cube shrunk 4x so each body has ~290)."""
    x, _, m = _ics(N)
    x = np.asarray(x, np.float64) * scale
    rcut = _config()["simulation"]["nlist_rcut"]
    d2 = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    brute = int((d2 <= rcut * rcut).sum()) - N
    work = registry.module("work", "cutoff")
    assert brute > (100 if scale == 1.0 else 100000)
    assert work.pairs_within(x, rcut) == brute
    cfg = _config()
    w = work.per_eval(cfg, x, m)
    assert w["flops"] == 20.0 * brute and w["bytes"] == 28.0 * N


def test_reference_search_finds_every_pair_that_comes_near():
    """The grid search of ``near_paths`` finds exactly the pairs whose
    straight paths come within reach in the window (a brute-force
    closest-approach test over all pairs at 512 bodies)."""
    x, v, _ = _ics(512)
    x, v = np.asarray(x, np.float64), np.asarray(v, np.float64)
    window_s, reach = 190 * 3600.0, 5e10
    rows = np.arange(0, 512, 3)
    got = cref.near_paths(x, v, rows, reach=reach, window_s=window_s,
                          chunk=64)
    d0, dv = x[None, :] - x[rows][:, None], v[None, :] - v[rows][:, None]
    with np.errstate(invalid="ignore"):  # a body against itself: 0/0
        t = np.clip(-(d0 * dv).sum(-1) / (dv * dv).sum(-1), 0.0, window_s)
    dist = np.sqrt(((d0 + dv * t[..., None]) ** 2).sum(-1))
    for r, row in enumerate(rows):
        want = np.nonzero(dist[r] <= reach)[0]
        want = want[want != row]
        assert sorted(got[r][got[r] >= 0].tolist()) == want.tolist()


def test_rehearsal_is_correct(tmp_path):
    out = _run(tmp_path)
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == {"state_gap"}
    gap = out["checks"]["state_gap"]
    assert gap["value"] < gap["limit"] / 10
    assert out["window"]["sampled_bodies"] == _cell()["sample_targets"]


def _patch_nlist(monkeypatch, wrap):
    """Break the nlist force the Simulator builds its step from."""
    import gravity_tpu.ops.pallas_nlist as pallas_nlist

    inner = pallas_nlist.nlist_accelerations_vs
    monkeypatch.setattr(pallas_nlist, "nlist_accelerations_vs",
                        lambda t, p, m, **kw: wrap(inner, t, p, m, **kw))


def _full_gravity(inner, t, p, m, *, rcut, side, cap, box, _self, **kw):
    from gravity_tpu.ops.forces import accelerations_vs

    return accelerations_vs(t, p, m, **kw)


def _rcut_halved(inner, t, p, m, *, rcut, **kw):
    return inner(t, p, m, rcut=0.5 * rcut, **kw)


def _half_the_sources(inner, t, p, m, **kw):
    keep = (jnp.arange(m.shape[0]) % 2 == 0).astype(m.dtype)
    return inner(t, p, 2 * m * keep, **kw)


def _one_answer_altered(inner, t, p, m, **kw):
    body = int(ref.sample_targets(SEED, N, _cell()["sample_targets"])[5])
    scale = jnp.where(jnp.arange(t.shape[0]) == body, 1.5, 1.0)
    return inner(t, p, m, **kw) * scale.astype(t.dtype)[:, None]


@pytest.mark.parametrize("fault", ["truncation_ignored", "rcut_halved",
                                   "half_the_sources", "one_answer_altered",
                                   "state_unchanged", "cap_overflow"])
def test_faults_read_not_correct(monkeypatch, tmp_path, fault):
    import gravity_tpu.simulation as simulation

    if fault == "cap_overflow":
        # The program's own fault at 262,144 bodies, forced here: a cap
        # of 2 bodies a cell, which cells of the cube outgrow.
        out = _run(tmp_path, nlist_cap=2)
        assert out["correct"] is False, out["checks"]
        return
    if fault == "state_unchanged":
        monkeypatch.setattr(simulation, "make_step_fn",
                            lambda *a, **k: (lambda st, acc: (st, acc)))
    else:
        _patch_nlist(monkeypatch, {
            "truncation_ignored": _full_gravity,
            "rcut_halved": _rcut_halved,
            "half_the_sources": _half_the_sources,
            "one_answer_altered": _one_answer_altered}[fault])
    out = _run(tmp_path)
    assert out["correct"] is False, out["checks"]


def test_control_reads_not_correct():
    """The reference with bfloat16 pair arithmetic, put in the
    program's place, fails the cell's limit (at this size too)."""
    cell = _cell()
    gaps = chip_control.control_ref_bf16(
        cell, SEED, 2 * cell["block_steps"], sim_overrides=TINY)
    assert gaps["state_gap"] > cell["limits"]["state_gap"]


def test_one_shell_reads_as_two(monkeypatch):
    """Bodies beyond the first shell move on straight lines; making the
    second shell active too (every body near an active one's path)
    changes no sampled body's final state."""
    x0, v0, m = _ics(N)
    sim = _config()["simulation"]
    idx = ref.sample_targets(SEED, N, 16)
    kw = dict(dt=sim["dt"], steps=40, g=sim["g"], eps=sim["eps"],
              cutoff=sim["cutoff"], nlist_rcut=sim["nlist_rcut"])
    one = cref.integrate_targets(x0, v0, m, idx, **kw)
    shell = cref.active_set
    monkeypatch.setattr(cref, "active_set", lambda x, v, i, **k: shell(
        x, v, shell(x, v, i, **k), **k))
    two = cref.integrate_targets(x0, v0, m, idx, **kw)
    assert all(np.array_equal(a, b) for a, b in zip(one, two))


def test_reference_needs_a_softening():
    with pytest.raises(ValueError):
        cref.reach_of(np.ones(4), g=1.0, eps=0.0, rcut=1.0, window_s=1.0)
