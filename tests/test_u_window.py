"""The back-projection kernel's u-step window (kernels/backproject/kernel.py).

Each output tile contracts its hat over K detector columns from a start
computed per (tile, view), not over all N_u. These tests hold the window
to two things:

  * equivalence: the windowed kernel equals the kernel at u_window=None
    (all N_u columns) within f32 rounding, in the Pallas interpreter, on
    small C-arm-like geometries whose detector is much wider than one
    tile's shadow;
  * the bound: on the host, every in-detector tap (u0 = floor(u) and
    u0 + 1) of every voxel column lies in its tile's window, for every view
    and tile, at the benchmark's RabbitCT geometry and at random ones.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import shift_pmats_i
from repro.core.geometry import CBCTGeometry, projection_matrices
from repro.core.plan import ReconstructionPlan, shift_pmats_j
from repro.kernels.backproject import tune
from repro.kernels.backproject.kernel import (
    backproject_dual_pallas, sublane_align, u_spans, window_starts,
    window_width,
)
from repro.kernels.backproject.ops import backproject_pallas

RABBITCT = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                        "rabbitct512.json")


def _carm(n_u=256, px_per_voxel=12.0, n=16, n_proj=6, n_v=16):
    """A 16^3 volume under a C-arm-like cone (magnification 2) whose
    detector is n_u columns of 1/px_per_voxel voxel shadows: at 12 per
    voxel the volume's shadow overhangs a 256-column detector on both
    edges."""
    d_u = (2.0 / n) * 2.0 / px_per_voxel
    return CBCTGeometry(n_proj=n_proj, n_u=n_u, n_v=n_v, d_u=d_u,
                        d_v=3.0 / n_v, d=4.0, dsd=8.0, n_x=n, n_y=n, n_z=n,
                        d_x=2 / n, d_y=2 / n, d_z=2 / n)


def _rabbitct() -> CBCTGeometry:
    with open(RABBITCT) as f:
        return CBCTGeometry(**json.load(f)["geometry"])


def _views(g, dtype, seed=0):
    """Random (Np, N_v, N_u) views in the wire dtype: the window must not
    depend on what the projections hold."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((g.n_proj, g.n_v, g.n_u)).astype(np.float32)
    return jnp.asarray(q).astype(dtype)


# (case, detector columns, tile (bi, bj, bs), wire dtype, K below N_u?)
CASES = [
    ("edges-f32", 256, (2, 8, 2), jnp.float32, True),
    ("edges-bf16", 256, (2, 8, 2), jnp.bfloat16, True),
    ("fp8", 384, (2, 8, 2), jnp.float8_e4m3fn, True),
    ("view-padding", 256, (2, 8, 4), jnp.float32, True),
    ("slab-shift", 256, (2, 8, 1), jnp.float32, True),
    ("chunk-shift", 256, (2, 8, 1), jnp.float32, True),
    ("k-reaches-nu", 256, (8, 16, 2), jnp.float32, False),
    ("vmap-batched", 256, (2, 8, 2), jnp.bfloat16, True),
]


@pytest.mark.parametrize("case,n_u,blocks,dtype,windowed", CASES,
                         ids=[c[0] for c in CASES])
def test_windowed_kernel_matches_full_contraction(case, n_u, blocks, dtype,
                                                  windowed):
    g = _carm(n_u=n_u)
    bi, bj, bs = blocks
    pm = projection_matrices(g)
    kw = tune.tile_window(pm, (g.n_x, g.n_y), bi, bj, g.n_u, dtype)
    assert (kw < g.n_u) == windowed, kw
    q = _views(g, dtype)
    pmj = jnp.asarray(pm)
    nx, ny = g.n_x, g.n_y
    if case == "slab-shift":     # the second of two x-slabs
        nx = g.n_x // 2
        pmj = shift_pmats_i(pmj, jnp.float32(nx))
    elif case == "chunk-shift":  # the second of two y-chunks
        ny = g.n_y // 2
        pmj = shift_pmats_j(pmj, jnp.float32(ny))

    def bp(proj, window):
        return backproject_pallas(pmj, proj, nx, ny, g.n_z, bi=bi, bj=bj,
                                  bs=bs, u_window=window)

    if case == "vmap-batched":   # the batched engine vmaps the kernel
        batch = jnp.stack([q, _views(g, dtype, seed=1)])
        got = jax.vmap(lambda p: bp(p, kw))(batch)
        want = jnp.stack([bp(batch[0], None), bp(batch[1], None)])
    else:
        got, want = bp(q, kw), bp(q, None)
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6 * scale)


def test_batched_engine_runs_the_window():
    """The plan hands its window to every engine; the batched one (a
    vmap of the kernel) still gives each lane the single-scan answer."""
    g = _carm(n_u=256, n_proj=8)
    plan = ReconstructionPlan(geometry=g, impl="kernel", blocks=(2, 8, 2))
    assert plan.resolved_u_window() < g.n_u
    proj = jnp.stack([_views(g, jnp.float32), _views(g, jnp.float32, 1)])
    got = np.asarray(plan.build_batched(2)(proj))
    one = plan.build()
    for b in range(2):
        np.testing.assert_array_equal(got[b], np.asarray(one(proj[b])))


def _assert_taps_in_window(pm, nx, ny, bi, bj, nu, kw, dtype):
    """Every in-detector tap of every voxel column, as the kernel computes
    u (f32, x0 * (1/z)), lies in [start, start + kw) of its tile."""
    pm13 = np.concatenate([np.asarray(pm, np.float32).reshape(-1, 12),
                           np.ones((len(pm), 1), np.float32)], axis=1)
    align = sublane_align(dtype)
    nu_rows = nu if kw == nu else -(-nu // align) * align
    starts = np.asarray(window_starts(jnp.asarray(pm13), nx, ny, bi, bj,
                                      nu_rows, kw, align))
    i = np.arange(nx, dtype=np.float32)[:, None]
    j = np.arange(ny, dtype=np.float32)[None, :]
    for v, p in enumerate(pm13):
        x0 = p[0] * i + p[1] * j + p[3]
        z = p[8] * i + p[9] * j + p[11]
        u0 = np.floor(x0 * (np.float32(1.0) / z)).astype(np.int64)
        s = np.repeat(np.repeat(starts[:, :, v], bi, 0), bj, 1)
        for tap in (u0, u0 + 1):
            on = (tap >= 0) & (tap < nu)
            assert np.all((tap[on] >= s[on]) & (tap[on] < s[on] + kw)), (
                f"view {v}: a tap leaves its tile's window")


def test_window_holds_every_tap_rabbitct():
    """The benchmark's geometry (496 views of 1248 x 960 -> 512^3), at the
    tile the tuner picks for its per-call shape: K is below N_u and every
    tap of every column, view and tile lies in its window."""
    g = _rabbitct()
    plan = ReconstructionPlan(geometry=g, impl="kernel", precision="fp32",
                              schedule="pipelined", n_steps=2)
    bi, bj, _ = plan.resolved_blocks()
    kw = plan.resolved_u_window()
    assert kw < g.n_u
    assert plan.describe()["u_window"] == kw
    pm = projection_matrices(g)
    _assert_taps_in_window(pm, g.n_x, g.n_y, bi, bj, g.n_u, kw, jnp.float32)


@pytest.mark.parametrize("seed", range(6))
def test_window_holds_every_tap_random_geometry(seed):
    """Random cone geometries, tiles, wire dtypes and slab shifts."""
    rng = np.random.default_rng(seed)
    n = int(rng.choice([16, 24, 32]))
    n_u = int(rng.choice([160, 256, 300, 512]))
    d = float(rng.uniform(3.0, 6.0))
    g = CBCTGeometry(n_proj=int(rng.integers(5, 13)), n_u=n_u, n_v=8,
                     d_u=float(rng.uniform(0.004, 0.03)), d_v=0.5,
                     d=d, dsd=float(d * rng.uniform(1.2, 2.5)),
                     n_x=n, n_y=n, n_z=8, d_x=2 / n, d_y=2 / n, d_z=0.25)
    dtype = [jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn][seed % 3]
    bi = int(rng.choice([d_ for d_ in (1, 2, 4, 8) if n % d_ == 0]))
    bj = 8
    pm = projection_matrices(g)
    kw = tune.tile_window(pm, (n, n), bi, bj, n_u, dtype)
    r = 2  # the window holds for each x-slab of a 2-slab split
    for rank in range(r):
        pms = np.asarray(shift_pmats_i(jnp.asarray(pm),
                                       jnp.float32(rank * n // r)))
        _assert_taps_in_window(pms, n // r, n, bi, bj, n_u, kw, dtype)


@pytest.mark.parametrize("seed", range(3))
def test_corner_spans_match_every_voxel(seed):
    """u_spans reads u at tile corners only (Theorem 2); over every voxel
    of every tile the span of floor(u) is the same."""
    rng = np.random.default_rng(100 + seed)
    n = 24
    g = CBCTGeometry(n_proj=7, n_u=300, n_v=8,
                     d_u=float(rng.uniform(0.004, 0.03)), d_v=0.5, d=4.0,
                     dsd=float(rng.uniform(5.0, 10.0)), n_x=n, n_y=n, n_z=8,
                     d_x=2 / n, d_y=2 / n, d_z=0.25)
    pm = projection_matrices(g).astype(np.float32)
    tiles = [(1, 8), (3, 8), (4, 24), (24, 8), (2, 12)]
    i = np.arange(n, dtype=np.float32)[:, None]
    j = np.arange(n, dtype=np.float32)[None, :]
    want = dict.fromkeys(tiles, 0)
    for p in pm:
        u = np.floor((p[0, 0] * i + p[0, 1] * j + p[0, 3])
                     / (p[2, 0] * i + p[2, 1] * j + p[2, 3]))
        for bi, bj in tiles:
            t = u.reshape(n // bi, bi, n // bj, bj)
            span = (t.max(axis=(1, 3)) - t.min(axis=(1, 3))).max()
            want[bi, bj] = max(want[bi, bj], int(span))
    assert u_spans(pm, n, n, tiles) == want


def test_window_width_rule():
    """K covers the taps floor(u_min) .. floor(u_max) + 1, the alignment
    slack and three rounding columns, in whole 128-lane blocks, capped at
    N_u; an unbounded span (a voxel behind the source) keeps N_u."""
    assert window_width(116, 1248, jnp.float32) == 128   # 116 + 12 = 128
    assert window_width(117, 1248, jnp.float32) == 256
    assert window_width(108, 1248, jnp.bfloat16) == 128  # 108 + 20
    assert window_width(109, 1248, jnp.bfloat16) == 256
    assert window_width(92, 1248, jnp.float8_e4m3fn) == 128  # 92 + 36
    assert window_width(1200, 1248, jnp.float32) == 1248
    assert window_width(None, 1248, jnp.float32) == 1248


def test_tuning_key_changed():
    """A pick cached under the pre-window tuning key (the kernel that
    contracted every tile over N_u) is never served again."""
    from repro.kernels.backproject.tune import _FILE_CACHE
    tune.clear_cache()
    old_key = (16, 16, 16, 8, 24, 24, jnp.dtype(jnp.float32).str,
               tune.DEFAULT_VMEM_BUDGET, True, None, None, None, True)
    _FILE_CACHE.put(old_key, {"bi": 16, "bj": 16, "bs": 8, "vmem": 1,
                              "u_window": 24, "elapsed": 1.0})
    cfg = tune.autotune(16, 16, 16, 8, 24, 24, interpret=True,
                        measure=False)
    assert cfg.vmem > 1 and cfg.u_window == 24  # not the planted entry


def test_describe_and_span_report_the_window(monkeypatch):
    from repro import obs
    from repro.core.plan import clear_engine_cache
    g = _carm(n_u=256, n_proj=4)
    plan = ReconstructionPlan(geometry=g, impl="kernel", blocks=(2, 8, 2))
    kw = plan.resolved_u_window()
    d = plan.describe()
    assert d["u_window"] == kw < g.n_u
    assert d["u_window_share"] == pytest.approx(kw / g.n_u)
    assert "u_window" not in ReconstructionPlan(geometry=g).describe()
    clear_engine_cache()
    tracer = obs.Tracer(enabled=True)
    previous = obs.set_tracer(tracer)
    try:
        jax.block_until_ready(plan.build()(_views(g, jnp.float32)))
    finally:
        obs.set_tracer(previous)
    (span,) = tracer.spans("engine.reconstruct")
    assert span["args"]["u_window"] == kw
    assert span["args"]["u_window_share"] == pytest.approx(kw / g.n_u)


def test_kernel_refuses_unaligned_window():
    g = _carm(n_u=256)
    with pytest.raises(ValueError, match="multiple of 16"):
        backproject_dual_pallas(
            jnp.asarray(projection_matrices(g)).reshape(-1, 12),
            jnp.zeros((g.n_proj, g.n_u, g.n_v), jnp.bfloat16),
            g.n_x, g.n_y, g.n_z, bi=2, bj=8, bs=2, interpret=True,
            u_window=136)
