"""The benchmark's copies of the projector and of FDK agree with the
program's own references as they stand."""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import benchtiny
from bench.reference import fdk, phantom
from repro.core.geometry import default_geometry
from repro.core.phantom import forward_project
from repro.core.plan import ReconstructionPlan


@pytest.fixture(scope="module")
def scan():
    g = default_geometry(16, n_proj=24)
    return g, dataclasses.asdict(g), np.asarray(forward_project(g))


def test_projector_matches_core_phantom(scan):
    g, geom, want = scan
    got = np.asarray(phantom.project(phantom.SHEPP_LOGAN_3D, geom))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-3 * np.abs(want).max()


def test_fdk_matches_the_programs_reference_impl(scan):
    g, geom, proj = scan
    want = np.asarray(ReconstructionPlan(geometry=g, impl="reference")
                      .build()(jnp.asarray(proj)))
    voxels = np.stack(np.meshgrid(*[np.arange(16)] * 3, indexing="ij"),
                      -1).reshape(-1, 3)
    got = fdk.fdk_voxels(geom, proj, voxels).reshape(want.shape)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_seeded_phantom_is_repeatable_and_moves_with_the_seed():
    big = 2**31 + 12345
    a, b = phantom.seeded_phantom(big), phantom.seeded_phantom(big)
    c = phantom.seeded_phantom(big + 1)
    assert np.array_equal(a, b)
    assert a.shape == c.shape == (2 + phantom.N_INNER, 8)
    assert not np.array_equal(a, c)
    assert np.array_equal(phantom.seeded_phantom(-3),
                          phantom.seeded_phantom(-3))


def test_inner_ellipsoids_stay_inside_the_brain():
    for seed in range(20):
        table = phantom.seeded_phantom(seed)
        reach = (np.linalg.norm(table[2:, 4:7], axis=1)
                 + table[2:, 1:4].max(axis=1))
        assert reach.max() < table[1, 1:4].min()


def test_phantom_fits_the_field_of_view():
    table = phantom.seeded_phantom(2**31 + 5)
    geom = dataclasses.asdict(default_geometry(512, n_proj=720))
    assert np.array_equal(phantom.fit(table, geom), table)
    with open(os.path.join(benchtiny.ROOT, "bench", "configs",
                           "rabbitct512.json")) as f:
        rabbit = json.load(f)["geometry"]
    fitted = phantom.fit(table, rabbit)
    half_fan = np.arctan(1248 * 0.308 / 2 / 1200.0)
    scale = 785.0 * np.sin(half_fan)              # 124.1 mm < 128 mm
    assert np.allclose(fitted[:, 1:7], table[:, 1:7] * scale)
    assert np.array_equal(fitted[:, [0, 7]], table[:, [0, 7]])
