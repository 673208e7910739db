"""Pallas back-projection kernel vs the pure-jnp oracle (ref.py).

Per the deliverable: sweep shapes/dtypes and assert_allclose against the
oracle. interpret=True executes the kernel body on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backprojection import (
    backproject_factorized, backproject_reference, to_dual_slab,
)
from repro.core.filtering import filter_projections
from repro.core.geometry import (
    CBCTGeometry, default_geometry, projection_matrices,
)
from repro.core.phantom import forward_project
from repro.kernels.backproject.kernel import backproject_dual_pallas, vmem_bytes
from repro.kernels.backproject.ops import backproject_mxu, backproject_pallas
from repro.kernels.backproject.ref import backproject_dual_ref


def _case(n, n_proj):
    g = default_geometry(n, n_proj=n_proj)
    pm = jnp.asarray(projection_matrices(g))
    q = filter_projections(g, forward_project(g))
    return g, pm, q


class TestPallasKernel:
    # (12, 6) was (24, 6): same non-power-of-two/odd-batch coverage at an
    # eighth of the voxels — fast-tier diet (DESIGN.md §Test tiers).
    @pytest.mark.parametrize("n,n_proj", [(8, 4), (16, 8), (16, 12), (12, 6)])
    def test_shape_sweep_vs_oracle(self, n, n_proj):
        g, pm, q = _case(n, n_proj)
        want = backproject_dual_ref(pm, jnp.swapaxes(q, -1, -2),
                                    g.n_x, g.n_y, g.n_z)
        got = to_dual_slab(backproject_pallas(pm, q, g.n_x, g.n_y, g.n_z))
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-6)

    # bj is a multiple of 8 or the whole N_y: the (8, 128) output tiling
    @pytest.mark.parametrize("bi,bj,bs", [(4, 8, 2), (8, 8, 4), (16, 16, 12)])
    def test_block_shape_sweep(self, bi, bj, bs):
        g, pm, q = _case(16, 12)
        want = backproject_factorized(pm, q, g.n_x, g.n_y, g.n_z)
        got = backproject_pallas(pm, q, g.n_x, g.n_y, g.n_z,
                                 bi=bi, bj=bj, bs=bs)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-6)

    def test_bf16_projections(self):
        """bf16 input with f32 accumulation stays within bf16 tolerance."""
        g, pm, q = _case(16, 8)
        want = backproject_factorized(pm, q, g.n_x, g.n_y, g.n_z)
        got = backproject_pallas(pm, q.astype(jnp.bfloat16),
                                 g.n_x, g.n_y, g.n_z)
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        assert float(jnp.max(jnp.abs(got - want))) / scale < 0.03

    def test_projection_padding(self):
        """N_p not divisible by the batch block is padded harmlessly."""
        g, pm, q = _case(16, 10)  # 10 % 8 != 0
        want = backproject_factorized(pm, q, g.n_x, g.n_y, g.n_z)
        got = backproject_pallas(pm, q, g.n_x, g.n_y, g.n_z, bs=8)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-6)

    # Detectors narrower than the volume's shadow: taps fall off both edges
    # of the detector line (v in (-1, 0) and past N_v - 1).
    @pytest.mark.parametrize("n_det,half", [(12, 1.6), (20, 1.3)])
    def test_detector_edges_vs_reference(self, n_det, half):
        g = CBCTGeometry(n_proj=8, n_u=n_det, n_v=n_det,
                         d_u=2 * half / n_det, d_v=2 * half / n_det,
                         d=4.0, dsd=8.0, n_x=16, n_y=16, n_z=16,
                         d_x=2 / 16, d_y=2 / 16, d_z=2 / 16)
        pm = jnp.asarray(projection_matrices(g))
        q = filter_projections(g, forward_project(g))
        want = backproject_reference(pm, q, g.n_x, g.n_y, g.n_z)
        got = backproject_pallas(pm, q, g.n_x, g.n_y, g.n_z)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-6)

    def test_vmem_budget_helper(self):
        # a VMEM-conscious config for a 1k detector (bf16 batch of 2, both
        # double-buffered) fits the default 32 MiB budget
        assert vmem_bytes(8, 8, 2, 1024, 1024, 512, jnp.bfloat16) < 32 * 2**20
        # and the helper scales linearly in the batch block
        assert vmem_bytes(8, 8, 4, 64, 64, 32) > vmem_bytes(8, 8, 2, 64, 64, 32)

    def test_kernel_accumulates_over_projection_batches(self):
        """Grid revisiting: two batches must sum, not overwrite."""
        g, pm, q = _case(8, 8)
        got = backproject_pallas(pm, q, g.n_x, g.n_y, g.n_z, bs=4)
        want = backproject_factorized(pm, q, g.n_x, g.n_y, g.n_z)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-5, atol=1e-6)


class TestMXUVariant:
    """Gather-free (relu-hat matmul) formulation — bit-exact semantics."""

    @pytest.mark.parametrize("n,n_proj", [(8, 4), (16, 8)])
    def test_vs_factorized(self, n, n_proj):
        g, pm, q = _case(n, n_proj)
        want = backproject_factorized(pm, q, g.n_x, g.n_y, g.n_z)
        got = backproject_mxu(pm, q, g.n_x, g.n_y, g.n_z)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-4, atol=1e-6)

    def test_boundary_handling_without_masks(self):
        """Out-of-range coordinates get zero weight for free."""
        g, pm, _ = _case(8, 4)
        # projections of ones: center voxels accumulate, far voxels may be 0
        q = jnp.ones(g.proj_shape(), jnp.float32)
        got = backproject_mxu(pm, q, g.n_x, g.n_y, g.n_z)
        want = backproject_factorized(pm, q, g.n_x, g.n_y, g.n_z)
        np.testing.assert_allclose(np.array(got), np.array(want),
                                   rtol=1e-4, atol=1e-5)
