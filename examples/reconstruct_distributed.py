"""End-to-end driver: DISTRIBUTED iFDK reconstruction with fault injection.

Runs the paper's full pipeline on a virtual 8-device mesh (2 pods x 2 data x
2 model): per-rank load+filter, column AllGather, slab back-projection, row
reduce-scatter — then demonstrates checkpoint/restart by killing the job
mid-stream and resuming.

    PYTHONPATH=src python examples/reconstruct_distributed.py

It needs 8 devices: on the CPU the XLA_FLAGS line below makes 8 virtual
ones. On a TPU host with fewer chips (a 4-chip v5e host) it does not run;
`chip_smoke.py --chips 4` is the multi-chip check there.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.distributed import input_sharding
from repro.core.fdk import fdk_scale, gups
from repro.core.geometry import default_geometry
from repro.core.phantom import forward_project
from repro.core.plan import ReconstructionPlan, plan_from_spec
from repro.parallel.mesh import make_mesh
from repro.planner import search_plans
from repro.runtime import ResumableReconstruction, StragglerMonitor


def main():
    g = default_geometry(32, n_proj=64)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    print(f"mesh: {dict(mesh.shape)}  problem: "
          f"{g.n_u}^2 x {g.n_proj} -> {g.n_x}^3")

    proj = forward_project(g)
    # Auto-planning: the planner (repro/planner) prices the schedule x
    # reduce x precision cross-product with the paper's Eq. 8-19 model,
    # prunes what cannot fit in HBM, and hands back the best feasible plan.
    for i, p in enumerate(search_plans(g, mesh, top_k=3)):
        print(f"  candidate {i}: {p.spec()}  "
              f"t_run={p.breakdown.t_runtime:.3f}s  "
              f"footprint={p.footprint.total / 2**20:.0f}MiB")
    plan = plan_from_spec(g, "auto,precision=fp32", mesh=mesh)
    print(f"auto plan: {plan.describe()}")
    fn = plan.build()
    out = fn(jax.device_put(proj, input_sharding(mesh)))
    vol = np.array(out).reshape(g.n_x, g.n_y, g.n_z)
    ref = np.array(ReconstructionPlan(geometry=g).build()(proj))
    print(f"distributed vs single-device max err: "
          f"{np.max(np.abs(vol - ref)):.2e}")

    # --- fault-tolerant micro-batched reconstruction -----------------------
    import time
    from repro.core.backprojection import backproject_factorized
    from repro.core.filtering import filter_projections
    from repro.core.geometry import projection_matrices

    pm = jnp.asarray(projection_matrices(g))
    q = filter_projections(g, proj)
    nb, bsz = 8, g.n_proj // 8

    def step_fn(acc, bi):
        lo = bi * bsz
        return acc + backproject_factorized(
            pm[lo:lo + bsz], q[lo:lo + bsz], g.n_x, g.n_y, g.n_z
        )

    with tempfile.TemporaryDirectory() as ckdir:
        mgr = CheckpointManager(ckdir)
        r = ResumableReconstruction(step_fn, jnp.zeros(g.volume_shape()),
                                    nb, mgr, checkpoint_every=2)
        try:
            r.run(fail_at=5)
        except RuntimeError as e:
            print(f"injected fault: {e} -> restarting from checkpoint")
        r2 = ResumableReconstruction(step_fn, jnp.zeros(g.volume_shape()),
                                     nb, mgr, checkpoint_every=2)
        r2.resume()
        print(f"resumed at micro-batch {r2.state.cursor}/{nb}")
        t0 = time.perf_counter()
        acc = r2.run()
        dt = time.perf_counter() - t0
        vol2 = np.array(acc) * fdk_scale(g)
        print(f"recovered reconstruction max err: "
              f"{np.max(np.abs(vol2 - ref)):.2e} "
              f"({gups(g, dt):.3f} GUPS for the resumed half)")

    mon = StragglerMonitor()
    for t in (1.0, 1.02, 0.98, 3.0, 1.01):
        mon.record(t)
    print(f"straggler monitor flagged steps: {mon.flagged}; "
          f"rebalance hint: {mon.rebalance_hint(nb, 8)}")


if __name__ == "__main__":
    main()
