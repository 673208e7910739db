#!/usr/bin/env python3
"""Smoke run of the reconstruction path on a TPU: does it start, and is it right.

    python chip_smoke.py             # one chip (device 0)
    python chip_smoke.py --chips 4   # the 2x2 (data, model) mesh phase only

The scan is a clinical cone-beam CT: 720 projections of 768x768 over 360
degrees (0.5 degree steps) reconstructing a 512^3 f32 volume, i.e.
`default_geometry(512, n_proj=720)`: 1.6 GB of f32 projections, a 0.5 GB
volume. The projections come from the analytic Shepp-Logan projector
(`core/phantom.forward_project`) and are written to a `ProjectionSource` in
a temporary directory; that is set-up and is not timed.

One chip:
  auto     plan_from_spec(g, "auto") built with the source and a VolumeSink:
           read, filter, back-project, write. Cold (compile included) and
           warm time to volume are printed as smoke timings, not a benchmark.
  kernel   the auto plan pinned to impl="kernel" at fp32 and at bf16; the
           compiled Pallas kernel must be in the program.
  service  ReconstructionService(spec="auto") serving 3 scans of the family;
           every ticket must end DONE.
Four chips (--chips 4): the kernel plan on a (data=2, model=2) mesh with
reduce="psum" and reduce="scatter", each compared with the same plan on
device 0 in the same process. No other phase runs.

Every one-chip volume is compared with impl="reference" on the same
projections at the same storage precision (only f32 reassociation differs),
against the fp32 bounds of the precision policy that the test suite holds
every plan to (`Precision("fp32").rmse_tol()` / `.max_tol()`, relative to
the reference's peak). The interior RMSE against the phantom is printed.

The last line of standard output is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
and is printed only when every check passed. Exit status: 0 when all
passed, 1 when a check failed, 2 when there is no TPU, when the Pallas
interpreter is requested, or outside a checkout of the repository.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

N, N_PROJ = 512, 720
N_SCANS = 3
# The reference (paper Alg. 2 in XLA, gather-bound) took 8.04 s per
# projection for the whole 512^3 volume on a v5e — 97 min for the scan — so
# it runs on two x-slabs of this many rows, a quarter and half way in.
REF_SLAB = 4


def _abort(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


class Checks:
    """Named pass/fail records; a phase that raises fails its own check."""

    def __init__(self):
        self.failed: list[str] = []

    def record(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"CHECK {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def phase(self, name: str, fn, *args):
        t0 = time.perf_counter()
        try:
            fn(*args)
        except Exception:  # noqa: BLE001 — report, and go on to the next phase
            traceback.print_exc()
            self.record(f"{name}/ran", False, "raised (traceback on stderr)")
        print(f"phase {name}: {time.perf_counter() - t0:.3f} s wall",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: the 2x2 mesh phase only")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        _abort(f"no src/repro next to {__file__}: run it from a checkout "
               "of the repository")
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from repro.kernels.backproject.kernel import resolve_interpret

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _abort(f"JAX found no TPU (device 0 is {dev.platform!r})")
    if resolve_interpret(None):
        _abort("REPRO_PALLAS_INTERPRET asks for the Pallas interpreter; "
               "the smoke runs the compiled kernel only")
    if len(devices) < args.chips:
        _abort(f"--chips {args.chips} needs {args.chips} devices, "
               f"JAX sees {len(devices)}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)

    checks = Checks()
    scratch = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        smoke = Smoke(N, N_PROJ, scratch, checks)
        if args.chips == 4:
            checks.phase("mesh", smoke.mesh_phase, devices[:4])
        else:
            checks.phase("auto", smoke.auto_phase)
            checks.phase("kernel", smoke.kernel_phase)
            checks.phase("service", smoke.service_phase)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              f"{', '.join(checks.failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


class Smoke:
    """The scan, its stores and the reference volumes shared by the phases."""

    def __init__(self, n: int, n_proj: int, scratch: str, checks: Checks):
        import jax
        import numpy as np

        from repro.core.geometry import default_geometry
        from repro.core.phantom import forward_project, shepp_logan_volume
        from repro.io.streams import ProjectionSource

        self.g = default_geometry(n, n_proj=n_proj)
        self.checks = checks
        # Projections and volumes live in separate directories: the sink
        # never writes next to the source it reads.
        self.in_dir = os.path.join(scratch, "in")
        self.out_dir = os.path.join(scratch, "out")
        os.makedirs(self.out_dir)
        t0 = time.perf_counter()
        proj = jax.block_until_ready(forward_project(self.g))
        self.source = ProjectionSource.write(
            os.path.join(self.in_dir, "projections"), proj)
        self.proj = proj
        m = n // 5
        inner = (slice(m, n - m),) * 3
        self.inner = inner
        self.ref_rows = tuple(r for c in (n // 4, n // 2)
                              for r in range(c, c + REF_SLAB))
        self.phantom = np.asarray(shepp_logan_volume(self.g))[inner]
        self._refs: dict = {}
        print(f"set-up (not timed): {self.g.n_proj} projections of "
              f"{self.g.n_v}x{self.g.n_u} -> {n}^3 in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)

    # -- references and comparison -------------------------------------------

    def reference(self, storage: str):
        """impl="reference" (paper Alg. 2) on the same projections at
        `storage`, on the host, over the x rows `ref_rows`; computed once
        per storage precision. The stages are the engine's own: the ramp
        filter, the storage codec's round trip, Alg. 2 on the x-slab (its
        offset folded into P, as the R x C engine does) and the FDK scale."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.core.backprojection import backproject_reference
        from repro.core.distributed import shift_pmats_i
        from repro.core.fdk import fdk_scale
        from repro.core.filtering import make_filter
        from repro.core.geometry import projection_matrices
        from repro.core.precision import Precision

        if storage not in self._refs:
            t0 = time.perf_counter()
            g = self.g
            q = make_filter(g, out_dtype=jnp.float32)(self.proj)
            data, scales = Precision(storage).codec.encode(q)
            del q
            pm = jnp.asarray(projection_matrices(g))
            slabs = []
            for i0 in self.ref_rows[::REF_SLAB]:
                slab = backproject_reference(
                    shift_pmats_i(pm, float(i0)), data, REF_SLAB, g.n_y,
                    g.n_z, scales=scales)
                slabs.append(np.asarray(
                    jax.block_until_ready(slab * fdk_scale(g))))
            self._refs[storage] = np.concatenate(slabs)
            print(f"reference {storage} (x rows {self.ref_rows}): "
                  f"{time.perf_counter() - t0:.3f} s (compile included)",
                  flush=True)
        return self._refs[storage]

    def compare(self, name: str, vol, want, rows=None) -> bool:
        """Relative RMSE and max error of `vol` (its x rows `rows`, or all)
        against `want`, within the fp32 bounds of the precision policy;
        prints the interior RMSE of the whole volume against the phantom."""
        import numpy as np

        from repro.core.precision import Precision

        fp32 = Precision("fp32")
        full = np.asarray(vol)
        got = full if rows is None else full[np.asarray(rows)]
        if got.shape != want.shape or not np.isfinite(full).all():
            return self.checks.record(
                name, False, f"shape {got.shape} (want {want.shape}) or "
                "non-finite values")
        scale = float(np.max(np.abs(want)))
        diff = np.abs(got - want)
        rel_max = float(diff.max()) / scale
        rel_rmse = float(np.sqrt(np.mean(diff ** 2))) / scale
        phantom_rmse = float(np.sqrt(np.mean(
            (full[self.inner] - self.phantom) ** 2)))
        ok = rel_rmse <= fp32.rmse_tol() and rel_max <= fp32.max_tol()
        return self.checks.record(
            name, ok, f"rel_rmse={rel_rmse:.3e} (<= {fp32.rmse_tol():.0e}) "
            f"rel_max={rel_max:.3e} (<= {fp32.max_tol():.0e}) "
            f"interior_rmse_vs_phantom={phantom_rmse:.5f}")

    def check_compiled(self, name: str, plan, proj) -> bool:
        """The kernel plan's program holds the Mosaic kernel, not the
        interpreter's unrolled body."""
        text = plan.build().__wrapped__.lower(proj).as_text()
        return self.checks.record(f"{name}/compiled_kernel",
                                  "tpu_custom_call" in text)

    # -- phases ----------------------------------------------------------------

    def auto_phase(self):
        import jax
        import numpy as np

        from repro.core.plan import plan_from_spec
        from repro.io.streams import VolumeSink

        plan = plan_from_spec(self.g, "auto")
        self.auto_plan = plan
        print(f"auto plan: {plan.describe()}", flush=True)
        if plan.impl == "kernel":
            self.check_compiled("auto", plan, self.proj)
        sink = VolumeSink(os.path.join(self.out_dir, "auto"))
        run = plan.build(source=self.source, sink=sink)
        t0 = time.perf_counter()
        vol = jax.block_until_ready(run())
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        vol = jax.block_until_ready(run())
        warm = time.perf_counter() - t0
        print(f"smoke timing (not a benchmark): auto plan, time to volume "
              f"with read and write: cold {cold:.3f} s (compile included), "
              f"warm {warm:.3f} s", flush=True)
        vol = np.asarray(vol)
        self.checks.record("auto/sink_roundtrip",
                           np.array_equal(sink.read(), vol))
        storage = plan.resolved_precision().storage
        self.compare(f"auto/{storage}_vs_reference", vol,
                     self.reference(storage), self.ref_rows)

    def kernel_phase(self):
        import jax

        base = getattr(self, "auto_plan", None)
        if base is None:
            from repro.core.plan import plan_from_spec
            base = plan_from_spec(self.g, "auto,impl=kernel")
        for storage in ("fp32", "bf16"):
            plan = dataclasses.replace(base, impl="kernel", precision=storage,
                                       blocks=None)
            print(f"kernel plan {storage}: {plan.describe()}", flush=True)
            self.check_compiled(f"kernel/{storage}", plan, self.proj)
            fn = plan.build()
            t0 = time.perf_counter()
            vol = jax.block_until_ready(fn(self.proj))
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            vol = jax.block_until_ready(fn(self.proj))
            warm = time.perf_counter() - t0
            print(f"smoke timing (not a benchmark): kernel {storage}, time "
                  f"to volume from device-resident projections: cold "
                  f"{cold:.3f} s (compile included), warm {warm:.3f} s",
                  flush=True)
            self.compare(f"kernel/{storage}_vs_reference", vol,
                         self.reference(storage), self.ref_rows)
            del vol

    def service_phase(self):
        from repro.io.streams import VolumeSink
        from repro.service import ReconstructionService
        from repro.service.requests import TicketState

        svc = ReconstructionService(spec="auto")
        try:
            tickets = [
                svc.submit(geometry=self.g, source=self.source,
                           sink=VolumeSink(os.path.join(self.out_dir,
                                                        f"scan{i}")))
                for i in range(N_SCANS)]
            plan = svc.plan_cache.resolve(tickets[0].family)
            print(f"service plan: {plan.describe()}", flush=True)
            storage = plan.resolved_precision().storage
            want = self.reference(storage)
            # The service reads its scans from the store: free the device
            # copy so the batched engine has the chip's memory to itself.
            self.proj.delete()
            t0 = time.perf_counter()
            svc.drain()
            print(f"smoke timing (not a benchmark): service, {N_SCANS} scans "
                  f"drained in {time.perf_counter() - t0:.3f} s (compile "
                  "included)", flush=True)
            for t in tickets:
                if self.checks.record(f"service/{t.scan_id}/done",
                                      t.state is TicketState.DONE,
                                      f"state={t.state.value} error={t.error!r}"):
                    self.compare(f"service/{t.scan_id}/{storage}_vs_reference",
                                 t.result(), want, self.ref_rows)
        finally:
            svc.close()

    def mesh_phase(self, devices):
        import jax
        import numpy as np

        from repro.core.distributed import input_sharding
        from repro.core.plan import ReconstructionPlan
        from repro.parallel.mesh import make_mesh

        one = ReconstructionPlan(geometry=self.g, impl="kernel")
        self.check_compiled("mesh/one_chip", one, self.proj)
        want = np.asarray(jax.block_until_ready(one.build()(self.proj)))
        mesh = make_mesh((2, 2), ("data", "model"),
                         devices=np.asarray(devices))
        proj = jax.device_put(self.proj, input_sharding(mesh))
        for reduce in ("psum", "scatter"):
            plan = dataclasses.replace(one, mesh=mesh, reduce=reduce)
            print(f"mesh plan: {plan.describe()}", flush=True)
            self.check_compiled(f"mesh/{reduce}", plan, proj)
            fn = plan.build()
            t0 = time.perf_counter()
            vol = jax.block_until_ready(fn(proj))
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            vol = jax.block_until_ready(fn(proj))
            warm = time.perf_counter() - t0
            print(f"smoke timing (not a benchmark): 2x2 mesh {reduce}, time "
                  f"to volume from device-resident projections: cold "
                  f"{cold:.3f} s (compile included), warm {warm:.3f} s",
                  flush=True)
            self.compare(f"mesh/{reduce}_vs_one_chip", vol, want)


if __name__ == "__main__":
    sys.exit(main())
