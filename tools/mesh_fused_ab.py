"""Fused-vs-unfused mesh hybrid A/B (ISSUE 2).

``sharded_hybrid_search`` used to run the coarse FDMT and every rescore
bucket as separate ``shard_map`` dispatches; the fused path collapses a
typical hit chunk's first round to ONE dispatch.  This probe pins the
dispatch/readback counters (platform-independent: each one is a host
sync) and the wall clock (platform-specific) for both routes.

Modes:

* default (virtual CPU mesh): A/B on a (1, 1) mesh and, when 8 devices
  exist, an (8, 1) mesh, plus the single-device hybrid row — the
  protocol behind ``docs/distributed.md``'s fused table.  CPU wall
  clock does not predict TPU wall clock; the dispatch counters transfer
  exactly.
* ``--tpu`` (run on the real chip): min-of-3 after warm-up at
  1,024 x 2^20 on a (1, 1) mesh, fused and unfused, against the
  single-device hybrid.  Not measured on today's code.

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/mesh_fused_ab.py [--out mesh_fused_ab.json]
  python tools/mesh_fused_ab.py --tpu
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

GEOM = (1200.0, 200.0, 0.0005)
DMMIN, DMMAX = 300.0, 400.0


def _bench(fn, repeats=3):
    fn()  # warm/compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.time()
        fn()
        best = min(best, time.time() - t0)
    return best


def _probe(fn):
    """min-of-3 wall + one counted run's budget counters."""
    from pulsarutils_tpu.utils.logging_utils import BudgetAccountant

    wall = _bench(fn)
    acct = BudgetAccountant()
    with acct.chunk("probe"):
        fn()
    counters = dict(acct.chunks[0]["counters"])
    counters.pop("compiles", None)
    counters.pop("compile_s", None)
    return {"wall_s": round(wall, 3), "trips": acct.trips(),
            "counters": counters}


def make_pulse_data(nchan, nsamp, dm=350.0, rng=0):
    """A typical HIT chunk: bright dispersed pulse at DM 350 in
    abs-normal noise (the round-5 probe dispersed pure noise — honest
    for same-work wall clock, but a noise chunk's guarantee loop
    rightly degenerates toward a full sweep, which is the certificate
    fast path's job, not this probe's)."""
    from pulsarutils_tpu.models.simulate import disperse_array

    r = np.random.default_rng(rng)
    data = np.zeros((nchan, nsamp), np.float32)
    data[:, nsamp // 2] = 2.0
    data = np.abs(r.normal(data, 0.4)).astype(np.float32)
    return disperse_array(data, dm, *GEOM[:2], GEOM[2])


def ab_cpu(quick=False, log=print):
    """The committed A/B: fused vs unfused sharded hybrid, dispatch
    counters pinned.  Returns the artifact dict (also used by
    ``bench_suite`` config 8)."""
    import jax

    from pulsarutils_tpu.ops.search import dedispersion_search
    from pulsarutils_tpu.parallel.mesh import make_mesh
    from pulsarutils_tpu.parallel.sharded_fdmt import sharded_hybrid_search

    nchan, nsamp = (64, 1 << 13) if quick else (256, 1 << 16)
    data = make_pulse_data(nchan, nsamp)
    devs = jax.devices()
    log(f"# {len(devs)} devices ({devs[0].platform}), "
        f"{nchan}x{nsamp}, DM {DMMIN}-{DMMAX}")

    def single():
        t = dedispersion_search(data, DMMIN, DMMAX, *GEOM, backend="jax",
                                kernel="hybrid")
        np.asarray(t["snr"][:1])

    out = {
        "mode": f"{devs[0].platform}_mesh_fused_ab",
        "n_devices": len(devs),
        "config": f"{nchan}x{nsamp}, DM {DMMIN}-{DMMAX}, width-1 pulse "
                  f"at DM 350 (a typical hit chunk)",
        "single_device_hybrid": _probe(single),
        "meshes": {},
        "note": "dispatch/readback counters are platform-independent "
                "(each is a host sync with the device); CPU wall clock "
                "is not a TPU prediction — see docs/distributed.md",
    }
    log(f"single-device hybrid: {out['single_device_hybrid']}")

    shapes = [(1, 1)] + ([(len(devs), 1)] if len(devs) > 1 else [])
    for shape in shapes:
        mesh = make_mesh(shape, ("dm", "chan"))
        row = {}
        for label, fused in (("fused", None), ("unfused", False)):
            def run(mesh=mesh, fused=fused):
                t = sharded_hybrid_search(data, DMMIN, DMMAX, *GEOM,
                                          mesh=mesh, fused=fused)
                np.asarray(t["snr"][:1])

            row[label] = _probe(run)
        out["meshes"]["x".join(map(str, shape))] = row
        log(f"mesh {shape}: fused {row['fused']}  "
            f"unfused {row['unfused']}")
    return out


def ab_tpu(log=print):
    """The same A/B on the real chip (min-of-3 warm, bench sizes)."""
    import bench
    from pulsarutils_tpu.ops.search import dedispersion_search
    from pulsarutils_tpu.parallel.mesh import make_mesh
    from pulsarutils_tpu.parallel.sharded_fdmt import sharded_hybrid_search
    from pulsarutils_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    nchan, nsamp = 1024, 1 << 20
    array = bench.make_data(nchan, nsamp)
    dev, up_s = bench.upload(array)
    log(f"# upload {up_s:.1f}s")

    rows = {}

    def plain():
        dedispersion_search(dev, DMMIN, bench.DMMAX, *GEOM, backend="jax",
                            kernel="hybrid")

    rows["single_device_hybrid"] = _probe(plain)
    log(f"hybrid, no mesh:         {rows['single_device_hybrid']}")

    mesh = make_mesh((1, 1), ("dm", "chan"))
    for label, fused in (("mesh_1x1_unfused", False), ("mesh_1x1_fused",
                                                       None)):
        def run(fused=fused):
            sharded_hybrid_search(dev, DMMIN, bench.DMMAX, *GEOM,
                                  mesh=mesh, fused=fused)

        rows[label] = _probe(run)
        log(f"{label}: {rows[label]}")
    base = rows["single_device_hybrid"]["wall_s"]
    return {
        "mode": "tpu_mesh_fused_ab",
        "config": f"{nchan}x{nsamp}, DM {DMMIN}-{bench.DMMAX} "
                  "(min-of-3 warm)",
        **rows,
        "overhead_unfused_s": round(
            rows["mesh_1x1_unfused"]["wall_s"] - base, 3),
        "overhead_fused_s": round(
            rows["mesh_1x1_fused"]["wall_s"] - base, 3),
    }


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--tpu", action="store_true")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--out", help="write the artifact JSON here")
    opts = p.parse_args(argv)

    if not opts.tpu:
        # virtual CPU mesh: both settings must precede backend init
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        result = ab_cpu(quick=opts.quick)
    else:
        result = ab_tpu()

    print(json.dumps(result, indent=2))
    if opts.out:
        with open(opts.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
