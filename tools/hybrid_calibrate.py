"""Adversarial calibration sweep for the hybrid guarantee (VERDICT r2 #4).

Runs hundreds of seeded geometry x pulse-width x DM x noise draws plus
constructed worst cases (width-1 pulses at band-edge DMs, every pulse
phase mod 8), asserting on EVERY draw that the hybrid's argbest equals
the float64 reference kernel's argbest, and measuring:

* the block-scorer coarse/exact retention (the HYBRID_COARSE_TRUST
  basis) against the analytic per-config bound
  (``certify.coarse_retention``);
* the sliding certificate retention against ``certify.cert_retention``
  and the empirical slack consumed in
  ``cert >= rho * exact - HYBRID_CERT_SLACK``;
* certificate behaviour: noise-only chunks must certify at the
  certifiable floor, pulse-above-floor chunks must never certify.

Usage::

    python tools/hybrid_calibrate.py [--draws 200] [--nchan 128]
        [--nsamp 8192] [--out docs/hybrid_calibration.md]

CPU-friendly (the bounds are plan math, platform-independent); run time
~draws x 1.5 s.  The CI-sized core of this sweep is
``tests/test_certify.py::TestGuaranteeSweep``.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--draws", type=int, default=200)
    p.add_argument("--nchan", type=int, default=128)
    p.add_argument("--nsamp", type=int, default=8192)
    p.add_argument("--dmmin", type=float, default=100.0)
    p.add_argument("--dmmax", type=float, default=200.0)
    p.add_argument("--out", default=None,
                   help="write the markdown report here too")
    opts = p.parse_args(argv)

    # BEFORE JAX initialises a backend: the bounds are plan math and
    # the sweep is CPU-sized, so this tool never takes an accelerator
    os.environ["JAX_PLATFORMS"] = "cpu"

    from pulsarutils_tpu.ops.certify import (
        HYBRID_CERT_SLACK,
        cert_retention,
        certifiable_snr_floor,
        coarse_retention,
    )
    from pulsarutils_tpu.ops.plan import dedispersion_plan
    from pulsarutils_tpu.ops.search import dedispersion_search
    from tests.test_certify import GARGS, inject_pulse, make_noise

    nchan, t = opts.nchan, opts.nsamp
    dms_grid = dedispersion_plan(nchan, opts.dmmin, opts.dmmax, *GARGS)
    rho_b = coarse_retention(nchan, dms_grid, *GARGS, t)
    rho_c = cert_retention(nchan, dms_grid, *GARGS, t)
    floor = certifiable_snr_floor(t, len(dms_grid), rho_c.min())

    rng = np.random.default_rng(42)
    cases = []
    for phase in range(8):  # constructed worst cases
        cases.append((1, opts.dmmin + 0.2 + 0.1 * phase, t // 2 + phase))
        cases.append((1, opts.dmmax - 1.0 + 0.1 * phase, t // 3 + phase))
    while len(cases) < opts.draws:
        cases.append((int(rng.choice([1, 1, 1, 2, 3, 4, 6, 8])),
                      float(rng.uniform(opts.dmmin, opts.dmmax)),
                      int(rng.integers(64, t - 64))))

    block_ratios, cert_ratios, slack_used = [], [], []
    mismatches = 0
    t0 = time.time()
    for i, (width, dm, pos) in enumerate(cases):
        noise = make_noise(nchan, t, 5000 + i)
        sig = inject_pulse(noise, dm, amp=float(rng.uniform(1.5, 5.0)),
                           width=width, pos=pos)
        hyb = dedispersion_search(sig, opts.dmmin, opts.dmmax, *GARGS,
                                  backend="jax", kernel="hybrid")
        ref = dedispersion_search(sig, opts.dmmin, opts.dmmax, *GARGS,
                                  backend="numpy")
        fdm = dedispersion_search(sig, opts.dmmin, opts.dmmax, *GARGS,
                                  backend="jax", kernel="fdmt")
        j = ref.argbest()
        if hyb.argbest() != j:
            mismatches += 1
            print(f"MISMATCH draw {i}: width={width} dm={dm:.2f} pos={pos} "
                  f"hyb={hyb.argbest()} ref={j}", file=sys.stderr)
        s_ref = float(ref["snr"][j])
        # coarse block score of the best row (nearest coarse grid row)
        from pulsarutils_tpu.ops.search import nearest_rows
        jc = nearest_rows(np.asarray(fdm["DM"]), dms_grid[j:j + 1])[0]
        block_ratios.append(float(fdm["snr"][jc]) / s_ref)
        cert_ratios.append(float(hyb["cert"][j]) / s_ref)
        slack_used.append(rho_c[j] * s_ref - float(hyb["cert"][j]))
        if (i + 1) % 25 == 0:
            print(f"... {i + 1}/{len(cases)} draws "
                  f"({time.time() - t0:.0f}s)", file=sys.stderr)

    # certificate behaviour on pure noise
    certified = 0
    n_noise = 20
    for seed in range(n_noise):
        tb = dedispersion_search(make_noise(nchan, t, 9000 + seed),
                                 opts.dmmin, opts.dmmax, *GARGS,
                                 backend="jax", kernel="hybrid",
                                 snr_floor=floor)
        certified += bool(tb.meta["certified"])

    br, cr, su = (np.asarray(x) for x in (block_ratios, cert_ratios,
                                          slack_used))
    report = f"""# Hybrid guarantee calibration (measured)

Config: {nchan} chan x {t} samples, DM {opts.dmmin:.0f}-{opts.dmmax:.0f}
({len(dms_grid)} plan trials), {len(cases)} pulse draws
(widths 1-8, all phases mod 8, band-edge DMs included), seed 42.

| Quantity | Analytic bound | Measured (worst / mean) |
|---|---|---|
| argbest(hybrid) == argbest(float64 reference) | must always hold | {len(cases) - mismatches}/{len(cases)} |
| block coarse/exact retention (HYBRID_COARSE_TRUST basis) | >= {rho_b.min():.3f} | {br.min():.3f} / {br.mean():.3f} |
| sliding cert/exact retention | >= {rho_c.min():.3f} | {cr.min():.3f} / {cr.mean():.3f} |
| cert slack consumed (rho*s - cert; must stay < {HYBRID_CERT_SLACK}) | < {HYBRID_CERT_SLACK} | {su.max():.3f} / {su.mean():.3f} |
| noise chunks certified at floor {floor:.2f} | typical | {certified}/{n_noise} |

Interpretation: the measured worst-case retentions must sit AT OR ABOVE
the analytic per-config bounds (the bounds are worst-phase; random draws
usually do better), and the certificate inequality's consumed slack must
stay below HYBRID_CERT_SLACK = {HYBRID_CERT_SLACK} — otherwise the
bounds are wrong and the sweep fails loudly.
"""
    ok = (mismatches == 0 and br.min() >= rho_b.min() - 1e-9
          and cr.min() >= rho_c.min() - 1e-9
          and su.max() < HYBRID_CERT_SLACK)
    print(report)
    print(f"RESULT: {'PASS' if ok else 'FAIL'} "
          f"({time.time() - t0:.0f}s total)")
    if opts.out:
        with open(opts.out, "w") as f:
            f.write(report)
        print(f"report written to {opts.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
