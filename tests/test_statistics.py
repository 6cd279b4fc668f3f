"""Statistical-correctness suite: the properties a replica-exchange
framework exists to deliver, checked against closed-form predictions.

The mechanical suites pin *equivalence* (bitwise exchange decisions,
analytic-vs-autodiff forces); nothing there would catch a sampler that
is consistently wrong.  This suite pins *distributions*, on the exactly
solvable Ornstein-Uhlenbeck ladder (HarmonicEngine), driven end-to-end
through ``run_fused``:

  * per-neighbor-pair swap acceptance matches the analytic prediction
    for two d-dof harmonic replicas (Nadler & Hansmann's acceptance
    optimization target — the quantity ladder design tunes);
  * per-rung sampled variance matches the OU stationary variance
    kB T / k_spring;
  * every replica's assignment chain visits the temperature rungs with
    uniform occupancy (chi-square bound) — the random walk along the
    ladder actually mixes.

All runs are SEEDED and deterministic; marked ``slow`` so CI runs them
in a dedicated job (they cost seconds, not minutes, but dominate the
quick suite's budget).

Analytic acceptance.  With reduced energies u = beta E and
E ~ stationary at the replica's own temperature, beta E ~ Gamma(d/2, 1)
for a d-dimensional harmonic well.  For the neighbor pair (c, c+1) with
beta_c > beta_{c+1} and r = beta_c / beta_{c+1}:

    delta = (beta_c - beta_{c+1}) (E_{c+1} - E_c)
          = (r - 1) b - (1 - 1/r) a,      a, b ~ Gamma(d/2, 1) iid

    P_acc = E[min(1, exp(-delta))]

evaluated here by Gauss-Legendre quadrature of the 2-D integral (exact
to ~1e-10 — "analytic" up to quadrature, with no sampling noise).
Propagation parameters are chosen so one cycle fully re-equilibrates
(gamma * dt * md_steps >> 1): post-swap states relax to stationarity
before the next attempt, which is the regime the iid prediction
describes.
"""
import math

import jax
import numpy as np
import pytest

from repro.config import RepExConfig
from repro.core import REMDDriver
from repro.md import HarmonicEngine
from repro.obs import Telemetry

pytestmark = pytest.mark.slow

KB = 0.0019872041
T_MIN, T_MAX, N_WINDOWS = 250.0, 600.0, 4
K_SPRING = 1.0
N_CYCLES, CHUNK, WARMUP = 6144, 32, 256


def p_acc_analytic(r: float, d: int = 3, n_nodes: int = 400,
                   hi: float = 60.0) -> float:
    """Quadrature evaluation of the harmonic-pair acceptance integral."""
    x, w = np.polynomial.legendre.leggauss(n_nodes)
    t = 0.5 * hi * (x + 1.0)
    wt = 0.5 * hi * w
    k = d / 2
    f = t ** (k - 1) * np.exp(-t) / math.gamma(k)
    a, b = np.meshgrid(t, t, indexing="ij")
    wa, wb = np.meshgrid(wt * f, wt * f, indexing="ij")
    delta = (r - 1.0) * b - (1.0 - 1.0 / r) * a
    return float(np.sum(wa * wb * np.minimum(1.0, np.exp(-delta))))


@pytest.fixture(scope="module")
def harmonic_run():
    """One seeded fused run shared by every check in this module.

    ``run_fused`` records the per-cycle assignment trace in the driver
    history; replica states are harvested at chunk boundaries (32
    cycles apart — far past the OU decorrelation time, so harvested
    samples are independent).

    Exchange statistics are read from the on-device telemetry counters
    (the ``RunReport`` the driver emits) rather than re-derived on the
    host: ``telemetry.reset()`` at the warm-up boundary scopes the
    counters to the production cycles, and the acceptance/occupancy
    checks below become consumers of the exact numbers the telemetry
    subsystem reports — so this suite doubles as an end-to-end accuracy
    pin on the counters themselves (cross-checked against the
    trace-derived values, which must agree exactly)."""
    cfg = RepExConfig(dimensions=(("temperature", N_WINDOWS),),
                      t_min=T_MIN, t_max=T_MAX, md_steps_per_cycle=60,
                      n_cycles=N_CYCLES, seed=1)
    # gamma * dt * md_steps = 15: each cycle fully re-equilibrates
    eng = HarmonicEngine(n_dim=3, k_spring=K_SPRING, dt=0.05, gamma=5.0)
    tel = Telemetry()
    drv = REMDDriver(eng, cfg, telemetry=tel)
    ens = drv.init()
    xs, rungs = [], []
    done = 0
    while done < N_CYCLES:
        ens = drv.run_fused(ens, n_cycles=CHUNK, chunk_cycles=CHUNK)
        done += CHUNK
        if done == WARMUP:
            tel.reset()                       # counters cover WARMUP..N_CYCLES
        if done > WARMUP:
            xs.append(np.asarray(ens.state["x"]))        # (R, 3)
            rungs.append(np.asarray(ens.assignment))     # (R,)
    assignment = np.stack([h["assignment"] for h in drv.history])
    return {
        "assignment": assignment,                        # (C, R)
        "cycles": np.asarray([h["cycle"] for h in drv.history]),
        "xs": np.stack(xs),                              # (S, R, 3)
        "rungs": np.stack(rungs),                        # (S, R)
        "temps": np.geomspace(T_MIN, T_MAX, N_WINDOWS),
        "report": drv.last_report.to_dict(),
    }


def _pair_rates_from_report(report):
    """Per-neighbor-pair (attempt, accept) from the RunReport counters.

    The telemetry rows are indexed (dim, parity, slot); on the 1-D
    ladder slot ``w`` at parity ``p`` is the pair (c, c+1) with
    ``c = 2w + p`` (DEO ordering — pairs listed by ctrl within parity).
    """
    att_rows = np.asarray(report["exchange"]["pair_attempt"])  # (1, 2, W)
    acc_rows = np.asarray(report["exchange"]["pair_accept"])
    att = np.zeros(N_WINDOWS - 1)
    acc = np.zeros(N_WINDOWS - 1)
    for c in range(N_WINDOWS - 1):
        p, w = c % 2, c // 2
        att[c] = att_rows[0, p, w]
        acc[c] = acc_rows[0, p, w]
    return att, acc


def test_pair_acceptance_matches_analytic(harmonic_run):
    """Measured swap rate per neighbor pair vs the Gamma(d/2) integral.

    Swap counts come from the on-device telemetry counters in the
    RunReport (scoped to post-warm-up cycles by the fixture's
    ``reset()``); the assignment trace provides an independent exact
    cross-check — in a DEO sweep ctrl c is touched by exactly one pair,
    so pair (c, c+1) swapped at cycle t iff the replica holding c
    changed.  ~2900 attempts/pair: binomial se ~ 0.009, tolerance
    0.03 ~ 3 sigma + quadrature slack.
    """
    temps = harmonic_run["temps"]
    beta = 1.0 / (KB * temps)
    att, acc = _pair_rates_from_report(harmonic_run["report"])
    assert att.min() > 1000

    # exact cross-check: counters == trace-derived swap counts
    assign = harmonic_run["assignment"]
    cycles = harmonic_run["cycles"]
    inv = np.argsort(assign, axis=1)          # inv[t, c] = holder of c
    att_trace = np.zeros(N_WINDOWS - 1)
    acc_trace = np.zeros(N_WINDOWS - 1)
    for t in range(WARMUP, assign.shape[0]):
        parity = cycles[t] % 2                # 1-D grid: parity = cycle%2
        for c in range(parity, N_WINDOWS - 1, 2):
            att_trace[c] += 1
            acc_trace[c] += inv[t, c] != inv[t - 1, c]
    np.testing.assert_array_equal(att, att_trace)
    np.testing.assert_array_equal(acc, acc_trace)

    for c in range(N_WINDOWS - 1):
        predicted = p_acc_analytic(beta[c] / beta[c + 1])
        measured = acc[c] / att[c]
        assert abs(measured - predicted) < 0.03, (
            f"pair {c}: measured {measured:.4f}, analytic {predicted:.4f}")


def test_pair_acceptance_wide_ladder():
    """Discrimination check at a LOW acceptance rate (temperature ratio
    2: analytic ~0.58, far from both 0 and 1 where errors hide)."""
    cfg = RepExConfig(dimensions=(("temperature", 2),), t_min=300.0,
                      t_max=600.0, md_steps_per_cycle=60,
                      n_cycles=2048, seed=3)
    eng = HarmonicEngine(n_dim=3, k_spring=K_SPRING, dt=0.05, gamma=5.0)
    tel = Telemetry()
    drv = REMDDriver(eng, cfg, telemetry=tel)
    ens, done = drv.init(), 0
    while done < 2048:
        ens = drv.run_fused(ens, n_cycles=64, chunk_cycles=64)
        done += 64
        if done == WARMUP:
            tel.reset()
    rep = drv.last_report.to_dict()
    # 2-window ladder: the only pair (0, 1) is slot 0 of parity 0
    att = np.asarray(rep["exchange"]["pair_attempt"])[0, 0, 0]
    acc = np.asarray(rep["exchange"]["pair_accept"])[0, 0, 0]
    assert att == (2048 - WARMUP + 1) // 2
    measured = acc / att
    predicted = p_acc_analytic(2.0)
    assert 0.4 < predicted < 0.7
    assert abs(measured - predicted) < 0.04, (measured, predicted)


def test_stationary_variance_matches_ou(harmonic_run):
    """Pooled position variance per rung vs kB T / k_spring.

    ~550 scalar samples per rung: se of the variance ratio
    ~ sqrt(2 / n) ~ 6%; tolerance 15% ~ 2.5 sigma."""
    xs, rungs = harmonic_run["xs"], harmonic_run["rungs"]
    temps = harmonic_run["temps"]
    for c in range(N_WINDOWS):
        sel = xs[rungs == c]                  # (n_c, 3)
        assert sel.size > 300
        ratio = sel.var() / (KB * temps[c] / K_SPRING)
        assert abs(ratio - 1.0) < 0.15, (c, ratio)


def test_rung_occupancy_uniform(harmonic_run):
    """Each replica's time at each rung ~ uniform: chi-square per
    replica below the 1e-4 critical value.

    Occupancy counts come from the telemetry accumulator in the
    RunReport (every post-warm-up cycle — no host-side thinning pass).
    Consecutive cycles are correlated with decorrelation time ~ TAU
    cycles, which inflates the chi-square statistic of the FULL counts
    by ~TAU relative to independent draws, so chi2 / TAU is compared to
    the same critical value the old thin-by-TAU test used (equal in
    expectation; a stuck or biased ladder still blows this up by orders
    of magnitude).  The counters are also cross-checked exactly against
    the host-side assignment trace."""
    from scipy import stats
    TAU = 8
    occ = np.asarray(harmonic_run["report"]["exchange"]["occupancy"])

    # exact cross-check: telemetry accumulator == trace-derived counts
    assign = harmonic_run["assignment"]
    full = np.stack([np.bincount(assign[WARMUP:, r], minlength=N_WINDOWS)
                     for r in range(N_WINDOWS)])
    np.testing.assert_array_equal(occ, full)

    n_counted = occ[0].sum()
    crit = stats.chi2.ppf(1.0 - 1e-4, N_WINDOWS - 1)
    expected = n_counted / N_WINDOWS
    for r in range(N_WINDOWS):
        chi2 = float(((occ[r] - expected) ** 2 / expected).sum()) / TAU
        assert chi2 < crit, (r, occ[r].tolist(), chi2, crit)
    # and globally: the POOLED occupancy of every (replica, rung) cell
    chi2 = float(((occ - expected) ** 2 / expected).sum()) / TAU
    assert chi2 < stats.chi2.ppf(1.0 - 1e-4,
                                 N_WINDOWS * (N_WINDOWS - 1))
