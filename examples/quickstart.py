"""Quickstart: learn two processing units' characteristics from passive
telemetry and pick the frontier-optimal split (the whole paper in ~60 lines).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fit, sweep_two_way, pareto_mask
from repro.core.frontier import UnitParams
from repro.sched import Objective, optimal_two_way_fraction

# ---------------------------------------------------------------------------
# 1. Two heterogeneous processing units (ground truth UNKNOWN to the system).
#    Unit i is slow but steady; unit j is fast but noisy (paper's Fig 1 setup).
# ---------------------------------------------------------------------------
TRUE = dict(i=dict(mu=30.0, sigma=2.0, alpha=0.92, beta=0.85),
            j=dict(mu=20.0, sigma=6.0, alpha=0.88, beta=0.80))

rng = np.random.default_rng(0)
N = 384


def observe(unit, f):
    p = TRUE[unit]
    return np.maximum(
        f ** p["alpha"] * p["mu"] + f ** p["beta"] * p["sigma"] * rng.normal(size=f.shape),
        1e-3,
    )

# Telemetry from ACTUAL workloads — no controlled experiments (paper §1).
f_seen = rng.uniform(0.05, 0.95, N).astype(np.float32)
t_i = observe("i", f_seen).astype(np.float32)
t_j = observe("j", 1.0 - f_seen).astype(np.float32)

# ---------------------------------------------------------------------------
# 2. Gibbs-estimate each unit (Algorithm 1, chained priors).
# ---------------------------------------------------------------------------
st_i, _ = fit(jax.random.PRNGKey(1), jnp.asarray(t_i), jnp.asarray(f_seen),
              batch_size=64, n_iters=15, grid_size=256)
st_j, _ = fit(jax.random.PRNGKey(2), jnp.asarray(t_j), jnp.asarray(1.0 - f_seen),
              batch_size=64, n_iters=15, grid_size=256)

print("learned unit i:", {k: round(float(v), 3) for k, v in
      dict(mu=st_i.mu, sigma=st_i.sigma, alpha=st_i.alpha, beta=st_i.beta).items()})
print("true    unit i:", TRUE["i"])
print("learned unit j:", {k: round(float(v), 3) for k, v in
      dict(mu=st_j.mu, sigma=st_j.sigma, alpha=st_j.alpha, beta=st_j.beta).items()})
print("true    unit j:", TRUE["j"])

# ---------------------------------------------------------------------------
# 3. Frontier: choose f for min expected time / risk-averse / var-budget QoS.
# ---------------------------------------------------------------------------
params = UnitParams.of(
    [float(st_i.mu), float(st_j.mu)], [float(st_i.sigma), float(st_j.sigma)],
    [float(st_i.alpha), float(st_j.alpha)], [float(st_i.beta), float(st_j.beta)],
)
fg, mu_f, var_f = sweep_two_way(params, num_f=101)
mask = np.asarray(pareto_mask(mu_f, var_f))

print("\n  f      mu(f)  var(f)  frontier")
for k in range(0, 101, 10):
    star = "*" if mask[k] else ""
    print(f"  {float(fg[k]):.2f}   {float(mu_f[k]):6.2f} {float(var_f[k]):7.2f}  {star}")

for obj in [Objective.mean(), Objective.mean_var(1.0),
            Objective.variance_budget(6.0)]:
    f_opt, m, v = optimal_two_way_fraction(params, objective=obj)
    print(f"objective={obj.kind:11s} -> f*={float(f_opt):.3f} "
          f"E[t]={float(m):.2f} Var[t]={float(v):.2f}")
