"""K-way partitioner: frontier optimization on the simplex and elastic fleets."""
import jax.numpy as jnp
import numpy as np

from repro import sched
from repro.core.frontier import UnitParams, mean_var_completion


def test_faster_worker_gets_more_work():
    p = UnitParams.of([10.0, 30.0], [1.0, 1.0])
    fr, stats = sched.solve_fractions(p, objective=sched.Objective.mean(), steps=300)
    assert float(fr[0]) > float(fr[1])  # unit 0 is 3x faster
    # beats equal split
    e_eq, _ = mean_var_completion(jnp.asarray([0.5, 0.5]), p)
    assert float(stats.e_t) < float(e_eq)


def test_optimizer_near_closed_form_linear_case():
    """With alpha=beta=1 and zero variance-aversion the optimal split for
    K linear units equalizes f_k * mu_k -> f_k proportional to 1/mu_k."""
    mus = [8.0, 16.0, 32.0]
    p = UnitParams.of(mus, [0.01, 0.01, 0.01])
    fr, _ = sched.solve_fractions(p, objective=sched.Objective.mean(), steps=300)
    inv = np.array([1 / m for m in mus])
    np.testing.assert_allclose(np.asarray(fr), inv / inv.sum(), atol=0.02)


def test_elastic_add_remove():
    part = sched.Scheduler(4, seed=1)
    part.remove_workers(np.array([False, True, False, False]))
    assert part.num_workers == 3
    part.add_workers(2)
    assert part.num_workers == 5
    fr, _, _ = part.propose_fractions()
    assert len(fr) == 5 and abs(fr.sum() - 1.0) < 1e-5
