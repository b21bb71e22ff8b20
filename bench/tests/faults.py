"""Faults planted under the timed path, in ``Simulation.run_ensemble``
(every cell's window drives it: the ensembles directly, the service once per
chunk)."""

import jax
import jax.numpy as jnp


def _observe(sim, states):
    return jax.vmap(lambda s: sim.stepper.observables(s, sim.cfg))(states)


def unchanged(res, sim, states):
    """A step that returns its state unchanged."""
    snaps = jnp.repeat(_observe(sim, states)[:, None], res.snapshots.shape[1], axis=1)
    return res._replace(state=states, snapshots=snaps.astype(res.snapshots.dtype))


def half_batch(res, sim, states):
    """Half of the batch left out: its members come back as they went in."""
    n = states.shape[0]
    keep = n - n // 2
    stale = unchanged(res, sim, states)
    return res._replace(
        state=jnp.concatenate([res.state[:keep], stale.state[keep:]]),
        snapshots=jnp.concatenate([res.snapshots[:keep], stale.snapshots[keep:]]),
    )


def altered(res, sim, states):
    """An answer altered where it is produced: member 0's fields doubled."""
    return res._replace(state=res.state.at[0].multiply(2.0),
                        snapshots=res.snapshots.at[0].multiply(2.0))


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "altered": altered}


def plant(monkeypatch, name):
    from repro.pde.solver import Simulation

    real = Simulation.run_ensemble
    fault = FAULTS[name]

    def broken(self, state0_batch, steps, **kw):
        return fault(real(self, state0_batch, steps, **kw), self, state0_batch)

    monkeypatch.setattr(Simulation, "run_ensemble", broken)
