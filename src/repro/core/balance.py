"""Algorithm 2: BalancePowerCap -- powercap-based entitlement balancing.

Progressive filling toward max-min fairness (paper ref [24]): repeatedly move
capacity (Watts) from the host with the lowest normalized entitlement to the
host with the highest, until the cluster imbalance metric (stddev of N_h)
drops below threshold or physical cap ranges bind.  A cap write costs <1 ms;
a vMotion costs seconds of copying plus CPU overhead on both hosts -- so this
runs *before* DRS's migration-based balancer and usually replaces it.

Safety invariants maintained per transfer:
  * donor capacity never drops below its VMs' reservations (admission),
  * recipient capacity never exceeds its physical peak,
  * the sum of caps never exceeds the cluster budget (transfers conserve it).

The loop itself is the pure-array kernel ``repro.core.kernels.balance_caps``,
shared with the jit-compiled batched sweep engine (``repro.sim.batch``);
this module is the object-plane adapter: snapshot -> columns -> kernel ->
snapshot, placements frozen for the loop's duration so the struct-of-arrays
view is built once and only the ``power_cap`` column evolves.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import backend as backend_mod
from repro.backend import NUMPY
from repro.core import kernels
from repro.drs import actions as act
from repro.drs.snapshot import ClusterSnapshot


@dataclasses.dataclass
class BalanceConfig:
    # Cap writes cost <1 ms, so powercap balancing can afford a much tighter
    # target than migration balancing (saturated hosts pin N_h at 1.0, so a
    # loose threshold would strand them short of their demand).
    imbalance_threshold: float = 0.01
    max_iters: int = 64
    min_transfer: float = 1e-3      # capacity units; below this we stop

    def params(self) -> kernels.BalanceParams:
        return kernels.BalanceParams(
            imbalance_threshold=self.imbalance_threshold,
            max_iters=self.max_iters,
            min_transfer=self.min_transfer)


def balance_power_cap(snapshot: ClusterSnapshot,
                      config: BalanceConfig | None = None
                      ) -> tuple[ClusterSnapshot, bool]:
    """Returns (what-if snapshot with rebalanced caps, did-anything flag)."""
    config = config or BalanceConfig()
    f = snapshot.clone()
    av = f.as_arrays()
    if int(av.host_on.sum()) < 2:
        # Nothing to balance between: skip the kernel (and its initial
        # entitlement waterfill) entirely.
        return f, False
    hosts = av.host_cols()
    floors, ceils, weights, seg = av.waterfill_cols()

    if backend_mod.pallas_enabled():
        # Executor lift: rebuild the ragged VM lists as the dense slot
        # layout and run the fused Pallas loop on the JAX plane.  Same
        # protocol, same per-host waterfill math; entitlements differ from
        # the segment form only by reduction-order rounding.
        new_caps, did_balance = _balance_caps_pallas(
            f, av, hosts, floors, ceils, weights,
            snapshot.power_budget, config)
    else:
        def ents_at(caps):
            return kernels.entitlement_sums(NUMPY, hosts, caps,
                                            floors[None], ceils[None],
                                            weights[None], seg[None])

        caps, did, _ = kernels.balance_caps(
            NUMPY, hosts, av.power_cap[None].copy(), ents_at,
            av.cpu_reserved()[None],
            np.asarray([snapshot.power_budget]),
            np.asarray([True]),
            config.params())
        new_caps, did_balance = caps[0], bool(did[0])
    tree = snapshot.effective_tree()
    if tree is not None:
        # Hierarchical budgets: transfers conserve the cluster total but
        # may still push a row past its limit; scale the balanced caps
        # back under every node, protecting the reserved floors.
        floor_caps = kernels.reserved_floor_caps(
            np, hosts, av.cpu_reserved()[None])[0]
        new_caps = kernels.tree_project_caps(
            np, tree.cols(), av.host_on[None], new_caps[None],
            floor_caps[None])[0]
    av.write_caps(f, new_caps)
    if did_balance:
        f.validate()
    return f, did_balance


def _balance_caps_pallas(snapshot, av, hosts, floors, ceils, weights,
                         budget: float, config: BalanceConfig):
    """Run the balance loop through the fused Pallas kernel (``S == 1``).

    Packs the active VMs into the dense ``(1, H, J)`` slot layout (the same
    assignment the batched engine uses, so slot-ordered tie-breaks agree)
    and hands ``kernels.balance_caps`` the ``DenseCols`` bundle; the
    ``jax-pallas`` dispatch takes it from there.  Returns
    ``(caps (H,), did)`` on the NumPy plane.
    """
    import jax.numpy as jnp
    import jax

    from repro.drs.arrays import dense_slot_assignment
    from repro.drs.entitlement import waterfill_dense

    H = av.n_hosts
    _, order, hj, slot, counts = dense_slot_assignment(snapshot, H)
    J = max(int(counts.max()) if counts.size else 0, 1)
    fl = np.zeros((1, H, J))
    ce = np.zeros((1, H, J))
    w = np.full((1, H, J), 1e-12)
    act = np.zeros((1, H, J), dtype=bool)
    fl[0, hj, slot] = floors[order]
    ce[0, hj, slot] = ceils[order]
    w[0, hj, slot] = weights[order]
    act[0, hj, slot] = True

    be = backend_mod.jax_backend()
    with jax.enable_x64(True):
        hosts_j = kernels.HostCols(*(jnp.asarray(c) for c in hosts))
        dense = kernels.DenseCols(jnp.asarray(fl), jnp.asarray(ce),
                                  jnp.asarray(w), jnp.asarray(act))

        def ents_at(c):
            managed = kernels.managed_capacity(jnp, hosts_j, c)
            alloc = waterfill_dense(jnp, be.fori, managed, dense.floors,
                                    dense.ceils, dense.weights,
                                    active=dense.active)
            return jnp.sum(alloc, axis=-1)

        caps, did, _ = kernels.balance_caps(
            be, hosts_j, jnp.asarray(av.power_cap[None]), ents_at,
            jnp.asarray(av.cpu_reserved()[None]),
            jnp.asarray([budget]), jnp.asarray([True]),
            config.params(), dense=dense)
        return np.asarray(caps)[0], bool(np.asarray(did)[0])


def emit_actions(before: ClusterSnapshot, after: ClusterSnapshot
                 ) -> list[act.Action]:
    """Cap-decrease actions are prerequisites of the increases they fund."""
    new_caps = {h.host_id: h.power_cap for h in after.powered_on_hosts()}
    return act.order_cap_changes(before, new_caps, reason="powercap-balance")
