"""Backend-neutral pure-array kernels for the CloudPowerCap allocation math.

Every scale-sensitive decision in the manager pipeline -- the Eqs. 1/3/4
Watts<->capacity maps, reserved-floor computation, RedivvyPowerCap's
proportional-share cap redistribution, and BalancePowerCap's progressive
filling -- is expressed here as pure functions over plain column arrays
(caps, demands, reservations), parameterized by a ``repro.backend`` executor:

  * the object plane (``repro.core.balance`` / ``repro.core.redivvy`` via
    ``repro.drs.arrays``) runs them eagerly on NumPy with ``S == 1``;
  * the batched sweep engine (``repro.sim.batch``) runs the *same* functions
    under JAX ``jit``, batched over ``S`` scenario cells inside ``lax.scan``.

All kernels take a leading cell axis: host columns are ``(S, H)``, VM
columns ``(S, V)``, per-cell scalars ``(S,)``.  Padding convention: padded
hosts have ``on == False`` (and a nonzero ``power_peak - power_idle`` range
so the Eq. 3 division stays finite); padded/inactive VMs carry zero
floors/ceilings so they allocate nothing, with ``vm_seg`` pointing at host 0.
"""

from __future__ import annotations

from typing import NamedTuple

from repro import backend as backend_mod
from repro.drs.entitlement import waterfill_core, waterfill_dense

#: Minimum cap delta that counts as a change -- must match the emission
#: threshold in ``repro.drs.actions.order_cap_changes`` so the batched
#: engine's action counting agrees with the object plane's.
CAP_CHANGE_EPS = 1e-9


class HostCols(NamedTuple):
    """Static host columns, ``(S, H)`` each (a pytree, so jit-transparent)."""

    on: object             # bool: powered on
    power_idle: object     # Watts at 0% utilization
    power_peak: object     # Watts at 100% utilization
    capacity_peak: object  # capacity at 100% utilization, uncapped
    hyp_overhead: object   # Eq. 4's C_H


class BalanceParams(NamedTuple):
    """Static configuration of the balance loop (mirrors BalanceConfig)."""

    imbalance_threshold: float = 0.01
    max_iters: int = 64
    min_transfer: float = 1e-3


class DPMParams(NamedTuple):
    """Static DPM thresholds (mirrors ``repro.drs.dpm.DPMConfig``)."""

    high_util: float = 0.81        # power-on trigger
    low_util: float = 0.45         # power-off consideration band
    target_util: float = 0.45      # post-consolidation ceiling on targets
    stable_window_s: float = 300.0 # utilization must be low this long


class MigrationParams(NamedTuple):
    """Static configuration of the migration balancer (mirrors
    ``repro.drs.balancer.BalancerConfig``)."""

    imbalance_threshold: float = 0.05
    max_moves: int = 16
    min_goodness: float = 1e-3
    cost_per_gb: float = 2e-4
    contention_threshold: float = 0.9


class MigrationLimits(NamedTuple):
    """Per-invocation launch gates on manager migrations.

    ``slots_per_host``: a host may be an *endpoint* (source or destination)
    of at most this many migration launches per manager invocation;
    ``bandwidth``: cluster-wide cap on total launches per invocation.
    ``None`` means ungated, ``0`` means no launches at all.  Gated moves
    are simply not emitted -- the manager re-scores them at its next
    invocation, so corrections cascade across rounds instead of bursting.
    Evacuations (DPM consolidation) are exempt: power-off is all-or-nothing
    and already waits for its migrations to drain, so in-flight counts MAY
    exceed ``slots_per_host`` while a host evacuates.
    """

    slots_per_host: int | None = None
    bandwidth: int | None = None

    @property
    def gated(self) -> bool:
        return self.slots_per_host is not None or self.bandwidth is not None


class DenseCols(NamedTuple):
    """Dense-slot VM entitlement columns, ``(S, H, J)`` each.

    Callers that hold their VMs in the dense slot layout can hand these to
    :func:`balance_caps` alongside ``ents_at``; the ``jax-pallas`` executor
    then fuses the per-round waterfill with the balance math in a single
    kernel pass instead of materializing the ``(S, H, J)`` allocation
    between them.  ``active`` is the live-slot mask (stale values in padded
    slots are neutralized inside the primitive); ``iters`` the bisection
    trip count (static).
    """

    floors: object                 # (S, H, J)
    ceils: object                  # (S, H, J)
    weights: object                # (S, H, J)
    active: object                 # (S, H, J) bool
    iters: int = 200


class RulesMeta(NamedTuple):
    """Static shape of a grid's rule set (compile-time loop bounds)."""

    n_groups: int = 0              # merged affinity groups
    n_anti: int = 0                # anti-affinity rules
    n_vmhost: int = 0              # VM-host rules
    max_group_members: int = 0     # largest affinity group
    max_anti_members: int = 0      # total anti-rule members

    @property
    def move_bound(self) -> int:
        """Upper bound on constraint-correction moves per invocation."""
        return (self.n_groups * self.max_group_members + self.n_vmhost
                + self.max_anti_members)

    @property
    def any(self) -> bool:
        return (self.n_groups + self.n_anti + self.n_vmhost) > 0


#: Waterfill trips used by the migration kernels in *every* engine -- the
#: object-plane adapters and the jitted batch program must bisect the same
#: number of times so their entitlement scores (and therefore their greedy
#: argmax decisions) agree bit-for-bit.
MIGRATION_WATERFILL_ITERS = 100


# ------------------------------------------------------------ power model
def capped_capacity(xp, hosts: HostCols, caps):
    """Eq. 3 per host; 0 for powered-off hosts."""
    c = xp.clip(caps, hosts.power_idle, hosts.power_peak)
    frac = (c - hosts.power_idle) / (hosts.power_peak - hosts.power_idle)
    return xp.where(hosts.on, hosts.capacity_peak * frac, 0.0)


def managed_capacity(xp, hosts: HostCols, caps):
    """Eq. 4 per host; 0 for powered-off hosts."""
    return xp.where(
        hosts.on,
        xp.maximum(capped_capacity(xp, hosts, caps) - hosts.hyp_overhead,
                   0.0),
        0.0)


def peak_managed_capacity(xp, hosts: HostCols):
    return xp.maximum(hosts.capacity_peak - hosts.hyp_overhead, 0.0)


def cap_for_managed_capacity(xp, hosts: HostCols, capacities):
    """Inverse of Eq. 4 (vectorized ``HostPowerSpec.cap_for_managed_capacity``)."""
    c = xp.clip(capacities + hosts.hyp_overhead, 0.0, hosts.capacity_peak)
    return hosts.power_idle + (hosts.power_peak - hosts.power_idle) * (
        c / hosts.capacity_peak)


def power_consumed(xp, hosts: HostCols, utilization):
    """Eq. 1: utilization -> consumed Watts (0 when powered off)."""
    u = xp.clip(utilization, 0.0, 1.0)
    return xp.where(hosts.on,
                    hosts.power_idle
                    + (hosts.power_peak - hosts.power_idle) * u,
                    0.0)


def reserved_floor_caps(xp, hosts: HostCols, cpu_reserved):
    """Per-host minimum cap honoring resident reservations (paper Fig. 3
    step 1); never below idle, 0 for powered-off hosts."""
    floor = xp.maximum(cap_for_managed_capacity(xp, hosts, cpu_reserved),
                       hosts.power_idle)
    return xp.where(hosts.on, floor, 0.0)


# ---------------------------------------------------------------- redivvy
def redivvy_caps(xp, on, caps_start, caps_floor):
    """Algorithm 1 (RedivvyPowerCap), conserving form.

    ``caps_start`` are pre-correction caps C_{i,S}; ``caps_floor`` the
    post-correction reservation floors C_{i,F}.  Hosts whose floor grew keep
    it; hosts whose floor shrank surrender exactly the fraction ``r`` of
    their excess that funds the growth and keep the rest.  Powered-off hosts
    keep ``caps_start`` untouched.
    """
    delta = xp.where(on, caps_floor - caps_start, 0.0)
    needed = xp.sum(xp.where(delta > 0.0, delta, 0.0), axis=-1)
    excess = xp.sum(xp.where(delta > 0.0, 0.0, -delta), axis=-1)
    r = xp.minimum(needed / xp.maximum(excess, 1e-300), 1.0)[..., None]
    shrunk = caps_floor + (1.0 - r) * (caps_start - caps_floor)
    new = xp.where(delta > 0.0, caps_floor, shrunk)
    # Corner cases exactly as the object-plane algorithm resolves them:
    # nothing grew -> every host keeps its original cap; growth with no
    # excess -> every host sits at its floor.
    new = xp.where((excess > 0.0)[..., None], new, caps_floor)
    new = xp.where((needed > 0.0)[..., None], new, caps_start)
    return xp.where(on, new, caps_start)


def count_cap_changes(xp, on, before, after):
    """Per-cell count of hosts whose cap change would emit a SetPowerCap
    action (the ``order_cap_changes`` threshold)."""
    changed = on & (xp.abs(after - before) > CAP_CHANGE_EPS)
    return xp.sum(changed, axis=-1)


# ------------------------------------------------------------ budget tree
#
# Hierarchical budgets (host -> rack -> row -> room) arrive flattened as an
# ancestor incidence matrix (see ``repro.core.budget_tree.BudgetTree``), so
# every tree question is a masked segment reduction over the node axis:
# subtree cap-sums are a segment-sum up the tree, per-host slack a masked
# min gather down, and over-limit repair a per-node proportional scale.
# The ops are deliberately pure ``(S, H) x (S, H, N) -> (S, N)`` array math
# so the same source runs eagerly on NumPy (object plane, S == 1) and under
# jit inside the batched engine's ``lax.scan``.

#: A node counts as *binding* for projection only past this overshoot, so
#: conserving kernels whose totals drift by float-summation ULPs (well
#: below 1e-9 at rack scale) pass through bitwise untouched.
TREE_PROJECT_EPS = 1e-9

#: Headroom below this counts a node as *saturated* for evacuation scoping.
TREE_BIND_EPS = 1e-6


class TreeCols(NamedTuple):
    """Budget-tree columns (a pytree, so jit-transparent).

    ``anc[s, h, m]`` -- node ``m`` lies on host ``h``'s root path (ancestor
    incidence, self-inclusive via the host's leaf).  Padded hosts have an
    all-False row; padded nodes an all-False column with ``limit == +inf``
    and ``depth == -1``, so they never constrain anything.
    """

    anc: object      # (S, H, N) bool
    limit: object    # (S, N) Watts
    depth: object    # (S, N) int, root 0 (padding -1)


def tree_anc_at(xp, tree: TreeCols, host):
    """Ancestor row of per-cell host index ``host`` (``(S,) -> (S, N)``)."""
    return xp.take_along_axis(
        tree.anc, host[..., None, None], axis=-2)[..., 0, :]


def tree_node_sums(xp, tree: TreeCols, on, caps):
    """Per-node subtree cap-sum: segment-sum of powered-on caps up the
    tree through the ancestor incidence (``(S, H) -> (S, N)``)."""
    caps_on = xp.where(on, caps, 0.0)
    return xp.sum(xp.where(tree.anc, caps_on[..., None], 0.0), axis=-2)


def tree_headroom(xp, tree: TreeCols, on, caps):
    """Per-node remaining watts under the node limit (may be < 0)."""
    return tree.limit - tree_node_sums(xp, tree, on, caps)


def tree_host_slack(xp, tree: TreeCols, headroom):
    """Per-host effective slack: tightest headroom along the root path
    (gather down; ``+inf`` for hosts outside the tree, i.e. padding)."""
    return xp.min(xp.where(tree.anc, headroom[..., None, :], xp.inf),
                  axis=-1)


def tree_project_caps(xp, tree: TreeCols, on, caps, floors):
    """Scale caps down until every node limit holds, never below floors.

    Each host's cap splits into a protected floor and excess; every node
    whose subtree sum overshoots its limit by more than
    ``TREE_PROJECT_EPS`` computes the proportional excess scale that lands
    it exactly on the limit, and each host applies the tightest scale along
    its root path.  One pass suffices: a node's post-projection sum is at
    most ``node_floor + s_node * node_excess == limit`` because every
    subtree host's scale is <= ``s_node``.  Non-binding nodes (every node,
    for a flat tree inside its budget) leave caps bitwise untouched.

    Precondition: the floors themselves fit under every limit (the
    reserved-floor analogue of ``correct_constraints``); otherwise the
    projection bottoms out at the floors and the engine invariants flag
    the misconfigured tree.
    """
    fl = xp.where(on, xp.minimum(floors, caps), 0.0)
    ex = xp.where(on, caps, 0.0) - fl
    node_fl = xp.sum(xp.where(tree.anc, fl[..., None], 0.0), axis=-2)
    node_ex = xp.sum(xp.where(tree.anc, ex[..., None], 0.0), axis=-2)
    binding = node_fl + node_ex > tree.limit + TREE_PROJECT_EPS
    scale = xp.clip((tree.limit - node_fl) / xp.maximum(node_ex, 1e-300),
                    0.0, 1.0)
    s_node = xp.where(binding, scale, 1.0)
    s_host = xp.min(xp.where(tree.anc, s_node[..., None, :], xp.inf),
                    axis=-1)
    return xp.where(on & (s_host < 1.0), fl + s_host * ex, caps)


def tree_evac_scope(xp, tree: TreeCols, on, caps, victim):
    """Destination scope for evacuating ``victim``: the subtree of its
    deepest *saturated* ancestor (headroom < ``TREE_BIND_EPS``), so the
    freed watts and the displaced demand stay inside the binding domain.
    With no saturated ancestor (always, for a flat tree inside its budget)
    every host is in scope -- the scalar-protocol behavior.
    """
    s, h, _ = tree.anc.shape
    head = tree_headroom(xp, tree, on, caps)
    anc_v = tree_anc_at(xp, tree, victim)                     # (S, N)
    saturated = anc_v & (head < TREE_BIND_EPS)
    key = xp.where(saturated, tree.depth, -1)
    node = xp.argmax(key, axis=-1)                            # deepest
    scope = xp.take_along_axis(
        tree.anc, xp.broadcast_to(node[..., None, None], (s, h, 1)),
        axis=-1)[..., 0]                                      # (S, H)
    return xp.where(xp.any(saturated, axis=-1)[..., None], scope,
                    xp.ones_like(scope))


# ---------------------------------------------------------------- balance
def _masked_std(xp, values, mask, count):
    """Population stddev of ``values`` where ``mask`` (count = mask sum)."""
    safe = xp.maximum(count, 1)
    mean = xp.sum(values * mask, axis=-1) / safe
    var = xp.sum(mask * (values - mean[..., None]) ** 2, axis=-1) / safe
    return xp.sqrt(var)


def entitlement_sums(be, hosts: HostCols, caps, vm_floors, vm_ceils,
                     vm_weights, vm_seg, iters: int = 200):
    """Per-host VM-entitlement sums at the given caps: one lockstep
    waterfill over every (cell, host, VM) at once.

    VM columns are ``(S, V)`` with ``vm_seg`` the resident host index
    (inactive/padded VMs: zero floor/ceiling, seg 0).  Segments are
    flattened to ``S * H`` so a single bisection serves the whole batch.
    """
    xp = be.xp
    s, h = caps.shape
    v = vm_seg.shape[-1]
    offs = xp.arange(s)[:, None] * h
    seg_flat = (vm_seg + offs).reshape(s * v)
    capacity = managed_capacity(xp, hosts, caps)
    alloc = waterfill_core(
        be, capacity.reshape(s * h), vm_floors.reshape(s * v),
        vm_ceils.reshape(s * v), vm_weights.reshape(s * v), seg_flat,
        s * h, iters)
    return be.seg_sum(alloc, seg_flat, s * h).reshape(s, h)


def balance_round(xp, hosts: HostCols, caps, managed, ents, ns, done, did,
                  ents_at, cpu_reserved, budget, n_on, peak_managed,
                  params: BalanceParams):
    """One BalancePowerCap progressive-filling round (the body of the
    :func:`balance_caps` loop, extracted so the fused Pallas kernel executes
    the *same* function on its VMEM blocks -- bit-identity between the lax
    and Pallas executors is by construction, not by parallel maintenance).

    Takes and returns the loop state ``(caps, managed, ents, ns, done,
    did)``; ``ents_at(caps) -> (S, H)`` supplies per-host VM-entitlement
    sums at candidate caps.
    """
    on = hosts.on
    imbalance = _masked_std(xp, ns, on, n_on)
    total_cap = xp.sum(managed * on, axis=-1)
    # Cluster-average normalized entitlement: the water level every
    # host would sit at if capacity were perfectly divisible.
    n_avg = xp.sum(ents * on, axis=-1) / xp.maximum(total_cap, 1e-300)
    halt = ((imbalance <= params.imbalance_threshold)
            | (total_cap <= 0.0) | (n_avg <= 1e-12))

    # Batched progressive filling: every host above the average level
    # is a recipient (bounded by its physical peak), every host below
    # is a donor (bounded by the average level and by its reservations).
    cbar = ents / xp.maximum(n_avg, 1e-300)[..., None]
    recipients = on & (ns > n_avg[..., None])
    donors = on & (ns < n_avg[..., None])
    need = xp.where(
        recipients,
        xp.maximum(xp.minimum(peak_managed, cbar) - managed, 0.0), 0.0)
    avail = xp.where(
        donors,
        xp.maximum(managed - xp.maximum(cbar, cpu_reserved), 0.0), 0.0)
    total_need = xp.sum(need, axis=-1)
    total_avail = xp.sum(avail, axis=-1)
    transfer = xp.minimum(total_need, total_avail)
    # Powercap range exhausted -> DRS migration handles the residue.
    halt = halt | (transfer <= params.min_transfer)

    grow = recipients & (need > 0.0)
    new_caps = xp.where(grow, cap_for_managed_capacity(
        xp, hosts,
        managed + transfer[..., None] * need
        / xp.maximum(total_need, 1e-300)[..., None]), caps)
    shrink = donors & (avail > 0.0)
    new_caps = xp.where(shrink, cap_for_managed_capacity(
        xp, hosts,
        managed - transfer[..., None] * avail
        / xp.maximum(total_avail, 1e-300)[..., None]), new_caps)
    # Watts conservation under heterogeneous specs: trim recipients if
    # the budget would be exceeded (linear maps conserve exactly for
    # homogeneous specs; this is a safety net).
    over = xp.sum(new_caps * on, axis=-1) - budget
    n_rec = xp.sum(recipients, axis=-1)
    trim = (over > 1e-6)[..., None] & recipients
    new_caps = xp.where(
        trim,
        xp.maximum(new_caps
                   - (over / xp.maximum(n_rec, 1))[..., None],
                   hosts.power_idle),
        new_caps)

    new_managed = managed_capacity(xp, hosts, new_caps)
    new_ents = ents_at(new_caps)
    new_ns = xp.where(new_managed > 0.0,
                      new_ents / xp.maximum(new_managed, 1e-300), 0.0)
    # Heterogeneous Watts<->capacity maps (plus the trim above) can make
    # a round non-improving near convergence: skip it and stop rather
    # than oscillate.
    worse = _masked_std(xp, new_ns, on, n_on) > imbalance + 1e-12
    commit = ~done & ~halt & ~worse
    cm = commit[..., None]
    return (xp.where(cm, new_caps, caps),
            xp.where(cm, new_managed, managed),
            xp.where(cm, new_ents, ents),
            xp.where(cm, new_ns, ns),
            done | halt | worse,
            did | commit)


def balance_caps(be, hosts: HostCols, caps, ents_at, cpu_reserved, budget,
                 enabled, params: BalanceParams = BalanceParams(),
                 dense: DenseCols | None = None):
    """Algorithm 2 (BalancePowerCap) as a pure batched loop.

    Progressive filling toward max-min fairness on normalized entitlements
    N_h, moving Watts instead of VMs.  ``ents_at(caps) -> (S, H)`` supplies
    the per-host VM-entitlement sums at candidate caps (the object plane
    injects the segment waterfill :func:`entitlement_sums`; the batched
    engine injects the dense-slot form).  Returns ``(caps, did)`` where
    ``did`` is the per-cell did-anything flag.  Cells with
    ``enabled == False`` or fewer than two powered-on hosts pass through
    unchanged.

    The loop body is shared verbatim between backends: the NumPy driver
    (``S == 1`` in the object-plane manager) early-exits through
    ``be.while_loop`` on concrete booleans; the JAX driver runs the same
    ``while_loop`` under ``jit`` with per-cell ``done`` masking, so
    converged cells freeze while stragglers keep transferring.

    ``dense`` (optional) carries the dense-slot entitlement columns behind
    ``ents_at``; when the ``jax-pallas`` executor is active and the caller
    is on the JAX plane, the whole loop is delegated to the fused Pallas
    driver (one kernel launch per round: the balance math and the waterfill
    at the candidate caps in a single pass over ``(S, H, J)``).
    """
    if (dense is not None and getattr(be, "name", "") != "numpy"
            and backend_mod.pallas_enabled()):
        from repro.kernels.powercap.ops import pallas_balance_caps
        return pallas_balance_caps(hosts, caps, dense, cpu_reserved,
                                   budget, enabled, params)
    xp = be.xp
    on = hosts.on
    n_on = xp.sum(on, axis=-1)
    peak_managed = peak_managed_capacity(xp, hosts)

    managed = managed_capacity(xp, hosts, caps)
    ents = ents_at(caps)
    ns = xp.where(managed > 0.0,
                  ents / xp.maximum(managed, 1e-300), 0.0)
    done0 = ~enabled | (n_on < 2)
    did0 = xp.zeros_like(done0)

    def cond(state):
        caps, managed, ents, ns, done, did, rounds = state
        return (rounds < params.max_iters) & ~xp.all(done)

    def body(state):
        caps, managed, ents, ns, done, did, rounds = state
        out = balance_round(xp, hosts, caps, managed, ents, ns, done, did,
                            ents_at, cpu_reserved, budget, n_on,
                            peak_managed, params)
        return (*out, rounds + 1)

    state = (caps, managed, ents, ns, done0, did0, 0)
    caps, _, _, _, _, did, rounds = be.while_loop(cond, body, state)
    return caps, did, rounds


# -------------------------------------------------- DPM + redistribution
def host_utilizations(xp, hosts: HostCols, caps, eff_demand_h, mem_demand_h,
                      host_mem):
    """Per-host (cpu, mem) utilizations, matching the object plane's
    ``ArrayView.host_cpu_utilization`` / ``host_mem_utilization``: zero for
    powered-off hosts and hosts with no capacity."""
    managed = managed_capacity(xp, hosts, caps)
    cpu = xp.where(managed > 0.0,
                   eff_demand_h / xp.maximum(managed, 1e-300), 0.0)
    ok = hosts.on & (host_mem > 0.0)
    mem = xp.where(ok, mem_demand_h / xp.maximum(host_mem, 1e-300), 0.0)
    return cpu, mem


def dpm_hot_mask(xp, on, cpu_util, mem_util, high_util):
    """DPM power-on trigger: powered-on hosts running hot on CPU or memory."""
    return on & ((cpu_util > high_util) | (mem_util > high_util))


def dpm_all_low(xp, on, cpu_util, mem_util, low_util):
    """DPM power-off consideration: every powered-on host below the low band
    on both CPU and memory (per cell; vacuously true with no hosts on)."""
    low = (cpu_util < low_util) & (mem_util < low_util)
    return xp.all(~on | low, axis=-1)


def power_on_funding_caps(be, hosts: HostCols, caps, cand, cpu_util,
                          host_demand, cpu_reserved, budget,
                          high_util: float, tree: TreeCols | None = None):
    """Algorithm 3 power-on funding (paper Fig. 5), batched.

    Funds the cap of candidate host ``cand`` (``(S,)`` index): unallocated
    budget first, then low-utilization donors drained -- lowest utilization
    first -- down to the capacity at which DPM's power-on trigger would fire
    (no oscillation), never below their reservations or idle power.  An
    already-powered-on candidate keeps its allocation; funding only tops it
    up toward peak.

    With a ``tree``, both funding sources additionally respect the budget
    hierarchy: the unallocated pool is clipped to the candidate's tightest
    ancestor headroom, and each donated watt that crosses a limit node on
    its way to the candidate (a node guarding the candidate but not the
    donor) debits that node's headroom and stops when it runs out -- so
    funding can never borrow across a saturated row boundary.  Donors
    inside the candidate's own binding subtree are untouched by the check
    (their watts never cross the boundary).  Without a tree (or with every
    crossed node slack) the result is bitwise the flat-protocol answer.

    Returns ``(new_caps, granted)`` where ``new_caps`` has donors drained
    and the candidate at its granted cap (``min(granted, peak)``), and
    ``granted`` is per cell.  The caller decides feasibility
    (``managed_capacity(granted) > 0``) and emission.
    """
    xp = be.xp
    on = hosts.on
    h_idx = xp.arange(caps.shape[-1])

    def at_cand(col):
        return xp.take_along_axis(col, cand[..., None], axis=-1)[..., 0]

    peak_c = at_cand(hosts.power_peak)
    cand_on = at_cand(on)
    granted0 = xp.where(cand_on, at_cand(caps), 0.0)
    needed = xp.maximum(peak_c - granted0, 0.0)

    # Step 1: unallocated budget (clipped to the candidate's ancestor
    # headroom when a tree is live -- unallocated watts still may not push
    # a row past its limit).
    pool = xp.maximum(budget - xp.sum(xp.where(on, caps, 0.0), axis=-1), 0.0)
    if tree is not None:
        head = tree_headroom(xp, tree, on, caps)
        anc_c = tree_anc_at(xp, tree, cand)                   # (S, N)
        pool_c = xp.min(xp.where(anc_c, head, xp.inf), axis=-1)
        pool = xp.minimum(pool, xp.maximum(pool_c, 0.0))
    take0 = xp.minimum(pool, needed)
    needed = needed - take0

    # Step 2: greedy drain, replicated exactly as a sorted prefix-sum: the
    # k-th coolest donor gives ``clip(needed - taken_so_far, 0, avail_k)``,
    # and donors past the 1e-9 residue give nothing (the object plane's
    # early break).
    is_cand = h_idx == cand[..., None]
    donor = on & ~is_cand & (cpu_util < high_util)
    floor_capacity = xp.maximum(host_demand / high_util, cpu_reserved)
    floor_cap = xp.maximum(
        cap_for_managed_capacity(xp, hosts, floor_capacity),
        hosts.power_idle)
    avail = xp.where(donor, xp.maximum(caps - floor_cap, 0.0), 0.0)
    order = be.argsort(xp.where(donor, cpu_util, xp.inf), axis=-1)
    sorted_avail = xp.take_along_axis(avail, order, axis=-1)
    cum_before = xp.cumsum(sorted_avail, axis=-1) - sorted_avail
    residue = needed[..., None] - cum_before
    take = xp.where(residue > 1e-9,
                    xp.clip(residue, 0.0, sorted_avail), 0.0)
    if tree is not None:
        # Tree pass over the same sorted donors: each donation is capped by
        # the remaining headroom of the nodes it crosses (ancestors of the
        # candidate that are not ancestors of the donor), then debits them.
        # The flat prefix-sum ``take`` stays the base amount, so when no
        # crossed node binds the result is bitwise the flat answer.
        s, n_hosts = caps.shape
        head = head - xp.where(anc_c, take0[..., None], 0.0)
        anc_sorted = xp.take_along_axis(
            tree.anc, order[..., None], axis=-2)              # (S, H, N)

        def drain(k, st):
            head_k, take_k = st
            anc_d = xp.take_along_axis(
                anc_sorted, xp.full((s, 1, 1), k, dtype=order.dtype),
                axis=-2)[..., 0, :]
            crossed = anc_c & ~anc_d                          # (S, N)
            room = xp.min(xp.where(crossed, head_k, xp.inf), axis=-1)
            base = xp.take_along_axis(
                take, xp.full((s, 1), k, dtype=order.dtype), axis=-1)[..., 0]
            t = xp.minimum(base, xp.maximum(room, 0.0))
            head_k = head_k - xp.where(crossed, t[..., None], 0.0)
            take_k = xp.where(h_idx[None, :] == k, t[..., None], take_k)
            return head_k, take_k

        _, take = be.fori(n_hosts, drain, (head, take))
    inverse = be.argsort(order, axis=-1)
    taken = xp.take_along_axis(take, inverse, axis=-1)

    granted = xp.minimum(granted0 + take0 + xp.sum(take, axis=-1), peak_c)
    new_caps = xp.where(is_cand, granted[..., None], caps - taken)
    return new_caps, granted


def power_off_reabsorb_caps(xp, hosts: HostCols, caps, off_idx, budget,
                            tree: TreeCols | None = None):
    """Algorithm 3 power-off reabsorption: the victim's cap returns to the
    pool and is spread over the remaining powered-on hosts proportionally to
    their headroom to peak.  Returns the new cap column (victim at 0).

    With a ``tree``, the grown caps are projected back under every node
    limit (floors at the pre-growth caps, so reabsorption growth -- never
    the surviving allocation -- is what gets scaled back).  For a flat tree
    inside its budget the projection is bitwise a no-op.
    """
    h_idx = xp.arange(caps.shape[-1])
    is_off = h_idx == off_idx[..., None]
    on_after = hosts.on & ~is_off
    caps0 = xp.where(is_off, 0.0, caps)
    pool = xp.maximum(
        budget - xp.sum(xp.where(on_after, caps0, 0.0), axis=-1), 0.0)
    recipients = on_after & (caps0 < hosts.power_peak - 1e-9)
    headroom = xp.where(recipients, hosts.power_peak - caps0, 0.0)
    total_head = xp.sum(headroom, axis=-1)
    grant_total = xp.minimum(pool, total_head)
    grown = xp.minimum(
        caps0 + grant_total[..., None] * headroom
        / xp.maximum(total_head, 1e-300)[..., None],
        hosts.power_peak)
    ok = (total_head > 0.0) & (pool > 0.0)
    result = xp.where(ok[..., None] & recipients, grown, caps0)
    if tree is None:
        return result
    return tree_project_caps(xp, tree, on_after, result, caps0)


def plan_evacuation(be, hosts: HostCols, caps, victim, occ, vm, eff_slot,
                    mem_slot, res_slot, migratable, host_mem,
                    target_util: float, allowed=None, anti=None,
                    scope=None):
    """DPM evacuation planning on the dense slot layout ``(S, H, J)``.

    Replays ``repro.drs.dpm.run_dpm``'s greedy: the victim's VMs leave in
    decreasing current-memory order, ties in ascending global VM index
    (``vm``, ``(S, H, J)`` int: each slot's VM, as ``run_dpm`` sorts the
    inventory), each to the feasible powered-on host with the strictly
    lowest post-move utilization (first host on ties), subject to the
    reservation/memory fit check and the ``target_util`` ceiling on both
    CPU and memory.  All-or-nothing: a single unplaceable or unmigratable
    VM cancels the whole evacuation.

    Returns ``(ok, order, dests, n_evac, slot_pressure, trips)``:
    ``order`` is the per-cell slot visit order, ``dests[:, k]`` the
    destination host of the k-th evacuee (-1 when unused),
    ``slot_pressure`` flags cells where the ``J`` slot bound excluded an
    otherwise-feasible destination (the caller must treat those results as
    invalid -- repack with more slack), and ``trips`` the planner loop's
    trips: ``J``, whatever the victims hold.

    ``allowed`` (``(S, H, J, H)``) and ``anti`` (``(S, H, J, R)``) add rule
    admission to the fit check (the object plane's ``placement.fits``):
    each evacuee may only land on a host its VM-host bitmask allows and
    where no member of any of its anti-affinity rules lives -- counting
    evacuees already placed earlier in the same plan.

    ``scope`` (``(S, H)`` bool) restricts destinations, e.g. to the
    victim's tightest saturated budget-tree subtree
    (:func:`tree_evac_scope`), so displaced demand stays inside the
    binding power domain.
    """
    xp = be.xp
    s, h, j = occ.shape
    on = hosts.on
    h_idx = xp.arange(h)
    s_idx = xp.arange(s)
    managed = managed_capacity(xp, hosts, caps)
    act = occ & on[..., None]
    eff_h = xp.sum(xp.where(act, eff_slot, 0.0), axis=-1)
    mem_h = xp.sum(xp.where(act, mem_slot, 0.0), axis=-1)
    res_h = xp.sum(xp.where(act, res_slot, 0.0), axis=-1)
    cnt_h = xp.sum(occ, axis=-1)
    is_vic = h_idx == victim[..., None]

    def at_victim(col):
        shape = (s, 1) + col.shape[2:]
        idx = xp.broadcast_to(
            victim.reshape((s,) + (1,) * (col.ndim - 1)), shape)
        return xp.take_along_axis(col, idx, axis=1)[:, 0]

    vic_occ = at_victim(occ)
    vic_eff = at_victim(eff_slot)
    vic_mem = at_victim(mem_slot)
    vic_res = at_victim(res_slot)
    vic_mig = at_victim(migratable)
    vic_allowed = at_victim(allowed) if allowed is not None else None
    vic_anti = at_victim(anti) if anti is not None else None
    # Visit order: memory descending, ties by global VM index (empty slots
    # last).  Slot order is not VM order once a first-free placement has
    # landed a VM on the victim, and the greedy's destinations depend on
    # the order it visits equal-memory VMs in.
    by_vm = be.argsort(xp.where(vic_occ, at_victim(vm),
                                xp.iinfo(vm.dtype).max), axis=-1)
    by_mem = be.argsort(xp.take_along_axis(
        xp.where(vic_occ, -vic_mem, xp.inf), by_vm, axis=-1), axis=-1)
    order = xp.take_along_axis(by_vm, by_mem, axis=-1)
    n_vic = xp.sum(vic_occ, axis=-1)

    def order_k(k):
        return xp.take_along_axis(order, xp.full((s, 1), k, order.dtype),
                                  axis=-1)[..., 0]

    def body(k, st):
        eff_h = st["eff_h"]
        mem_h = st["mem_h"]
        res_h = st["res_h"]
        cnt_h = st["cnt_h"]
        valid = k < n_vic
        ko = order_k(k)
        e = vic_eff[s_idx, ko]
        m = vic_mem[s_idx, ko]
        r = vic_res[s_idx, ko]
        mig = vic_mig[s_idx, ko]
        fit = on & ~is_vic
        if scope is not None:
            fit = fit & scope
        fit = fit & (res_h + r[..., None] <= managed + 1e-9)
        fit = fit & (mem_h + m[..., None] <= host_mem + 1e-9)
        util_after = (eff_h + e[..., None]) / xp.maximum(managed, 1e-9)
        mem_after = (mem_h + m[..., None]) / xp.maximum(host_mem, 1e-9)
        fit = fit & (util_after <= target_util) & (mem_after <= target_util)
        if vic_allowed is not None:
            fit = fit & vic_allowed[s_idx, ko]
        a_k = None
        if vic_anti is not None:
            a_k = vic_anti[s_idx, ko]                       # (S, R)
            conflict = xp.matmul(
                (st["anti_cnt"] > 0).astype(xp.float64),    # (S, H, R)
                a_k[..., None].astype(xp.float64))[..., 0] > 0.5
            fit = fit & ~conflict
        slot_ok = cnt_h < j
        pressure = st["pressure"] | xp.any(
            valid[..., None] & fit & ~slot_ok, axis=-1)
        fit = fit & slot_ok
        score = xp.where(fit, util_after, xp.inf)
        best = xp.argmin(score, axis=-1)
        found = xp.isfinite(xp.min(score, axis=-1))
        ok = st["ok"] & (~valid | (mig & found))
        place = valid & ok
        upd = place[..., None] & (h_idx == best[..., None])
        col_k = xp.arange(j) == k
        dests = xp.where(col_k[None, :] & place[..., None],
                         best[..., None], st["dests"])
        out = dict(
            st, dests=dests, ok=ok, pressure=pressure,
            eff_h=eff_h + xp.where(upd, e[..., None], 0.0),
            mem_h=mem_h + xp.where(upd, m[..., None], 0.0),
            res_h=res_h + xp.where(upd, r[..., None], 0.0),
            cnt_h=cnt_h + upd.astype(cnt_h.dtype))
        if a_k is not None:
            out["anti_cnt"] = st["anti_cnt"] + (
                upd[..., None] & a_k[:, None, :]).astype(st["anti_cnt"].dtype)
        return out

    init = {"eff_h": eff_h, "mem_h": mem_h, "res_h": res_h, "cnt_h": cnt_h,
            "dests": xp.full((s, j), -1, dtype=victim.dtype),
            "ok": xp.ones(s, dtype=bool),
            "pressure": xp.zeros(s, dtype=bool)}
    if vic_anti is not None:
        init["anti_cnt"] = xp.sum(
            (anti & act[..., None]).astype(xp.int64), axis=2)   # (S, H, R)
    st = be.fori(j, body, init)
    ok, dests, pressure = st["ok"], st["dests"], st["pressure"]
    n_evac = xp.where(ok, n_vic, 0)
    return ok, order, dests, n_evac, pressure, j


# ------------------------------------------------------- migration layer
#
# The migration decisions (constraint correction and the DRS load-balancing
# hill-climb) operate on the dense slot layout ``(S, H, J)`` -- the same
# layout the batched sweep engine carries through its ``lax.scan`` -- so one
# kernel source serves the object plane (NumPy, S == 1, via
# ``repro.core.migration_core.MigrationCore``) and the jitted grid program.
# Rules arrive pre-scattered into slot space (see
# ``repro.drs.arrays.RulesPack``): ``aff_group`` (S, H, J) int, ``allowed``
# (S, H, J, H) bool, ``anti`` (S, H, J, R) bool.

#: Pad values restored to a slot when its VM moves away.  Engines carrying
#: extra per-slot columns (demand traces, tag masks) extend this mapping.
SLOT_PAD = {
    "occ": False, "reservation": 0.0, "limit": float("inf"),
    "weights": 1e-12, "migratable": True, "cpu": 0.0, "mem": 0.0,
    "aff_group": -1, "allowed": True, "anti": False,
}


def _tail(mask, ndim):
    """Broadcast a leading-axes mask against an array with trailing dims."""
    return mask.reshape(mask.shape + (1,) * (ndim - mask.ndim))


def move_slot(xp, work, do, src, j, dst, pads=SLOT_PAD):
    """Move slot ``(src, j)`` to ``dst``'s first *free* slot, per cell.

    ``work`` maps column names to ``(S, H, J, ...)`` arrays (must contain
    ``"occ"``); every column travels with the VM and the vacated slot is
    restored to its pad value.  Free slots are found by occupancy (argmin
    over the ``occ`` row), so holes left by earlier moves are reused --
    unlike an occupancy-count cursor, this stays correct after arbitrary
    move sequences.  Returns ``(work, moved)`` where ``moved`` masks the
    cells whose destination actually had a free slot (callers gate on the
    admission kernels, which already require one).
    """
    occ = work["occ"]
    s_ax, h_ax, j_ax = occ.shape
    s_idx = xp.arange(s_ax)
    src_c = xp.clip(src, 0, h_ax - 1)
    j_c = xp.clip(j, 0, j_ax - 1)
    dst_c = xp.clip(dst, 0, h_ax - 1)
    occ_d = occ[s_idx, dst_c]                        # (S, J)
    ns = xp.argmin(occ_d, axis=-1)                   # first free (False)
    free = ~occ_d[s_idx, ns]
    moved = do & free
    out = {}
    for key, arr in work.items():
        # Scatter-style two-point update: O(cells * trailing) per move,
        # not O(whole column) -- the trace columns riding along make a
        # full-array rewrite per move the dominant cost otherwise.
        val = arr[s_idx, src_c, j_c]                 # (S, *trailing)
        m = _tail(moved, val.ndim)
        cur_d = arr[s_idx, dst_c, ns]
        new_d = xp.where(m, val, cur_d)
        if hasattr(arr, "at"):                       # JAX: XLA scatter
            arr = arr.at[s_idx, dst_c, ns].set(new_d)
            cur_s = arr[s_idx, src_c, j_c]
            arr = arr.at[s_idx, src_c, j_c].set(
                xp.where(m, pads[key], cur_s))
        else:                                        # NumPy: copy + assign
            arr = arr.copy()
            arr[s_idx, dst_c, ns] = new_d
            arr[s_idx, src_c, j_c] = xp.where(m, pads[key],
                                              arr[s_idx, src_c, j_c])
        out[key] = arr
    return out, moved


def record_move(xp, moves, n_moves, do, src, j, dst):
    """Append ``(src, j, dst)`` at each cell's cursor position where ``do``.

    ``moves`` is ``(S, M, 3)`` int (-1 padded), ``n_moves`` the per-cell
    cursor.  Returns the updated ``(moves, n_moves)``.
    """
    m = moves.shape[1]
    at = xp.arange(m)[None, :] == n_moves[:, None]   # (S, M)
    triple = xp.stack(
        [src, j, dst], axis=-1).astype(moves.dtype)  # (S, 3)
    upd = (at & do[:, None])[..., None]
    moves = xp.where(upd, triple[:, None, :], moves)
    return moves, n_moves + do.astype(n_moves.dtype)


def _gather_slots(xp, col, srcs, js):
    """Gather per-slot columns at K (host, slot) coordinates: (S, K, ...)."""
    s_idx = xp.arange(col.shape[0])[:, None]
    return col[s_idx, srcs, js]


def _affinity_keep_slots(xp, work, act, n_groups: int, srcs, js):
    """Mask of (gathered slot, dest) moves that do not *create* an affinity
    split: a grouped VM may move only where a group mate already lives (or
    if it is its group's only placed member).  ``(S, K, H)``."""
    s_ax, h_ax, _ = act.shape
    k_ax = srcs.shape[-1]
    if "aff_group" not in work or n_groups == 0:
        return xp.ones((s_ax, k_ax, h_ax), dtype=bool)
    grp = work["aff_group"]
    g_idx = xp.arange(n_groups)
    member = (grp[..., None] == g_idx) & act[..., None]   # (S, H, J, G)
    per_host = xp.sum(member, axis=2)                     # (S, H, G)
    total = xp.sum(per_host, axis=1)                      # (S, G)
    g_v = _gather_slots(xp, grp, srcs, js)                # (S, K)
    g_c = xp.clip(g_v, 0, max(n_groups - 1, 0))
    tot_v = xp.take_along_axis(total, g_c, axis=1)        # (S, K)
    host_g = xp.swapaxes(per_host, 1, 2)                  # (S, G, H)
    dest_cnt = xp.take_along_axis(
        host_g, g_c[..., None] * xp.ones((1, 1, h_ax), dtype=g_c.dtype),
        axis=1)                                           # (S, K, H)
    return (g_v[..., None] < 0) | (tot_v[..., None] <= 1) | (dest_cnt > 0)


def _admission_slots(xp, on, work, capacity, host_mem, srcs, js,
                     limits: MigrationLimits | None = None, launch=None):
    """Reservation + memory + rules + free-slot admission for K gathered
    candidate slots against every destination: ``(S, K, H)``.

    Returns ``(fit, fit_unbounded, res_h, mem_h)`` where ``fit_unbounded``
    ignores the free-slot bound (for slot-pressure detection) and
    ``res_h``/``mem_h`` are the per-host rollups at the current placement.
    The capacity column is the *injected* view -- current-cap or
    fundable-cap managed capacity (paper Fig. 3) -- zero for powered-off
    hosts.  Gathering the candidates first keeps every admission pass
    O(K * H) instead of O(V * H) with K = the few slots a phase can
    actually move.

    ``limits``/``launch`` apply the per-invocation launch gates: with
    ``launch = (launch_h, launch_n)`` -- per-host endpoint counts (S, H)
    and the per-cell total (S,) of moves already launched this invocation
    -- a candidate fits only if both its endpoints and the cluster budget
    still have headroom.  The gate lands on the *shared* fit (before the
    free-slot split), so a launch-gated deferral is deliberate policy, not
    slot pressure.
    """
    occ = work["occ"]
    act = occ & on[..., None]
    res_h = xp.sum(xp.where(act, work["reservation"], 0.0), axis=-1)
    mem_h = xp.sum(xp.where(act, work["mem"], 0.0), axis=-1)
    h_ax = occ.shape[1]
    h_idx = xp.arange(h_ax)
    res_v = _gather_slots(xp, work["reservation"], srcs, js)   # (S, K)
    mem_v = _gather_slots(xp, work["mem"], srcs, js)
    fit = on[:, None, :] & (h_idx[None, None, :] != srcs[..., None])
    fit = fit & (res_h[:, None, :] + res_v[..., None]
                 <= capacity[:, None, :] + 1e-9)
    fit = fit & (mem_h[:, None, :] + mem_v[..., None]
                 <= host_mem[:, None, :] + 1e-9)
    if "allowed" in work:
        fit = fit & _gather_slots(xp, work["allowed"], srcs, js)
    if "anti" in work and work["anti"].shape[-1] > 0:
        anti_cnt = xp.sum(work["anti"] & act[..., None], axis=2)  # (S,H,R)
        a_v = _gather_slots(xp, work["anti"], srcs, js)           # (S,K,R)
        conflict = xp.matmul(
            a_v.astype(xp.float64),
            xp.swapaxes((anti_cnt > 0).astype(xp.float64), 1, 2)) > 0.5
        fit = fit & ~conflict
    if limits is not None and limits.gated:
        launch_h, launch_n = launch
        if limits.slots_per_host is not None:
            src_launch = xp.take_along_axis(launch_h, srcs, axis=-1)
            fit = fit & (src_launch < limits.slots_per_host)[..., None]
            fit = fit & (launch_h < limits.slots_per_host)[:, None, :]
        if limits.bandwidth is not None:
            fit = fit & (launch_n < limits.bandwidth)[:, None, None]
    free_slot = xp.any(~occ, axis=-1)                 # (S, H)
    return fit & free_slot[:, None, :], fit, res_h, mem_h


def correct_constraints_slots(be, hosts: HostCols, capacity, work, host_mem,
                              rmeta: RulesMeta, enabled, moves, n_moves,
                              pads=SLOT_PAD,
                              limits: MigrationLimits = MigrationLimits(),
                              launch=None):
    """Constraint correction on the dense slot layout (paper Fig. 1a/3).

    Replays the object plane's correction protocol as bounded array loops:

      1. *Affinity*: per group, gather every member onto one home host,
         all-or-nothing -- the anchor's host (the member with the largest
         reservation) when it can admit the group, else the feasible
         member host with the most free capacity; with no feasible home
         the group stays split (reported upstream).
      2. *VM-host*: each misplaced VM moves to the admissible allowed host
         with the most free capacity.
      3. *Anti-affinity*: while some rule has two members sharing a host,
         move the first surplus member with a feasible destination to the
         admissible host with the most free capacity.

    ``capacity`` is the injected admission view (current-cap managed
    capacity for static policies, fundable capacity during Powercap
    Allocation).  Moves mutate ``work`` in slot space and are appended to
    ``moves``/``n_moves``; returns ``(work, moves, n_moves, pressure,
    launch)`` where ``pressure`` flags cells whose J slot bound blocked an
    otherwise-feasible correction and ``launch = (launch_h, launch_n)``
    carries the per-invocation launch counts (shared with the balancer
    phase) updated for every committed move.  ``limits`` gates launches
    per :class:`MigrationLimits`; affinity gathers stay all-or-nothing --
    a group whose remaining launch headroom cannot cover the whole gather
    is deferred intact to the next invocation.
    """
    xp = be.xp
    on = hosts.on
    s_ax, h_ax, j_ax = work["occ"].shape
    h_idx = xp.arange(h_ax)
    pressure = xp.zeros(s_ax, dtype=bool)
    gated = limits.gated
    if launch is None:
        launch = (xp.zeros((s_ax, h_ax), dtype=n_moves.dtype),
                  xp.zeros(s_ax, dtype=n_moves.dtype))
    launch_h, launch_n = launch

    # ---------------------------------------------------- 1. affinity
    def aff_body(g, state):
        work, moves, n_moves, pressure, launch_h, launch_n = state
        occ = work["occ"]
        act = occ & on[..., None]
        res = work["reservation"]
        memb = act & (work["aff_group"] == g)
        cnt_h = xp.sum(memb, axis=-1)                     # (S, H)
        violated = xp.sum(cnt_h > 0, axis=-1) > 1
        total = xp.sum(cnt_h, axis=-1)

        # Gather-feasibility of EVERY candidate home at once (vectorized
        # over H): a home must host a member, admit every other member's
        # reservation/memory under the injected capacity view, respect
        # each mover's VM-host bitmask and anti-affinity rules, and have
        # the free slots -- the object plane's historical multi-home
        # retry, evaluated in one pass.
        n_movers = total[:, None] - cnt_h                 # (S, H)
        nm_h = xp.sum(memb & ~work["migratable"], axis=-1)
        ok = (xp.sum(nm_h, axis=-1)[:, None] - nm_h) == 0
        if "allowed" in work:
            bad = memb[..., None] & ~work["allowed"]      # (S, H, J, H)
            bad_total = xp.sum(bad, axis=(1, 2))          # (S, H) per home
            bad_on_home = xp.sum(xp.moveaxis(
                xp.diagonal(bad, axis1=1, axis2=3), -1, 1), axis=-1)
            ok = ok & ((bad_total - bad_on_home) == 0)
        if "anti" in work and rmeta.n_anti:
            anti = work["anti"]
            c_rh = xp.sum(anti & act[..., None], axis=2)    # (S, H, R)
            g_rh = xp.sum(anti & memb[..., None], axis=2)   # (S, H, R)
            m_r = xp.sum(g_rh, axis=1)[:, None, :] - g_rh   # movers in r
            ok = ok & xp.all((m_r == 0) | (c_rh + m_r <= 1), axis=-1)
        res_h = xp.sum(xp.where(act, res, 0.0), axis=-1)
        mem_h = xp.sum(xp.where(act, work["mem"], 0.0), axis=-1)
        memb_res_h = xp.sum(xp.where(memb, res, 0.0), axis=-1)
        memb_mem_h = xp.sum(xp.where(memb, work["mem"], 0.0), axis=-1)
        moving_res = xp.sum(memb_res_h, axis=-1)[:, None] - memb_res_h
        moving_mem = xp.sum(memb_mem_h, axis=-1)[:, None] - memb_mem_h
        ok = ok & (res_h + moving_res <= capacity + 1e-9)
        ok = ok & (mem_h + moving_mem <= host_mem + 1e-9)
        ok = ok & (cnt_h > 0)
        if gated:
            # All-or-nothing under the launch gates too: every member
            # host must have endpoint headroom for its departures, the
            # home for all arrivals, and the cluster budget for the whole
            # gather -- otherwise the group defers intact.
            if limits.slots_per_host is not None:
                sl = limits.slots_per_host
                dep_bad = ((cnt_h > 0) & (launch_h + cnt_h > sl)).astype(
                    launch_h.dtype)
                ok = ok & ((xp.sum(dep_bad, axis=-1)[:, None]
                            - dep_bad) == 0)
                ok = ok & (launch_h + n_movers <= sl)
            if limits.bandwidth is not None:
                ok = ok & (launch_n[:, None] + n_movers
                           <= limits.bandwidth)
        free_h = j_ax - xp.sum(occ, axis=-1)
        ok_full = ok & (free_h >= n_movers)
        feasible = xp.any(ok_full, axis=-1)
        pressure = pressure | (enabled & violated & ~feasible
                               & xp.any(ok, axis=-1))

        # Home choice: the anchor's host (the member with the largest
        # reservation -- hardest to move) when feasible, else the feasible
        # member host with the most free admission capacity.
        flat = xp.where(memb, res, -xp.inf).reshape(s_ax, -1)
        anchor_home = xp.argmax(flat, axis=-1) // j_ax    # (S,)
        anchor_ok = xp.take_along_axis(
            ok_full, anchor_home[:, None], axis=-1)[..., 0]
        best_home = xp.argmax(
            xp.where(ok_full, capacity - res_h, -xp.inf), axis=-1)
        home = xp.where(anchor_ok, anchor_home, best_home)
        on_home = h_idx[None, :, None] == home[:, None, None]
        do_g = enabled & violated & feasible

        def mover_body(_, st):
            work, moves, n_moves, launch_h, launch_n = st
            movers_now = ((work["occ"] & on[..., None])
                          & (work["aff_group"] == g) & ~on_home)
            any_m = xp.any(movers_now, axis=(-1, -2))
            first = xp.argmax(movers_now.reshape(s_ax, -1), axis=-1)
            src = first // j_ax
            jj = first % j_ax
            do = do_g & any_m
            work, moved = move_slot(xp, work, do, src, jj, home, pads)
            moves, n_moves = record_move(xp, moves, n_moves, moved, src,
                                         jj, home)
            if gated:
                is_ep = ((h_idx[None, :] == src[:, None])
                         | (h_idx[None, :] == home[:, None]))
                launch_h = launch_h + (moved[:, None] & is_ep).astype(
                    launch_h.dtype)
                launch_n = launch_n + moved.astype(launch_n.dtype)
            return work, moves, n_moves, launch_h, launch_n

        work, moves, n_moves, launch_h, launch_n = be.fori(
            rmeta.max_group_members, mover_body,
            (work, moves, n_moves, launch_h, launch_n))
        return work, moves, n_moves, pressure, launch_h, launch_n

    if rmeta.n_groups:
        work, moves, n_moves, pressure, launch_h, launch_n = be.fori(
            rmeta.n_groups, aff_body,
            (work, moves, n_moves, pressure, launch_h, launch_n))

    # ----------------------------------- shared mover for phases 2 and 3
    def greedy_move(work, moves, n_moves, pressure, launch_h, launch_n,
                    viol, k_bound):
        """Move the first slot in ``viol`` that has a feasible destination
        to the admissible host with the most free capacity.

        Gathers the first ``k_bound`` violating slots per cell (``k_bound``
        is the phase's rule-count bound, so no violator is ever missed) and
        evaluates admission only for those -- O(K * H) per step instead of
        O(V * H)."""
        flat = viol.reshape(s_ax, -1)
        big = h_ax * j_ax
        keys = xp.where(flat, xp.arange(big), big)
        order = be.argsort(keys, axis=-1)[:, :k_bound]     # (S, K)
        kvalid = xp.take_along_axis(keys, order, axis=-1) < big
        srcs = order // j_ax
        js = order % j_ax
        fit, fit_unb, res_h, _ = _admission_slots(
            xp, on, work, capacity, host_mem, srcs, js,
            limits, (launch_h, launch_n))
        mig_v = _gather_slots(xp, work["migratable"], srcs, js)
        ok_v = (kvalid & mig_v)[..., None]
        fit = fit & ok_v
        fit_unb = fit_unb & ok_v
        has_dest = xp.any(fit, axis=-1)                    # (S, K)
        pressure = pressure | (
            enabled & xp.any(xp.any(fit_unb, axis=-1) & ~has_dest,
                             axis=-1))
        found = enabled & xp.any(has_dest, axis=-1)
        first_k = xp.argmax(has_dest, axis=-1)             # (S,)
        s_idx = xp.arange(s_ax)
        src = srcs[s_idx, first_k]
        jj = js[s_idx, first_k]
        free = capacity - res_h                            # (S, H)
        fit_v = fit[s_idx, first_k]                        # (S, H)
        dest = xp.argmax(xp.where(fit_v, free, -xp.inf), axis=-1)
        work, moved = move_slot(xp, work, found, src, jj, dest, pads)
        moves, n_moves = record_move(xp, moves, n_moves, moved, src, jj,
                                     dest)
        if gated:
            is_ep = ((h_idx[None, :] == src[:, None])
                     | (h_idx[None, :] == dest[:, None]))
            launch_h = launch_h + (moved[:, None] & is_ep).astype(
                launch_h.dtype)
            launch_n = launch_n + moved.astype(launch_n.dtype)
        return work, moves, n_moves, pressure, launch_h, launch_n, found

    # ---------------------------------------------------- 2. VM-host
    if rmeta.n_vmhost:
        def vh_viol(work):
            act = work["occ"] & on[..., None]
            allowed_self = xp.moveaxis(
                xp.diagonal(work["allowed"], axis1=1, axis2=3), -1, 1)
            return act & ~allowed_self

        def vh_cond(state):
            work, moves, n_moves, pressure, lh, ln, go, k = state
            return (k < rmeta.n_vmhost) & xp.any(go)

        def vh_body(state):
            work, moves, n_moves, pressure, lh, ln, go, k = state
            work, moves, n_moves, pressure, lh, ln, found = greedy_move(
                work, moves, n_moves, pressure, lh, ln, vh_viol(work),
                rmeta.n_vmhost)
            return work, moves, n_moves, pressure, lh, ln, go & found, k + 1

        go0 = enabled & xp.any(vh_viol(work), axis=(-1, -2))
        work, moves, n_moves, pressure, launch_h, launch_n, _, _ = \
            be.while_loop(vh_cond, vh_body,
                          (work, moves, n_moves, pressure, launch_h,
                           launch_n, go0, 0))

    # ------------------------------------------------ 3. anti-affinity
    if rmeta.n_anti:
        def anti_extra(work):
            act = work["occ"] & on[..., None]
            member = work["anti"] & act[..., None]          # (S, H, J, R)
            cnt = xp.sum(member, axis=2)                    # (S, H, R)
            keeper_j = xp.argmax(member, axis=2)            # (S, H, R)
            j_col = xp.arange(j_ax)[None, None, :, None]
            extra = (member & (j_col != keeper_j[:, :, None, :])
                     & (cnt[:, :, None, :] > 1))
            return xp.any(extra, axis=-1)                   # (S, H, J)

        def anti_cond(state):
            work, moves, n_moves, pressure, lh, ln, go, k = state
            return (k < rmeta.max_anti_members) & xp.any(go)

        def anti_body(state):
            work, moves, n_moves, pressure, lh, ln, go, k = state
            work, moves, n_moves, pressure, lh, ln, found = greedy_move(
                work, moves, n_moves, pressure, lh, ln, anti_extra(work),
                rmeta.max_anti_members)
            return work, moves, n_moves, pressure, lh, ln, go & found, k + 1

        go0 = enabled & xp.any(anti_extra(work), axis=(-1, -2))
        work, moves, n_moves, pressure, launch_h, launch_n, _, _ = \
            be.while_loop(anti_cond, anti_body,
                          (work, moves, n_moves, pressure, launch_h,
                           launch_n, go0, 0))

    return work, moves, n_moves, pressure, (launch_h, launch_n)


def balance_migrations(be, hosts: HostCols, caps, work, host_mem,
                       params: MigrationParams, rmeta: RulesMeta, enabled,
                       moves, n_moves, pads=SLOT_PAD,
                       iters: int = MIGRATION_WATERFILL_ITERS,
                       limits: MigrationLimits = MigrationLimits(),
                       launch=None):
    """DRS's greedy hill-climb balancer (paper Sec. IV-A), batched.

    One move per round: every (migratable slot on the *most-strained*
    donor host, below-average destination) candidate that passes
    reservation + memory + rule admission is scored by the drop in the
    imbalance metric it would produce -- the stddev of normalized
    entitlements with the moved VM carrying its current entitlement -- and
    the argmax wins if its gain beats the risk-cost-benefit floor
    (``min_goodness`` plus the memory-proportional migration cost).
    Rounds continue until the imbalance threshold is met, no candidate
    passes, the true imbalance stops improving, or ``max_moves`` is
    reached.  The contention gate (no strained host => migration cost
    outweighs benefit) is evaluated once on entry, as in the object plane.
    ``limits``/``launch`` apply the per-invocation launch gates shared
    with constraint correction (:class:`MigrationLimits`; a hot host with
    no endpoint headroom simply yields no admissible candidate); returns
    ``(work, moves, n_moves, pressure, launch)``.

    Two deliberate departures from the historical object-plane loop, shared
    by every engine so parity is exact by construction:

      * scoring is a closed-form update of the stddev from per-host
        entitlement sums instead of a full re-waterfill per candidate
        (which made a balancer pass O(V^2 H)); after a committed move only
        the two touched hosts are re-waterfilled (bit-identical, since the
        bisection is per-host independent);
      * candidates come from the hottest host each round -- the greedy
        argmax move relieves it anyway, and the restriction keeps a round
        O(J * H) instead of O(V * H).
    """
    xp = be.xp
    on = hosts.on
    s_ax, h_ax, j_ax = work["occ"].shape
    if launch is None:
        launch = (xp.zeros((s_ax, h_ax), dtype=n_moves.dtype),
                  xp.zeros(s_ax, dtype=n_moves.dtype))
    if params.max_moves <= 0:
        return (work, moves, n_moves, xp.zeros(s_ax, dtype=bool), launch)
    launch_h0, launch_n0 = launch
    n_on = xp.sum(on, axis=-1)
    managed = managed_capacity(xp, hosts, caps)

    def _fill(managed_cols, occ, res, lim, cpu, weights, on_cols):
        act = occ & on_cols[..., None]
        eff = xp.where(act, xp.clip(cpu, res, lim), 0.0)
        floors = xp.where(act, xp.minimum(res, lim), 0.0)
        alloc = waterfill_dense(xp, be.fori, managed_cols, floors, eff,
                                weights, iters, active=act)
        alloc = xp.where(act, alloc, 0.0)
        ents = xp.sum(alloc, axis=-1)
        ns = xp.where(managed_cols > 0.0,
                      ents / xp.maximum(managed_cols, 1e-300), 0.0)
        return act, alloc, ents, ns

    def entitlements(work):
        return _fill(managed, work["occ"], work["reservation"],
                     work["limit"], work["cpu"], work["weights"], on)

    _, alloc0, ents0, ns0 = entitlements(work)
    strained = xp.max(xp.where(on, ns0, 0.0), axis=-1)
    done0 = (~enabled | (n_on < 2)
             | (strained <= params.contention_threshold))
    pressure0 = xp.zeros(s_ax, dtype=bool)
    h_idx = xp.arange(h_ax)

    def _refill_pair(work, alloc, ents, ns, moved, src, dest):
        """Re-waterfill only the two hosts a move touched (the bisection
        is per-host independent, so this is bit-identical to a full
        pass), scattering the refreshed rows back into the carried
        entitlement state."""
        idx2 = xp.stack([src, dest], axis=-1)               # (S, 2)

        def g3(col):                                        # (S,H,J)->(S,2,J)
            return xp.take_along_axis(
                col, idx2[..., None]
                * xp.ones((1, 1, j_ax), dtype=idx2.dtype), axis=1)

        def g2(col):                                        # (S,H) -> (S,2)
            return xp.take_along_axis(col, idx2, axis=-1)

        _, alloc2, ents2, ns2 = _fill(
            g2(managed), g3(work["occ"]), g3(work["reservation"]),
            g3(work["limit"]), g3(work["cpu"]), g3(work["weights"]),
            g2(on))
        src_row = h_idx[None, :] == src[:, None]
        dst_row = h_idx[None, :] == dest[:, None]
        m2 = moved[:, None]
        m3 = moved[:, None, None]
        alloc = xp.where(m3 & src_row[..., None], alloc2[:, :1], alloc)
        alloc = xp.where(m3 & dst_row[..., None], alloc2[:, 1:], alloc)
        ents = xp.where(m2 & src_row, ents2[:, :1], ents)
        ents = xp.where(m2 & dst_row, ents2[:, 1:], ents)
        ns = xp.where(m2 & src_row, ns2[:, :1], ns)
        ns = xp.where(m2 & dst_row, ns2[:, 1:], ns)
        return alloc, ents, ns

    def cond(state):
        (work, moves, n_moves, done, prev_imb, pressure, alloc, ents, ns,
         launch_h, launch_n, k) = state
        return (k < params.max_moves) & ~xp.all(done)

    j_arange = xp.arange(j_ax)

    def body(state):
        (work, moves, n_moves, done, prev_imb, pressure, alloc, ents, ns,
         launch_h, launch_n, k) = state
        act = work["occ"] & on[..., None]
        imb = _masked_std(xp, ns, on, n_on)
        halt = (imb <= params.imbalance_threshold) | (imb >= prev_imb)
        mean_n = xp.sum(ns * on, axis=-1) / xp.maximum(n_on, 1)
        s_idx = xp.arange(s_ax)

        # Candidates come from the most-strained donor host this round:
        # the hill climb moves one VM per round anyway and the
        # argmax-gain move relieves the hottest host, so restricting the
        # candidate scan to it keeps every round O(J * H) instead of
        # O(V * H) -- at grid scale the difference between a migration
        # round and a full admission sweep.
        hot = xp.argmax(xp.where(on, ns, -xp.inf), axis=-1)     # (S,)
        ns_hot = ns[s_idx, hot]
        halt = halt | (ns_hot <= mean_n)                   # nothing above avg
        srcs = hot[:, None] * xp.ones((1, j_ax), dtype=hot.dtype)
        js = j_arange[None, :] * xp.ones((s_ax, 1), dtype=hot.dtype)
        cand = (_gather_slots(xp, act, srcs, js)
                & _gather_slots(xp, work["migratable"], srcs, js))
        # A destination with no managed capacity would starve the mover
        # (its normalized entitlement is pinned at 0): never a receiver.
        recv = (on & (ns <= mean_n[..., None]) & (managed > 0.0))
        fit, fit_unb, _, _ = _admission_slots(
            xp, on, work, managed, host_mem, srcs, js,
            limits, (launch_h, launch_n))
        aff_ok = _affinity_keep_slots(xp, work, act, rmeta.n_groups, srcs,
                                      js)
        fit = fit & aff_ok & cand[..., None] & recv[:, None, :]
        fit_unb = fit_unb & aff_ok & cand[..., None] & recv[:, None, :]
        live = ~done & ~halt
        pressure = pressure | (live & xp.any(
            fit_unb & ~fit, axis=(-1, -2)))

        # Closed-form stddev after the move: the VM carries its current
        # entitlement e_v from the hot host to the destination.
        e_v = _gather_slots(xp, alloc, srcs, js)           # (S, J)
        safe_cap = xp.where(managed > 0.0, managed, 1.0)
        cap_src = safe_cap[s_idx, hot][:, None]
        cap_d = safe_cap[:, None, :]
        ns_src = ns_hot[:, None]
        ns_d = ns[:, None, :]
        ents_src = ents[s_idx, hot][:, None]
        ns_src_new = (ents_src - e_v) / cap_src            # (S, J)
        ns_d_new = (ents[:, None, :] + e_v[..., None]) / cap_d
        t1 = xp.sum(ns * on, axis=-1)[:, None, None]
        t2 = xp.sum(ns * ns * on, axis=-1)[:, None, None]
        t1n = (t1 - ns_src[..., None] - ns_d
               + ns_src_new[..., None] + ns_d_new)
        t2n = (t2 - (ns_src ** 2)[..., None] - ns_d ** 2
               + (ns_src_new ** 2)[..., None] + ns_d_new ** 2)
        denom = xp.maximum(n_on, 1)[:, None, None]
        var = xp.maximum(t2n / denom - (t1n / denom) ** 2, 0.0)
        gain = imb[:, None, None] - xp.sqrt(var)
        cost = (params.min_goodness
                + params.cost_per_gb
                * _gather_slots(xp, work["mem"], srcs, js) / 1024.0)
        score = xp.where(fit & (gain > cost[..., None]), gain, -xp.inf)

        flat = score.reshape(s_ax, -1)                     # (S, J*H)
        best = xp.argmax(flat, axis=-1)
        found = xp.isfinite(
            xp.take_along_axis(flat, best[:, None], axis=-1)[..., 0])
        jj = best // h_ax
        dest = best % h_ax
        do = live & found
        work, moved = move_slot(xp, work, do, hot, jj, dest, pads)
        moves, n_moves = record_move(xp, moves, n_moves, moved, hot, jj,
                                     dest)
        alloc, ents, ns = _refill_pair(work, alloc, ents, ns, moved, hot,
                                       dest)
        if limits.gated:
            is_ep = ((h_idx[None, :] == hot[:, None])
                     | (h_idx[None, :] == dest[:, None]))
            launch_h = launch_h + (moved[:, None] & is_ep).astype(
                launch_h.dtype)
            launch_n = launch_n + moved.astype(launch_n.dtype)
        return (work, moves, n_moves, done | halt | ~found, imb, pressure,
                alloc, ents, ns, launch_h, launch_n, k + 1)

    state = (work, moves, n_moves, done0, xp.full(s_ax, xp.inf), pressure0,
             alloc0, ents0, ns0, launch_h0, launch_n0, 0)
    (work, moves, n_moves, _, _, pressure, _, _, _, launch_h, launch_n,
     _) = be.while_loop(cond, body, state)
    return work, moves, n_moves, pressure, (launch_h, launch_n)
