"""One DRS cluster under CloudPowerCap, tick by tick, in plain scalar code.

Written from the paper (arXiv:1403.1289) and the protocol the benchmark's
configurations state, one host and one VM at a time: no arrays shared
across hosts, no segment operations, nothing batched.

* Power model: Eq. 1 (power drawn at a utilization), Eq. 3 (capacity
  under a cap), Eq. 4 (capacity the manager may allocate), and the
  inverse of Eq. 4.
* Host scheduling: each tick a host's managed capacity is divided among
  its VMs by weighted max-min fairness (reservation floor, demand ceiling,
  shares as weights), solved exactly by walking the sorted breakpoints of
  the water level.
* Every DRS period, the three phases of the manager: Powercap Allocation
  (caps to their reserved floors, then RedivvyPowerCap, Algorithm 1, in
  its budget-conserving form), Powercap-based Balancing (BalancePowerCap,
  Algorithm 2: progressive filling of normalized entitlements by moving
  capacity between hosts), and Powercap Redistribution (DPM's power-on
  with its cap funded from the unallocated budget and then from the
  coolest hosts, or its power-off after evacuating the coolest host, with
  the freed watts spread by headroom; Algorithm 3).
* Execution: cap changes take effect at once, cap decreases before the
  increases they fund; evacuation vMotions complete at the tick they
  start; power-on takes 120 s and power-off 30 s; a DRS invocation waits
  while actions are in flight.  Every tick the caps of powered-on hosts,
  and of hosts whose power-on is under way, stay within the budget.

``num`` is the number type of delivery and of the payload and energy
sums: ``float`` (float64) as the configurations state, ``numpy.float32``
only for the benchmark's lower-precision control.
"""

from __future__ import annotations

import bisect
import dataclasses
import math

#: Halting rules of BalancePowerCap: imbalance target (stddev of the
#: normalized entitlements), round limit, smallest transfer (MHz).
IMBALANCE_THRESHOLD = 0.01
MAX_ROUNDS = 64
MIN_TRANSFER = 1e-3
#: Cap changes smaller than this (W) are not emitted.
CAP_EPS = 1e-9
POWER_ON_S = 120.0
POWER_OFF_S = 30.0


@dataclasses.dataclass(frozen=True)
class HostSpec:
    capacity_peak: float          # MHz at 100% utilization, uncapped
    power_idle: float             # W at 0% utilization
    power_peak: float             # W at 100% utilization
    memory_mb: float
    hypervisor_overhead: float = 0.0   # Eq. 4's C_H, MHz
    power_nameplate: float = 0.0       # label power; not used here


def power_drawn(spec: HostSpec, util):
    """Eq. 1."""
    u = min(max(util, 0.0), 1.0)
    return spec.power_idle + (spec.power_peak - spec.power_idle) * u


def managed_capacity(spec: HostSpec, cap: float) -> float:
    """Eq. 3 then Eq. 4: the capacity a cap of ``cap`` W leaves the
    manager."""
    c = min(max(cap, spec.power_idle), spec.power_peak)
    capped = spec.capacity_peak * ((c - spec.power_idle)
                                   / (spec.power_peak - spec.power_idle))
    return max(capped - spec.hypervisor_overhead, 0.0)


def cap_for(spec: HostSpec, capacity: float) -> float:
    """Inverse of Eq. 4: the cap that supports a managed capacity."""
    c = min(max(capacity + spec.hypervisor_overhead, 0.0),
            spec.capacity_peak)
    return spec.power_idle + (spec.power_peak - spec.power_idle) * (
        c / spec.capacity_peak)


def waterfill(capacity, floors, ceils, weights):
    """Weighted max-min division of ``capacity``: ``x_i = clip(w_i * L,
    f_i, c_i)`` at the level ``L`` where the ``x_i`` sum to
    ``min(capacity, sum c_i)``.  Floors that alone exceed the capacity
    are granted pro rata.  Works in the number type of its inputs."""
    ceils = [max(c, f) for c, f in zip(ceils, floors)]
    total_floor = sum(floors)
    if total_floor >= capacity:
        scale = capacity / max(total_floor, 1e-12)
        return [f * scale for f in floors]
    if sum(ceils) <= capacity:
        return ceils
    items = list(zip(floors, ceils, weights))

    def fill(level):
        return sum(min(max(w * level, f), c) for f, c, w in items)

    # The fill is linear between consecutive breakpoints f/w and c/w:
    # find the two around the target, then solve that line for the level.
    lo = 0.0
    for hi in sorted({f / w for f, _, w in items}
                     | {c / w for _, c, w in items}):
        if hi > lo and fill(hi) >= capacity:
            break
        lo = max(lo, hi)
    fixed, free_w = 0.0, 0.0
    for f, c, w in items:
        if c / w <= lo:
            fixed += c                       # at its ceiling
        elif f / w >= hi:
            fixed += f                       # at its floor
        else:
            free_w += w                      # on the water line
    level = (capacity - fixed) / free_w
    return [min(max(w * level, f), c) for f, c, w in items]


@dataclasses.dataclass
class VM:
    shares: float = 1000.0
    reservation: float = 0.0      # MHz
    limit: float = math.inf       # MHz
    demand: float = 0.0           # MHz, this tick
    mem_demand: float = 0.0       # MB, this tick

    @property
    def wanted(self) -> float:
        """Demand as entitlement sees it: within [reservation, limit]."""
        return min(max(self.demand, self.reservation), self.limit)


class Cluster:
    """Hosts, their caps and power states, and where each VM runs."""

    def __init__(self, specs, caps, on, vms, vm_host, budget):
        self.specs = list(specs)
        self.caps = [float(c) for c in caps]
        self.on = list(on)
        self.vms = vms
        self.budget = float(budget)
        self.residents = [[] for _ in self.specs]   # VM indices, in order
        for v, h in enumerate(vm_host):
            self.residents[h].append(v)
        self.host_of = list(vm_host)

    def copy(self) -> "Cluster":
        c = Cluster.__new__(Cluster)
        c.specs, c.vms, c.budget = self.specs, self.vms, self.budget
        c.caps, c.on = list(self.caps), list(self.on)
        c.residents = [list(r) for r in self.residents]
        c.host_of = list(self.host_of)
        return c

    def move(self, v: int, dest: int) -> None:
        self.residents[self.host_of[v]].remove(v)
        bisect.insort(self.residents[dest], v)
        self.host_of[v] = dest

    def hosts_on(self) -> list[int]:
        return [h for h in range(len(self.specs)) if self.on[h]]

    def managed(self, h: int, cap: float | None = None) -> float:
        if not self.on[h]:
            return 0.0
        return managed_capacity(self.specs[h],
                                self.caps[h] if cap is None else cap)

    def reserved(self, h: int) -> float:
        return sum(self.vms[v].reservation for v in self.residents[h])

    def wanted(self, h: int) -> float:
        return sum(self.vms[v].wanted for v in self.residents[h])

    def mem_demand(self, h: int) -> float:
        return sum(self.vms[v].mem_demand for v in self.residents[h])

    def cpu_util(self, h: int) -> float:
        cap = self.managed(h)
        return self.wanted(h) / cap if cap > 0.0 else 0.0

    def mem_util(self, h: int) -> float:
        mem = self.specs[h].memory_mb
        if not self.on[h] or mem <= 0.0:
            return 0.0
        return self.mem_demand(h) / mem

    def entitlement(self, h: int, managed: float) -> float:
        """Sum of the entitlements of ``h``'s VMs at managed capacity
        ``managed``: reservations up to demand, shared by shares."""
        vms = [self.vms[v] for v in self.residents[h]]
        if not vms:
            return 0.0
        return sum(waterfill(managed,
                             [min(m.reservation, m.limit) for m in vms],
                             [m.wanted for m in vms],
                             [max(m.shares, 1e-12) for m in vms]))

    def allocated(self) -> float:
        return sum(self.caps[h] for h in self.hosts_on())


# ------------------------------------------------------------- actions
class Plan:
    """The actions of one invocation, with their prerequisites."""

    def __init__(self):
        self.actions: list[dict] = []

    def add(self, kind, target, value=None, dest=None, after=()):
        a = {"kind": kind, "target": target, "value": value, "dest": dest,
             "after": tuple(after), "state": "waiting", "end": 0.0}
        self.actions.append(a)
        return a

    def caps(self, before: Cluster, hosts, new_caps, after=()) -> list:
        """Cap changes of ``hosts``: the decreases first, every increase
        after all of them, so the caps' sum never passes the budget."""
        down = [h for h in hosts if new_caps[h] < before.caps[h] - CAP_EPS]
        up = [h for h in hosts if new_caps[h] > before.caps[h] + CAP_EPS]
        dec = [self.add("cap", h, new_caps[h], after=after) for h in down]
        inc = [self.add("cap", h, new_caps[h], after=tuple(after) + tuple(
            map(id, dec))) for h in up]
        return dec + inc


# ---------------------------------------------------- manager, phase 1
def powercap_allocation(live: Cluster, plan: Plan) -> Cluster:
    """Caps to their reserved floors (GetFlexiblePower), then
    RedivvyPowerCap: hosts whose floor grew get it, funded by the others
    giving up the same share of their excess over their floor."""
    work = live.copy()
    on = live.hosts_on()
    floor = {h: max(cap_for(live.specs[h], live.reserved(h)),
                    live.specs[h].power_idle) for h in on}
    grow = {h: floor[h] - live.caps[h] for h in on}
    needed = sum(d for d in grow.values() if d > 0.0)
    excess = sum(-d for d in grow.values() if d <= 0.0)
    for h in on:
        if needed <= 0.0:
            work.caps[h] = live.caps[h]            # nothing grew
        elif excess <= 0.0 or grow[h] > 0.0:
            work.caps[h] = floor[h]
        else:
            keep = 1.0 - min(needed / excess, 1.0)
            work.caps[h] = floor[h] + keep * (live.caps[h] - floor[h])
    assert work.allocated() <= max(live.allocated(), live.budget) + 1e-6
    plan.caps(live, on, work.caps)
    return work


# ---------------------------------------------------- manager, phase 2
def _pstdev(values) -> float:
    mean = sum(values) / len(values)
    return math.sqrt(sum((x - mean) ** 2 for x in values) / len(values))


def balance_power_cap(work: Cluster, plan: Plan) -> Cluster:
    """Progressive filling toward equal normalized entitlement
    ``N_h = entitlement / managed capacity``: each round, hosts above the
    cluster's level receive capacity (up to their peak and to what their
    entitlement needs at that level) and hosts below give it (down to that
    level and to their reservations), in proportion to need and to what
    each can give.  Watts that the hosts' different Watts-per-MHz push
    past the budget come back from the receivers evenly.  A round that
    would raise the imbalance is dropped and the loop ends."""
    on = work.hosts_on()
    if len(on) < 2:
        return work
    specs = work.specs
    caps = {h: work.caps[h] for h in on}
    managed = {h: work.managed(h) for h in on}
    ents = {h: work.entitlement(h, managed[h]) for h in on}

    def level(m, e):
        return e / m if m > 0.0 else 0.0

    ns = {h: level(managed[h], ents[h]) for h in on}
    peak = {h: max(specs[h].capacity_peak - specs[h].hypervisor_overhead,
                   0.0) for h in on}
    moved = False
    for _ in range(MAX_ROUNDS):
        imbalance = _pstdev([ns[h] for h in on])
        total = sum(managed[h] for h in on)
        avg = sum(ents[h] for h in on) / max(total, 1e-300)
        if imbalance <= IMBALANCE_THRESHOLD or total <= 0.0 or avg <= 1e-12:
            break
        takers = [h for h in on if ns[h] > avg]
        givers = [h for h in on if ns[h] < avg]
        need, give = {}, {}
        for h in takers:
            need[h] = max(min(peak[h], ents[h] / avg) - managed[h], 0.0)
        for h in givers:
            give[h] = max(managed[h] - max(ents[h] / avg, work.reserved(h)),
                          0.0)
        total_need, total_give = sum(need.values()), sum(give.values())
        transfer = min(total_need, total_give)
        if transfer <= MIN_TRANSFER:
            break
        new = dict(caps)
        for h in takers:
            if need[h] > 0.0:
                new[h] = cap_for(specs[h], managed[h] + transfer * need[h]
                                 / max(total_need, 1e-300))
        for h in givers:
            if give[h] > 0.0:
                new[h] = cap_for(specs[h], managed[h] - transfer * give[h]
                                 / max(total_give, 1e-300))
        over = sum(new[h] for h in on) - work.budget
        if over > 1e-6:
            for h in takers:
                new[h] = max(new[h] - over / max(len(takers), 1),
                             specs[h].power_idle)
        new_managed = {h: work.managed(h, new[h]) for h in on}
        new_ents = {h: work.entitlement(h, new_managed[h]) for h in on}
        new_ns = {h: level(new_managed[h], new_ents[h]) for h in on}
        if _pstdev([new_ns[h] for h in on]) > imbalance + 1e-12:
            break
        caps, managed, ents, ns = new, new_managed, new_ents, new_ns
        moved = True
    if not moved:
        return work
    out = work.copy()
    for h in on:
        out.caps[h] = caps[h]
    assert out.allocated() <= out.budget + 1e-6, "balance broke the budget"
    plan.caps(work, on, out.caps)
    return out


# ---------------------------------------------------- manager, phase 3
def fund_power_on(work: Cluster, cand: int, high_util: float):
    """The candidate's cap, up to its peak: the unallocated budget first,
    then hosts below the power-on trigger drained coolest first, each
    down to the cap at which its demand would just trip the trigger, its
    reservations and its idle power.  Returns the funded cluster."""
    out = work.copy()
    on = work.hosts_on()
    spec = work.specs[cand]
    granted = work.caps[cand] if work.on[cand] else 0.0
    needed = max(spec.power_peak - granted, 0.0)
    pool = max(work.budget - work.allocated(), 0.0)
    take = min(pool, needed)
    granted += take
    needed -= take
    donors = sorted((h for h in on if h != cand
                     and work.cpu_util(h) < high_util),
                    key=work.cpu_util)
    for h in donors:
        if needed <= 1e-9:
            break
        floor = max(cap_for(work.specs[h], max(work.wanted(h) / high_util,
                                                work.reserved(h))),
                    work.specs[h].power_idle)
        take = min(max(work.caps[h] - floor, 0.0), needed)
        out.caps[h] = work.caps[h] - take
        granted += take
        needed -= take
    out.caps[cand] = min(granted, spec.power_peak)
    return out


def reabsorb_power_off(work: Cluster, victim: int) -> Cluster:
    """The victim's cap returns to the pool, which is spread over the
    hosts left on in proportion to their headroom to peak."""
    out = work.copy()
    out.on[victim] = False
    out.caps[victim] = 0.0
    rest = out.hosts_on()
    pool = max(out.budget - out.allocated(), 0.0)
    room = {h: out.specs[h].power_peak - out.caps[h] for h in rest
            if out.caps[h] < out.specs[h].power_peak - 1e-9}
    total = sum(room.values())
    if total > 0.0 and pool > 0.0:
        grant = min(pool, total)
        for h, r in room.items():
            out.caps[h] = min(out.caps[h] + grant * r / total,
                              out.specs[h].power_peak)
    assert out.allocated() <= out.budget + 1e-6, "reabsorb broke the budget"
    return out


def dpm(work: Cluster, params: dict, low_since: dict, now: float,
        last_change: float):
    """One DPM pass: ``("on", host, [])``, ``("off", host, moves)`` or
    ``None``.  A hot host (CPU or memory above ``high_util``) powers on
    the first standby host; a cluster low on both below ``low_util`` for
    ``stable_window_s`` (and as long since the last power event) evacuates
    its coolest host, each VM, largest memory first, to the coolest host
    that stays within ``target_util`` on CPU and memory."""
    on = work.hosts_on()
    high, low = params["high_util"], params["low_util"]
    util = {h: work.cpu_util(h) for h in on}
    if any(util[h] > high or work.mem_util(h) > high for h in on):
        standby = [h for h in range(len(work.specs)) if not work.on[h]]
        return ("on", standby[0], []) if standby else None
    if len(on) <= 1:
        return None
    if not all(util[h] < low and work.mem_util(h) < low for h in on):
        return None
    oldest = max(max(low_since.get(h, now) for h in on), last_change)
    if now - oldest < params["stable_window_s"]:
        return None
    victim = min(on, key=lambda h: util[h])        # first of equals
    trial = work.copy()
    moves = []
    target = params["target_util"]
    for v in sorted(trial.residents[victim],
                    key=lambda v: -work.vms[v].mem_demand):
        vm = work.vms[v]
        best, best_util = None, math.inf
        for h in trial.hosts_on():
            if h == victim:
                continue
            cap = trial.managed(h)
            mem = trial.specs[h].memory_mb
            if (trial.reserved(h) + vm.reservation > cap + 1e-9
                    or trial.mem_demand(h) + vm.mem_demand > mem + 1e-9):
                continue
            u = (trial.wanted(h) + vm.wanted) / max(cap, 1e-9)
            m = (trial.mem_demand(h) + vm.mem_demand) / max(mem, 1e-9)
            if u <= target and m <= target and u < best_util:
                best, best_util = h, u
        if best is None:
            return None
        trial.move(v, best)
        moves.append((v, best))
    return ("off", victim, moves)


def invoke(live: Cluster, plan: Plan, powercap: bool, dpm_params,
           low_since: dict, now: float, last_change: float) -> None:
    """One DRS invocation: the three phases on a what-if copy of the
    cluster, their actions added to ``plan``."""
    work = live.copy()
    if powercap:
        work = powercap_allocation(work, plan)
        work = balance_power_cap(work, plan)
    if dpm_params is None:
        return
    rec = dpm(work, dpm_params, low_since, now, last_change)
    if rec is None:
        return
    kind, host, moves = rec
    if kind == "on" and powercap:
        funded = fund_power_on(work, host, dpm_params["high_util"])
        if managed_capacity(work.specs[host], funded.caps[host]) <= 0.0:
            return                                 # cannot be funded
        hosts = [h for h in range(len(work.specs))
                 if work.on[h] or funded.on[h] or h == host]
        caps = plan.caps(work, hosts, funded.caps)
        plan.add("power_on", host, after=map(id, caps))
    elif kind == "on":
        plan.add("power_on", host)
    else:
        migs = [plan.add("migrate", v, dest=d) for v, d in moves]
        for v, d in moves:
            work.move(v, d)
        off = plan.add("power_off", host, after=map(id, migs))
        if powercap:
            after = reabsorb_power_off(work, host)
            hosts = [h for h in range(len(work.specs))
                     if work.on[h] or after.on[h]]
            plan.caps(work, hosts, after.caps, after=(id(off),))


# ----------------------------------------------------------- simulator
@dataclasses.dataclass
class Totals:
    cap_changes: int = 0
    vmotions: int = 0
    power_ons: int = 0
    power_offs: int = 0
    energy_j: float = 0.0
    cpu_payload_mhz_s: float = 0.0


def simulate(cluster: Cluster, traces, *, duration_s: float, tick_s: float,
             drs_period_s: float, powercap: bool, dpm_params,
             num=float) -> Totals:
    """Run the cluster for ``duration_s``.  ``traces[v](t)`` gives VM
    ``v``'s ``(cpu MHz, memory MB)`` demand at time ``t``."""
    live = cluster
    out = Totals(energy_j=num(0.0), cpu_payload_mhz_s=num(0.0))
    actions: list[dict] = []
    done: set[int] = set()
    low_since: dict[int, float] = {}
    last_change = -1e18
    low_util = dpm_params["low_util"] if dpm_params else 0.45

    def start(t):
        for a in actions:
            if a["state"] != "waiting" or not all(p in done
                                                  for p in a["after"]):
                continue
            if a["kind"] in ("power_on", "power_off"):
                a["state"] = "running"
                a["end"] = t + (POWER_ON_S if a["kind"] == "power_on"
                                else POWER_OFF_S)
                continue
            if a["kind"] == "cap":
                live.caps[a["target"]] = a["value"]
                out.cap_changes += 1
            elif live.host_of[a["target"]] != a["dest"]:
                live.move(a["target"], a["dest"])
                out.vmotions += 1
            a["state"] = "done"
            done.add(id(a))

    def complete(t):
        nonlocal last_change
        for a in actions:
            if a["state"] == "running" and a["end"] <= t:
                powering_on = a["kind"] == "power_on"
                live.on[a["target"]] = powering_on
                if powering_on:
                    out.power_ons += 1
                else:
                    out.power_offs += 1
                last_change = t
                a["state"] = "done"
                done.add(id(a))

    next_drs = drs_period_s
    t = 0.0
    while t < duration_s:
        for v, vm in enumerate(live.vms):
            vm.demand, vm.mem_demand = traces[v](t)
        complete(t)
        start(t)
        if t >= next_drs:
            if all(a["state"] == "done" for a in actions):
                plan = Plan()
                invoke(live, plan, powercap, dpm_params, low_since, t,
                       last_change)
                actions += plan.actions
                next_drs = t + drs_period_s
            else:
                next_drs = t + tick_s          # wait for the actions
        start(t)
        # Delivery and accounting, host by host.
        for h in live.hosts_on():
            spec = live.specs[h]
            vms = [live.vms[v] for v in live.residents[h]]
            dem = [num(min(m.demand, m.limit)) for m in vms]
            alloc = waterfill(
                num(live.managed(h)),
                [min(num(m.reservation), d) for m, d in zip(vms, dem)],
                dem, [num(m.shares) for m in vms]) if vms else []
            delivered = num(sum(alloc))
            out.cpu_payload_mhz_s += delivered * tick_s
            out.energy_j += power_drawn(
                spec, min(delivered / spec.capacity_peak, 1.0)) * tick_s
            if live.cpu_util(h) < low_util and live.mem_util(h) < low_util:
                low_since.setdefault(h, t)
            else:
                low_since.pop(h, None)
        starting = {a["target"] for a in actions if a["kind"] == "power_on"
                    and a["state"] != "done"}
        held = sum(live.caps[h] for h in range(len(live.specs))
                   if live.on[h] or h in starting)
        assert held <= live.budget + 1e-6, (
            f"budget violated at t={t}: {held} W > {live.budget} W")
        t += tick_s
    return out
