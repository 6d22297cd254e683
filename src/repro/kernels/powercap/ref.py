"""Pure-lax references for the differential harness.

These run the same math as the production lax executor but *never* consult
the executor globals (``repro.backend.pallas_enabled``), so the parity
tests can compare the Pallas kernels against them while the ``jax-pallas``
executor is globally active -- no risk of accidentally comparing the
kernels against themselves.

``lax_waterfill_dense`` / ``lax_balance_caps`` are exactly the production
lax paths (same pure-math bodies, same loop drivers); the Pallas executor
must be *bit-identical* to them in float64 when interpreting.
``lax_waterfill_segmented`` mirrors the CSR algorithm of
``pallas_waterfill_segmented`` (bit-identity target for the segmented
kernel); ``waterfill_core`` remains the semantic reference, matched to
reduction-order rounding.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import kernels as core_kernels
from repro.drs.entitlement import waterfill_dense_math


def _fori(n, body, init):
    return jax.lax.fori_loop(0, n, body, init)


@functools.partial(jax.jit, static_argnames=("iters",))
def _dense_ref(capacity, floors, ceilings, weights, active, *, iters):
    return waterfill_dense_math(jnp, _fori, capacity, floors, ceilings,
                                weights, iters=iters, active=active)


def lax_waterfill_dense(capacity, floors, ceilings, weights,
                        iters: int = 200, active=None):
    """The production lax dense waterfill (jitted, dispatch-free)."""
    fl = jnp.asarray(floors)
    act = (jnp.ones(fl.shape, bool) if active is None
           else jnp.asarray(active, bool))
    return _dense_ref(jnp.asarray(capacity), fl, jnp.asarray(ceilings),
                      jnp.asarray(weights), act, iters=iters)


@functools.partial(jax.jit, static_argnames=("iters", "params"))
def _balance_ref(hosts, caps, fl, ce, w, act, cpu_reserved, budget,
                 enabled, *, iters, params):
    def ents_at(c):
        managed = core_kernels.managed_capacity(jnp, hosts, c)
        alloc = waterfill_dense_math(jnp, _fori, managed, fl, ce, w,
                                     iters=iters, active=act)
        return jnp.sum(alloc, axis=-1)

    class _LaxBe:
        name = "jax"
        xp = jnp

        @staticmethod
        def while_loop(cond, body, init):
            return jax.lax.while_loop(cond, body, init)

    return core_kernels.balance_caps(_LaxBe, hosts, caps, ents_at,
                                     cpu_reserved, budget, enabled, params)


def lax_balance_caps(hosts, caps, dense, cpu_reserved, budget, enabled,
                     params=core_kernels.BalanceParams()):
    """The production lax BalancePowerCap loop over dense slot columns."""
    hosts = core_kernels.HostCols(*(jnp.asarray(c) for c in hosts))
    return _balance_ref(hosts, jnp.asarray(caps), jnp.asarray(dense.floors),
                        jnp.asarray(dense.ceils),
                        jnp.asarray(dense.weights),
                        jnp.asarray(dense.active, bool),
                        jnp.asarray(cpu_reserved), jnp.asarray(budget),
                        jnp.asarray(enabled, bool),
                        iters=int(dense.iters), params=params)


def lax_waterfill_segmented(capacity, floors, ceilings, weights, seg_ids,
                            n_segs: int, iters: int = 200):
    """Lax mirror of the segmented CSR algorithm (no Pallas, no dispatch):
    sort by segment, pad rows to the same ``JB``, run the dense primitive
    per host, scatter back.  Bit-identity target for
    ``pallas_waterfill_segmented``."""
    from repro.kernels.powercap.ops import _jb_for

    capacity = np.asarray(capacity, dtype=np.float64)
    floors = np.asarray(floors, dtype=np.float64)
    ceilings = np.asarray(ceilings, dtype=np.float64)
    weights = np.asarray(weights, dtype=np.float64)
    seg_ids = np.asarray(seg_ids, dtype=np.int64)
    n = floors.shape[0]
    if n == 0 or n_segs == 0:
        return jnp.zeros((n,), jnp.float64)
    srt = np.argsort(seg_ids, kind="stable")
    seg_sorted = seg_ids[srt]
    counts = np.bincount(seg_sorted, minlength=n_segs).astype(np.int64)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    jb = _jb_for(int(counts.max()))
    slot = np.arange(n, dtype=np.int64) - starts[seg_sorted]

    def dense_rows(col, fill=0.0):
        rows = np.full((n_segs, jb), fill, dtype=np.float64)
        rows[seg_sorted, slot] = col[srt]
        return rows

    active = np.zeros((n_segs, jb), dtype=bool)
    active[seg_sorted, slot] = True
    # Match the pallas entry point: the eager callers (delivery, tests) may
    # not have x64 on, so the mirror pins it the same way.
    with jax.enable_x64(True):
        out_rows = _dense_ref(jnp.asarray(capacity),
                              jnp.asarray(dense_rows(floors)),
                              jnp.asarray(dense_rows(ceilings)),
                              jnp.asarray(dense_rows(weights, fill=1e-12)),
                              jnp.asarray(active), iters=iters)
        out = np.zeros(n, dtype=np.float64)
        out[srt] = np.asarray(out_rows)[seg_sorted, slot]
        return jnp.asarray(out)
