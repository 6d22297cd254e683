"""Drivers for the powercap Pallas kernels (executor entry points).

The dispatchers in ``repro.drs.entitlement`` / ``repro.core.kernels`` call
these when the ``jax-pallas`` executor is active (``repro.backend.
pallas_enabled()``):

  * :func:`pallas_waterfill_dense`   -- drop-in for ``waterfill_dense`` on
    the JAX plane: one grid step per scenario cell over ``(S, H, J)``.
  * :func:`pallas_balance_caps`      -- the whole BalancePowerCap loop with
    the fused balance-round + waterfill kernel as the ``while_loop`` body.
  * :func:`pallas_waterfill_segmented` -- drop-in for the segmented
    (``seg_ids``) waterfill entry points: ragged host/VM counts via a CSR
    layout, one grid step per host, no ``H * J`` dense padding.

Interpret mode only: the kernels run under ``pl.pallas_call(...,
interpret=True)``, where they execute the same jnp op sequence as the lax
executor (bit-identical to it in float64, up to a few ULPs in the fused
balance round).  They cannot run on a TPU: the TPU compiler refuses a
float64 ``pallas_call`` (``NotImplementedError: 64-bit types are not
supported``), and their whole-cell ``(1, H)`` blocks are not tiled for it
either.  On a TPU backend every driver raises :class:`PallasUnsupported`
up front instead of failing deep inside a compile.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import kernels as core_kernels
from repro.kernels.powercap import kernel


class PallasUnsupported(NotImplementedError):
    """The ``jax-pallas`` executor was asked to run on a TPU backend."""


def _refuse_tpu() -> None:
    """Raise :class:`PallasUnsupported` on a TPU backend."""
    if jax.default_backend() == "tpu":
        raise PallasUnsupported(
            "the jax-pallas executor cannot run on a TPU: its kernels "
            "compute in float64, which the TPU Pallas compiler refuses "
            "('64-bit types are not supported'), and their (1, H) blocks "
            "are not tiled for the TPU; use the default 'jax' executor")


# ------------------------------------------------------- dense waterfill
@functools.partial(jax.jit, static_argnames=("iters",))
def _dense_call(capacity, floors, ceilings, weights, active, *, iters):
    return kernel.waterfill_call(capacity, floors, ceilings, weights,
                                 active, iters=iters, interpret=True)


def pallas_waterfill_dense(capacity, floors, ceilings, weights,
                           iters: int = 200, active=None):
    """Pallas twin of ``waterfill_dense_math`` (same shape contract:
    ``capacity (..., H)``, slot columns ``(..., H, J)``)."""
    _refuse_tpu()
    fl = jnp.asarray(floors)
    ce = jnp.asarray(ceilings)
    w = jnp.asarray(weights)
    act = (jnp.ones(fl.shape, bool) if active is None
           else jnp.asarray(active, bool))
    lead = fl.shape[:-2]
    h, j = fl.shape[-2:]
    if h == 0 or j == 0 or 0 in lead:
        return jnp.zeros(fl.shape, fl.dtype)
    cap = jnp.broadcast_to(jnp.asarray(capacity), lead + (h,))
    out = _dense_call(cap.reshape((-1, h)), fl.reshape((-1, h, j)),
                      ce.reshape((-1, h, j)), w.reshape((-1, h, j)),
                      act.reshape((-1, h, j)), iters=iters)
    return out.reshape(fl.shape)


# ----------------------------------------------------- fused balance loop
@functools.partial(jax.jit, static_argnames=("iters", "params"))
def _balance_loop(hosts, caps, fl, ce, w, act, cpu_reserved, budget,
                  enabled, *, iters, params):
    on = hosts.on
    n_on = jnp.sum(on, axis=-1)
    peak_managed = core_kernels.peak_managed_capacity(jnp, hosts)
    managed = core_kernels.managed_capacity(jnp, hosts, caps)
    alloc = kernel.waterfill_call(managed, fl, ce, w, act, iters=iters,
                                  interpret=True)
    ents = jnp.sum(alloc, axis=-1)
    ns = jnp.where(managed > 0.0, ents / jnp.maximum(managed, 1e-300), 0.0)
    done0 = ~enabled | (n_on < 2)
    did0 = jnp.zeros_like(done0)

    def cond(state):
        return (state[-1] < params.max_iters) & ~jnp.all(state[4])

    def body(state):
        caps, managed, ents, ns, done, did, rounds = state
        out = kernel.balance_round_call(
            hosts, (fl, ce, w, act), cpu_reserved, budget, n_on,
            peak_managed, (caps, managed, ents, ns, done, did),
            iters=iters, params=params, interpret=True)
        return (*out, rounds + 1)

    state = (caps, managed, ents, ns, done0, did0, 0)
    caps, _, _, _, _, did, rounds = jax.lax.while_loop(cond, body, state)
    return caps, did, rounds


def pallas_balance_caps(hosts, caps, dense, cpu_reserved, budget, enabled,
                        params):
    """Pallas driver for the BalancePowerCap loop.

    Mirrors ``repro.core.kernels.balance_caps`` on the JAX plane, with the
    per-round math running as the fused kernel; ``dense`` is the
    ``DenseCols`` bundle describing the same entitlement problem as the
    caller's ``ents_at`` closure (which this driver replaces).
    """
    _refuse_tpu()
    caps = jnp.asarray(caps)
    s, h = caps.shape
    fl = jnp.asarray(dense.floors)
    ce = jnp.asarray(dense.ceils)
    w = jnp.asarray(dense.weights)
    act = jnp.asarray(dense.active, bool)
    if s == 0 or h == 0:
        return caps, jnp.zeros(jnp.shape(enabled), bool), 0
    if fl.shape[-1] == 0:
        # No slots: pad one inactive slot so the kernel grid is well formed
        # (the masked slot allocates nothing).
        pad = ((0, 0),) * (fl.ndim - 1) + ((0, 1),)
        fl, ce, w = (jnp.pad(c, pad) for c in (fl, ce, w))
        act = jnp.pad(act, pad)
    return _balance_loop(hosts, caps, fl, ce, w, act, cpu_reserved,
                         budget, enabled, iters=int(dense.iters),
                         params=params)


# ---------------------------------------------------- segmented waterfill
@functools.partial(jax.jit, static_argnames=("n", "iters", "jb"))
def _segmented_call(capacity, starts, counts, fl, ce, w, seg_sorted, slot,
                    perm, *, n, iters, jb):
    dense = kernel.segmented_call(capacity, starts, counts, fl, ce, w,
                                  iters=iters, jb=jb, interpret=True)
    alloc_sorted = dense[seg_sorted, slot]
    return jnp.zeros((n,), fl.dtype).at[perm].set(alloc_sorted)


def _jb_for(max_count: int) -> int:
    """Static window width: next power of two (>= 4) covering the longest
    row, so recompiles happen on row-length doublings, not every call."""
    jb = 4
    while jb < max_count:
        jb *= 2
    return jb


def pallas_waterfill_segmented(capacity, floors, ceilings, weights,
                               seg_ids, n_segs: int, iters: int = 200):
    """Segmented (ragged) waterfill: flat item arrays plus ``seg_ids``.

    CSR layout built eagerly (inputs must be concrete, as in the NumPy and
    test callers): items are stably sorted by segment, each host's window
    ``[start, start + count)`` is processed by one grid step with a
    ``JB``-wide dynamic slice, and the per-host rows are scattered back to
    the original item order.  Per-host math is the dense primitive, so the
    result matches ``waterfill_core`` to reduction-order rounding.
    """
    _refuse_tpu()
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
    pad = np.zeros(jb, dtype=np.float64)
    slot = np.arange(n, dtype=np.int64) - starts[seg_sorted]
    with jax.enable_x64(True):
        return _segmented_call(
            jnp.asarray(capacity), jnp.asarray(starts), jnp.asarray(counts),
            jnp.asarray(np.concatenate([floors[srt], pad])),
            jnp.asarray(np.concatenate([ceilings[srt], pad])),
            jnp.asarray(np.concatenate([weights[srt], pad + 1e-12])),
            jnp.asarray(seg_sorted), jnp.asarray(slot), jnp.asarray(srt),
            n=n, iters=iters, jb=jb)
