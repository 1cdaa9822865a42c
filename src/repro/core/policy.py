"""Scheduler policy/priority logic as pure array functions (paper Eqs. 6-7).

Single source of truth for the priority math, shared by three call sites:

* the scalar discrete-event simulator (:mod:`repro.core.scheduler`), which
  calls these with python floats / bools;
* the vectorized fleet simulator (:mod:`repro.fleet`), which calls them with
  ``(devices, queue)``-shaped ``jnp`` arrays under ``vmap``/``scan``;
* the Pallas priority kernel (:mod:`repro.kernels.fleet_priority`), whose
  kernel body evaluates the same expressions on VMEM-resident tiles.

To stay polymorphic over float / numpy / jnp / Pallas tracer inputs, the
priority functions use only arithmetic and comparisons (booleans are blended
by multiplication instead of ``where``).  Larger score = higher priority
everywhere; EDF-style "earliest wins" keys are therefore negated deadlines.
"""
from __future__ import annotations

import jax.numpy as jnp

# Policy identifiers shared by the scalar and fleet paths.
POLICY_IDS = {"zygarde": 0, "edf": 1, "edf-m": 2, "rr": 3}
IMPRECISE_POLICIES = ("zygarde", "edf-m")   # early exit enabled

# Sentinel for "never schedulable" (python scalar so Pallas treats it as a
# compile-time constant, not a captured array).
NEG = -1e30

# Deadline ties are broken by release order (scalar path: lexicographic
# ``(deadline, release)``); in the array path the release enters the score at
# a scale far below any deadline difference.
_TIE = 1e-9

# Round-robin task rotation: the rotation distance of a slot's task from the
# per-device cursor dominates the within-task FIFO release key.  Requires
# releases (bounded by the horizon) to stay below this weight — true for any
# horizon under ~10^4 s (the fleet grids run minutes, not hours).
RR_TASK_W = 1e4


def exit_test(margin, threshold):
    """The utility test (paper §4.1): exit when the classifier margin clears
    the per-unit threshold.  Strict ``>`` matches the host-side calibration
    in :func:`repro.core.utility.calibrate_threshold` (and the precomputed
    ``JobProfile.passes`` tables).  Polymorphic over floats and arrays so
    the fleet simulator can evaluate it against *tuned* per-device
    ``(D, U)`` threshold arrays instead of baked-in booleans.
    """
    return margin > threshold


def zeta_priority(laxity, utility, mandatory, alpha, beta):
    """Eq. 6 (continuous power): dynamic priority zeta.

    laxity    : deadline - t_now
    utility   : psi, classifier confidence after the last executed unit
    mandatory : gamma, 1/True if the *next* unit is mandatory
    """
    gamma = 1.0 * mandatory
    return (1.0 - alpha * laxity) + (1.0 - beta * utility) + gamma


def zeta_intermittent_priority(laxity, utility, mandatory, alpha, beta,
                               eta, energy, e_opt):
    """Eq. 7 (intermittent power): the eta-weighted energy gate zeroes the
    priority of optional units while the store is below E_opt."""
    base = (1.0 - alpha * laxity) + (1.0 - beta * utility)
    gamma = 1.0 * mandatory
    gate = 1.0 * (eta * energy >= e_opt)
    return gate * (base + gamma) + (1.0 - gate) * gamma * base


def edf_key(deadline, release):
    """Earliest-deadline-first as a max-score key.

    ``deadline`` may be absolute or a laxity (deadline - t): subtracting a
    common t leaves the per-device ordering unchanged.  Deadline ties break
    by release order through a float perturbation — equivalent to the scalar
    simulator's exact lexicographic ``(deadline, release)`` whenever genuine
    deadline gaps exceed ``_TIE * release`` (always true for the fleet path's
    single periodic task stream, whose deadlines are distinct by period).
    """
    return -(deadline + _TIE * release)


def edfm_key(deadline, release, mandatory):
    """EDF over mandatory units only: optional work is never schedulable."""
    m = 1.0 * mandatory
    return m * edf_key(deadline, release) + (1.0 - m) * NEG


def rr_key(release, task_rank=0.0):
    """Round-robin at unit granularity: rotate across tasks, FIFO-by-release
    within a task.  ``task_rank`` is the rotation distance of the slot's task
    from the device's round-robin cursor (``(task - cursor) mod K``); with a
    single task stream it is identically 0 and the key degenerates to the
    pure FIFO ``-release`` (bit-identical to the pre-task-set fleet path).
    The scalar simulator implements the same rotation imperatively."""
    return -(task_rank * RR_TASK_W + release)


def policy_scores(policy_id, active, laxity, release, utility, mandatory,
                  alpha, beta, eta, energy, e_opt, persistent,
                  task_rank=0.0):
    """Batched score matrix + validity threshold for every policy.

    Queue-shaped args (``active`` .. ``mandatory``, ``task_rank``) carry a
    trailing queue axis; per-device args (``policy_id`` .. ``persistent``)
    must broadcast against them (callers pass ``x[..., None]`` shapes).
    ``task_rank`` (the round-robin rotation distance of each slot's task,
    0 for single-task devices) only enters the ``rr`` key.  Returns
    ``(scores, threshold)``: pick ``argmax(scores)`` and treat the device as
    idle when ``max(scores) <= threshold``.
    """
    zyg = jnp.where(
        persistent.astype(bool),
        zeta_priority(laxity, utility, mandatory, alpha, beta),
        zeta_intermittent_priority(laxity, utility, mandatory, alpha, beta,
                                   eta, energy, e_opt),
    )
    edf = edf_key(laxity, release)
    edfm = edfm_key(laxity, release, mandatory)
    rr = rr_key(release, task_rank)

    # chained where, not jnp.select: select lowers through an argmax over
    # the condition stack, which Mosaic refuses for bool operands
    scores = jnp.where(policy_id == 0, zyg,
                       jnp.where(policy_id == 1, edf,
                                 jnp.where(policy_id == 2, edfm, rr)))
    scores = jnp.where(active.astype(bool), scores, NEG)
    # zygarde idles when even the best score is <= 0 (energy-gated optional
    # work); the deadline-keyed policies only idle on an empty queue.
    threshold = jnp.where(policy_id == 0, 0.0, 0.5 * NEG)
    return scores, threshold
