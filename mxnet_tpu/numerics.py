"""Numerical-health guard: fused finite-checks, skip-step, clipping,
divergence auto-recovery.

A single NaN/Inf gradient silently corrupts optimizer state and poisons
every later step — the failure mode large bf16/f16 runs hit most often.
The reference stack guards against this with `DynamicLossScaler.has_overflow`,
which does one blocking `asnumpy()` readback PER GRADIENT and therefore
defeats the fused-step pipelining (PR 2/3).  Here the guard lives inside
the compiled programs instead:

- `grad_health(raws)` — ONE cached jit over the step's raw gradient
  arrays returning a tiny ``(2,)`` f32 device array
  ``[all_finite, global_sq_norm]``.  No host sync happens at this point;
  the array stays on device.
- `StepGuard` — carries that device array into the fused optimizer
  programs (`optimizer/grouped.py`), which compute the health predicate
  IN-TRACE and `jnp.where` the updated weights/states against the
  originals.  An unhealthy step therefore leaves weights and optimizer
  state bitwise-unchanged without any extra dispatch, and a healthy step
  is bitwise-identical to the unguarded program (`where` with a true
  predicate is the identity; donation semantics are preserved).
- Exactly ONE scalar readback per step: the Trainer materializes the
  health array once, AFTER the update dispatch, so XLA pipelines the
  guard with the step.  `readback_count()` regression-tests this.
- `DivergenceMonitor` — host-side EWMA tracking of loss/grad-norm that,
  after `MXTPU_MAX_BAD_STEPS` consecutive unhealthy or exploding steps,
  rolls back to the last `resilience.LocalCheckpointer` snapshot with a
  re-seeded loss scale and quarantines the offending batch indices.

Env knobs (docs/env_vars.md): ``MXTPU_GRAD_GUARD`` (default 1),
``MXTPU_MAX_BAD_STEPS`` (default 25), ``MXTPU_CLIP_GLOBAL_NORM``
(unset = no clipping).  Fault-injection sites (docs/resilience.md):
``nan_grad`` poisons one gradient before health assessment;
``inf_loss`` corrupts the loss seen by `DivergenceMonitor.observe`.
"""

from __future__ import annotations

import logging
import math
import os

from .base import MXNetError

_LOG = logging.getLogger("mxnet_tpu.numerics")


# -- env plumbing --------------------------------------------------------------

def grad_guard_enabled() -> bool:
    """MXTPU_GRAD_GUARD gate (default on); 0/false/off disables the
    fused finite-check + skip-step machinery.  Read at each step."""
    return os.environ.get("MXTPU_GRAD_GUARD", "1").lower() \
        not in ("0", "false", "off", "")


def max_bad_steps(default=25) -> int:
    """MXTPU_MAX_BAD_STEPS: consecutive unhealthy/exploding steps before
    `DivergenceMonitor` declares divergence and rolls back."""
    try:
        return int(os.environ.get("MXTPU_MAX_BAD_STEPS", default))
    except ValueError:
        return default


def clip_global_norm_env():
    """MXTPU_CLIP_GLOBAL_NORM as a float, or None when unset/<=0."""
    raw = os.environ.get("MXTPU_CLIP_GLOBAL_NORM")
    if not raw:
        return None
    try:
        val = float(raw)
    except ValueError:
        return None
    return val if val > 0.0 else None


# -- readback accounting (regression-tested: one host sync per step) -----------

_READBACK_COUNT = 0


def readback_count() -> int:
    """Number of health-scalar host readbacks since the last reset —
    exactly one per guarded step (the `StepGuard` materialization)."""
    return _READBACK_COUNT


def reset_readback_count() -> None:
    global _READBACK_COUNT
    _READBACK_COUNT = 0


# -- the fused health reduction ------------------------------------------------

_HEALTH_FN = None
_COMBINE_FN = None


def health_of(arrs):
    """Pure, traceable health reduction over raw arrays → ``(2,)`` f32
    ``[all_finite, global_sq_norm]``.  The ONE home of the health math:
    `grad_health` jits it for the eager path, and the whole-step capture
    (`gluon/captured.py`) inlines it so both paths reduce in the same
    order with the same accumulator dtype."""
    import jax.numpy as jnp

    # f32 accumulation: f16/bf16 inf/nan survive the upcast, and
    # the squared norm of a large group would overflow in f16.
    fin = jnp.bool_(True)
    sq = jnp.zeros((), jnp.float32)
    for a in arrs:
        af = a.astype(jnp.float32)
        fin = fin & jnp.all(jnp.isfinite(af))
        sq = sq + jnp.sum(jnp.square(af))
    return jnp.stack([fin.astype(jnp.float32), sq])


def _health_fn():
    global _HEALTH_FN
    if _HEALTH_FN is None:
        import jax

        _HEALTH_FN = jax.jit(health_of)
    return _HEALTH_FN


def grad_health(raws):
    """ONE jit dispatch over the step's raw gradient arrays → a ``(2,)``
    f32 device array ``[all_finite, global_sq_norm]``.  Nothing is read
    back to the host here; jit caches per (shapes, dtypes) structure.

    Sharding-aware by construction: jit keys on the inputs' committed
    shardings, so mesh-sharded gradients (parallel/sharding.py
    shard_model) get their own specialization in which GSPMD reduces
    each shard locally and psums the ``(2,)`` partials — the guard
    never gathers a full gradient."""
    return _health_fn()(list(raws))


def combine_health(parts):
    """Fold per-bucket ``(2,)`` health partials (e.g. one per allreduce
    bucket in `KVStore.bucketed_pushpull`) into one on device."""
    parts = list(parts)
    if len(parts) == 1:
        return parts[0]
    global _COMBINE_FN
    if _COMBINE_FN is None:
        import jax
        import jax.numpy as jnp

        def combine(cols):
            stacked = jnp.stack(cols)
            return jnp.stack([jnp.min(stacked[:, 0]),
                              jnp.sum(stacked[:, 1])])

        _COMBINE_FN = jax.jit(combine)
    return _COMBINE_FN(parts)


class StepGuard:
    """Per-step carrier for the device-resident health array.

    ``skip`` enables skip-step semantics (jnp.where in the fused
    programs + host-side skip of legacy-fallback items); ``clip`` bakes
    a global-norm clipping coefficient into the group programs.  The
    host readback happens at most ONCE, lazily, and is counted by
    `readback_count()`.
    """

    def __init__(self, health, skip=True, clip=None, extra=None):
        self.health = health          # (2,) f32 device array
        self.skip = bool(skip)
        self.clip = None if clip is None else float(clip)
        self.extra = extra            # (2,) u32 fingerprint (integrity)
        self._host = None
        self._extra_host = None

    def _materialize(self):
        if self._host is None:
            global _READBACK_COUNT
            _READBACK_COUNT += 1
            import numpy as _np

            from . import profiler

            # the step's ONE host sync: in a pipelined loop this span is
            # where the host waits out the device (benchmark/spans.py
            # reads it as the readback share of a step's idle time).  The
            # integrity fingerprint (when the step computed one) rides
            # the same transfer — attestation adds no extra sync.
            with profiler.annotate("guard_readback"):
                if self.extra is not None:
                    import jax

                    v, e = jax.device_get((self.health, self.extra))
                    self._extra_host = _np.asarray(e)
                else:
                    v = _np.asarray(self.health)
            self._host = (float(v[0]), float(v[1]))
        return self._host

    @property
    def fingerprint(self):
        """The step's integrity fingerprint as one u64 int, or None
        when the program computed none (integrity off / not an
        attestation step).  Shares the single guard readback."""
        if self.extra is None:
            return None
        self._materialize()
        e = self._extra_host
        return (int(e[1]) << 32) | int(e[0])

    def peek(self):
        """``(all_finite, global_sq_norm)`` if the host readback already
        happened, else None.  Telemetry reads the guard through this so
        attaching grad-norm to a StepStats record never forces a sync
        the step would not have done anyway."""
        return self._host

    @property
    def healthy(self) -> bool:
        """True iff every gradient is finite AND the global squared norm
        itself is finite (an astronomically exploding-but-finite f32
        group can overflow the f32 accumulator — treated as unhealthy,
        matching the in-trace predicate)."""
        fin, sq = self._materialize()
        return fin > 0.0 and math.isfinite(sq)

    @property
    def grad_norm(self) -> float:
        """Global L2 norm of the step's gradients (host value)."""
        _, sq = self._materialize()
        return math.sqrt(sq) if sq >= 0.0 else float("nan")


class StepSkipped:
    """Record of one skipped optimizer step (Trainer.skipped_steps)."""

    __slots__ = ("step", "reason", "grad_norm", "loss_scale")

    def __init__(self, step, reason, grad_norm=None, loss_scale=None):
        self.step = step
        self.reason = reason
        self.grad_norm = grad_norm
        self.loss_scale = loss_scale

    def __repr__(self):
        extra = ""
        if self.grad_norm is not None:
            extra += f", grad_norm={self.grad_norm:g}"
        if self.loss_scale is not None:
            extra += f", loss_scale={self.loss_scale:g}"
        return f"StepSkipped(step={self.step}, reason={self.reason!r}{extra})"


# -- fault-injection hooks (docs/resilience.md) --------------------------------

def maybe_inject_nan_grad(grads) -> bool:
    """`nan_grad` fault site: poison element 0 of the first float
    gradient with NaN (in its backing array, so the health reduction,
    the allreduce and the update kernels all see the same poisoned
    value).  Consumes one armed count per call; returns True if it fired."""
    from . import resilience

    if not grads or not resilience.consume_fault("nan_grad"):
        return False
    import jax.numpy as jnp

    for g in grads:
        raw = getattr(g, "_data", None)
        if raw is None or not jnp.issubdtype(raw.dtype, jnp.floating):
            continue
        poisoned = raw.ravel().at[0].set(jnp.nan).reshape(raw.shape)
        g._set_data(poisoned)
        _LOG.warning("fault injection: poisoned gradient with NaN "
                     "(MXTPU_FAULT_INJECT nan_grad)")
        return True
    return False


# -- divergence monitoring -----------------------------------------------------

class DivergenceError(MXNetError):
    """Training diverged and no checkpointer was attached for rollback.

    Carries the failing window so the caller can triage (same spirit as
    `gluon.data.DataLoaderWorkerError` surfacing the failing batch):
    ``bad_steps`` (length of the unhealthy streak), ``step`` (last
    observed step), ``batch_indices`` (quarantined sample/batch indices
    seen during the streak, if the caller supplied them).
    """

    def __init__(self, msg, step=None, bad_steps=None, batch_indices=None):
        super().__init__(msg)
        self.step = step
        self.bad_steps = bad_steps
        self.batch_indices = list(batch_indices or [])


class DivergenceMonitor:
    """EWMA-based divergence detector with checkpoint auto-rollback.

    Feed it one `observe()` per step — either attach it to a Trainer
    (``trainer.divergence_monitor = mon``; the Trainer then calls
    ``observe(healthy=..., grad_norm=...)`` from the guarded step) or
    drive it manually with the loss.  Do NOT do both, or each training
    step counts as two observations.

    A step is **bad** when it is unhealthy (non-finite grads/loss) or
    when grad-norm/loss explodes past ``explode_factor`` × its EWMA.
    After ``max_bad_steps`` consecutive bad steps (MXTPU_MAX_BAD_STEPS):

    - with a ``checkpointer`` + ``set_state``: roll back to the newest
      valid `resilience.LocalCheckpointer` snapshot, re-seed the loss
      scale (``reseed_scale`` or current/scale_factor), quarantine the
      batch indices observed during the streak, and return True;
    - without one: raise `DivergenceError` carrying the streak context.
    """

    def __init__(self, checkpointer=None, set_state=None, scaler=None,
                 max_bad_steps=None, ewma_alpha=0.05, explode_factor=8.0,
                 reseed_scale=None, logger=None):
        self.checkpointer = checkpointer
        self.set_state = set_state
        self.scaler = scaler
        self.max_bad_steps = int(max_bad_steps) if max_bad_steps \
            else globals()["max_bad_steps"]()
        self.ewma_alpha = float(ewma_alpha)
        self.explode_factor = float(explode_factor)
        self.reseed_scale = reseed_scale
        self.logger = logger or _LOG
        self.loss_ewma = None
        self.norm_ewma = None
        self.bad_streak = 0
        self.recoveries = 0
        self.quarantined = []
        self._streak_batches = []
        self._last_step = None
        # optional resumable input pipeline (an object with
        # load_state_dict/quarantine, e.g. gluon.data.DataLoader built
        # with seed=): rollback rewinds it to the restored checkpoint's
        # sample offset and quarantines the streak's batches so replay
        # skips them (one `batch_quarantined` event per skip)
        self.data_pipeline = None

    def _is_bad(self, loss, grad_norm, healthy):
        if not healthy:
            return True
        if loss is not None and not math.isfinite(loss):
            return True
        if grad_norm is not None and not math.isfinite(grad_norm):
            return True
        if grad_norm is not None and self.norm_ewma is not None \
                and self.norm_ewma > 0.0 \
                and grad_norm > self.explode_factor * self.norm_ewma:
            return True
        if loss is not None and self.loss_ewma is not None \
                and abs(loss) > self.explode_factor \
                * max(abs(self.loss_ewma), 1e-8):
            return True
        return False

    def observe(self, step=None, loss=None, grad_norm=None, healthy=True,
                batch_indices=None) -> bool:
        """Record one training step; returns True iff a rollback ran."""
        from . import resilience

        if resilience.consume_fault("inf_loss"):
            loss = float("inf")
        self._last_step = step if step is not None else \
            (self._last_step + 1 if self._last_step is not None else 0)
        if self._is_bad(loss, grad_norm, healthy):
            self.bad_streak += 1
            if batch_indices is not None:
                self._streak_batches.extend(
                    batch_indices if isinstance(batch_indices, (list, tuple))
                    else [batch_indices])
            if self.bad_streak >= self.max_bad_steps:
                return self._recover()
            return False
        self.bad_streak = 0
        self._streak_batches = []
        a = self.ewma_alpha
        if loss is not None:
            self.loss_ewma = loss if self.loss_ewma is None \
                else (1.0 - a) * self.loss_ewma + a * loss
        if grad_norm is not None:
            self.norm_ewma = grad_norm if self.norm_ewma is None \
                else (1.0 - a) * self.norm_ewma + a * grad_norm
        return False

    def _recover(self) -> bool:
        from . import resilience

        bad, step = self.bad_streak, self._last_step
        self.quarantined.extend(self._streak_batches)
        batches = list(self._streak_batches)
        self._streak_batches = []
        self.bad_streak = 0
        restored = 0
        if self.checkpointer is not None and self.set_state is not None:
            restored = resilience.resume_latest(
                self.checkpointer, self.set_state, logger=self.logger)
        if self.checkpointer is None or self.set_state is None \
                or (restored == 0
                    and not getattr(self.checkpointer, "all_steps",
                                    lambda: [])()):
            raise DivergenceError(
                f"training diverged: {bad} consecutive unhealthy/exploding "
                f"steps (last step {step}; loss ewma "
                f"{self.loss_ewma}, grad-norm ewma {self.norm_ewma}); "
                f"quarantined batch indices: {batches or 'none supplied'}. "
                "Attach a resilience.LocalCheckpointer for auto-rollback, "
                "or lower the learning rate / re-seed the loss scale.",
                step=step, bad_steps=bad, batch_indices=batches)
        if self.scaler is not None:
            if self.reseed_scale is not None:
                self.scaler.loss_scale = float(self.reseed_scale)
            else:
                self.scaler.loss_scale = max(
                    1.0, self.scaler.loss_scale / self.scaler.scale_factor)
            self.scaler._unskipped = 0
        if self.data_pipeline is not None:
            ds_fn = getattr(self.checkpointer, "data_state", None)
            ds = ds_fn(restored) if ds_fn is not None else None
            if ds is not None:
                # rewind the pipeline to the checkpoint's exact sample
                # offset FIRST (load replaces the quarantine set), then
                # quarantine the streak so replay skips the poison
                self.data_pipeline.load_state_dict(ds)
            bad_ids = [tuple(b) for b in batches
                       if isinstance(b, (list, tuple)) and len(b) == 2]
            if bad_ids:
                self.data_pipeline.quarantine(bad_ids)
        self.recoveries += 1
        self.logger.warning(
            "divergence auto-recovery #%d: rolled back to checkpoint step "
            "%d after %d bad steps; quarantined batches: %s",
            self.recoveries, restored, bad, batches or "none supplied")
        from . import telemetry
        telemetry.event("divergence_rollback", step=restored,
                        bad_steps=bad, last_step=step,
                        quarantined=len(batches))
        return True
