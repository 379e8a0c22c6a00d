"""Verification subsystem: static invariant lint + runtime sanitizers.

Two heads over the same concern — the engine invariants nothing else
enforces mechanically:

* :mod:`repro.analysis.lint` — the ``seclint`` AST rules (SEC001–SEC004)
  run by ``tools/seclint.py`` and the CI ``lint-static`` job.
* :mod:`repro.analysis.runtime` — the ``REPRO_DEBUG`` gate behind the
  structural ``validate()`` methods on ``HierIndex`` / ``SegmentPlan`` /
  ``DeviceIndex`` / ``ShardedDeviceIndex``, and ``span``, the timed
  profiler span of the serving path.
* :mod:`repro.analysis.sanitize` — the pytest sanitize mode: implicit
  transfer guard + jit compile counter.

``lint`` is import-light (stdlib ast only) so the CLI stays usable
without jax installed; the jax-importing pieces live in ``sanitize``.
"""

from repro.analysis.runtime import debug_enabled, force_debug, maybe_validate

__all__ = ["debug_enabled", "force_debug", "maybe_validate"]
