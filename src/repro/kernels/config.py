"""Central kernel tuning knobs (`KernelEnv`), alpa `global_env.py` idiom.

As the kernel surface grows (nary_accum, ties, dare, slerp, histogram
trim, int8 merge-on-arrival) the per-call keyword defaults stop scaling:
every wrapper probed the backend on every call and each knob lived in a
different signature. `KernelEnv` owns them in one place, seeded from the
environment at import and mutable at runtime (tests, benchmarks), with
the module singleton `kernel_env` as the process-wide source of truth.

Environment overrides (read once, at first access):

==========================  ================================================
variable                    effect
==========================  ================================================
REPRO_KERNEL_INTERPRET      "1"/"true" forces Pallas interpret mode, "0"/
                            "false" forces compiled mode; unset -> probe
                            the backend once (interpret iff not on TPU).
REPRO_KERNEL_BLOCK          per-grid-step tile width (default 2048).
REPRO_KERNEL_HIST_BINS      histogram trim-quantile resolution (default
                            512, matching `strategies.catalog`).
==========================  ================================================
"""
from __future__ import annotations

import os
from typing import Optional

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def _env_flag(name: str) -> Optional[bool]:
    raw = os.environ.get(name)
    if raw is None:
        return None
    v = raw.strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    raise ValueError(f"{name}={raw!r}: expected one of {_TRUE + _FALSE}")


class KernelEnv:
    """Process-wide kernel configuration (mutable; env-seeded).

    Attributes are plain mutable fields so tests and benchmarks can
    flip them (`kernel_env.interpret = True`); `reset()` restores the
    environment-seeded defaults. `interpret` stays ``None`` until the
    first `resolve_interpret()` so importing this module never triggers
    a backend probe.
    """

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.interpret: Optional[bool] = _env_flag("REPRO_KERNEL_INTERPRET")
        self.block: int = int(os.environ.get("REPRO_KERNEL_BLOCK", "2048"))
        self.hist_bins: int = int(
            os.environ.get("REPRO_KERNEL_HIST_BINS", "512"))
        if self.block <= 0:
            raise ValueError(f"REPRO_KERNEL_BLOCK must be > 0, "
                             f"got {self.block}")
        if self.hist_bins <= 1:
            raise ValueError(f"REPRO_KERNEL_HIST_BINS must be > 1, "
                             f"got {self.hist_bins}")

    def resolve_interpret(self) -> bool:
        """The effective interpret flag, probing the backend at most once.

        Unlike the old per-call `default_interpret()` in every wrapper,
        the probe result is cached on the env, so the hot path pays a
        single attribute read.
        """
        if self.interpret is None:
            import jax  # deferred: keep module import free of jax init
            self.interpret = jax.default_backend() != "tpu"
        return self.interpret


kernel_env = KernelEnv()
