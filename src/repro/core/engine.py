"""DEPRECATED shim — the multi-mode engine now lives in `repro.engine`.

`MultiModeEngine` (stateful dispatcher + mutable ledger + process-global
`default_engine()` singleton) has been replaced by the functional,
plan-based API in `repro.engine`:

    old                                   new
    ---------------------------------     ----------------------------------
    eng = MultiModeEngine(cfg)            (no object needed)
    eng.conv2d(x, w, ...)                 engine.conv2d(x, w, ..., backend=b)
    eng.matmul(x, w)                      engine.dense(x, w) / engine.matmul
    eng.conv1d_depthwise(x, w)            engine.conv1d_depthwise(x, w)
    eng.ledger / eng.report()             with engine.tracking() as ledger: ...
    default_engine()                      (ambient backend: engine.using_backend)

This module keeps the old names importable for one release; the class below
is a thin veneer over `repro.engine` with identical ledger semantics (same
record fields, same report format, same analytic totals). New code should
not use it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro import engine as _engine
from repro.engine.ledger import Ledger, OpRecord  # noqa: F401 (legacy name)

Backend = Literal["pallas", "xla", "ref"]


@dataclasses.dataclass
class EngineConfig:
    backend: Backend = "xla"
    interpret: Optional[bool] = None  # None: interpret exactly on a CPU
    accum_dtype: jnp.dtype = jnp.float32
    track_analytics: bool = True


class MultiModeEngine:
    """Deprecated object facade over `repro.engine` (see module docstring).

    The ledger is a `repro.engine.Ledger`; iteration and record fields are
    unchanged from the legacy `OpRecord`, so existing consumers keep
    working while they migrate to `engine.tracking()`.
    """

    def __init__(self, config: Optional[EngineConfig] = None):
        warnings.warn(
            "MultiModeEngine is deprecated; use the functional repro.engine "
            "API (engine.dense / engine.conv2d / engine.tracking)",
            DeprecationWarning, stacklevel=2)
        self.config = config or EngineConfig()
        self.ledger = Ledger()

    def _track(self):
        if self.config.track_analytics:
            return _engine.tracking(self.ledger)
        return contextlib.nullcontext()

    # -- modes ------------------------------------------------------------

    def conv2d(self, x: jax.Array, w: jax.Array, *, stride: int = 1,
               pad: int = 0, groups: int = 1) -> jax.Array:
        with self._track():
            return _engine.conv2d(
                x, w, stride=stride, pad=pad, groups=groups,
                backend=self.config.backend,
                accum_dtype=self.config.accum_dtype,
                interpret=self.config.interpret)

    def conv1d_depthwise(self, x: jax.Array, w: jax.Array, *,
                         causal: bool = True) -> jax.Array:
        with self._track():
            return _engine.conv1d_depthwise(
                x, w, causal=causal, backend=self.config.backend,
                interpret=self.config.interpret)

    def matmul(self, x: jax.Array, w: jax.Array) -> jax.Array:
        with self._track():
            return _engine.dense(
                x, w, backend=self.config.backend,
                accum_dtype=self.config.accum_dtype, out_dtype=x.dtype,
                interpret=self.config.interpret)

    # -- analytics --------------------------------------------------------

    def reset_ledger(self) -> None:
        self.ledger.clear()

    @property
    def total_cycles(self) -> int:
        return self.ledger.total_cycles

    @property
    def total_macs(self) -> int:
        return self.ledger.total_macs

    @property
    def performance_efficiency(self) -> float:
        return self.ledger.performance_efficiency

    def report(self) -> str:
        return self.ledger.report()


_DEFAULT: Optional[MultiModeEngine] = None  # analyze: allow[mutable-global] deprecated singleton shim


def default_engine() -> MultiModeEngine:
    """Deprecated process-wide engine (analytics off). Prefer the ambient
    `engine.using_backend(...)` / plain `engine.dense` calls."""
    global _DEFAULT
    warnings.warn(
        "default_engine() is deprecated; use the functional repro.engine "
        "API (ambient config via engine.using_backend/using_config)",
        DeprecationWarning, stacklevel=2)
    if _DEFAULT is None:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            _DEFAULT = MultiModeEngine(EngineConfig(track_analytics=False))
    return _DEFAULT
