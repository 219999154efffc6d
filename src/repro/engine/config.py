"""EngineConfig — the frozen, explicit execution configuration of the engine.

One immutable object carries everything the engine needs to resolve a call:
the backend, the Pallas interpret flag, the accumulation policy and the
backend-selection policy. Because it is a frozen dataclass of strings and
bools it is hashable and equality-comparable, so it can be

  * threaded through `jax.jit` as a *static* argument (two equal configs hit
    the same jit cache entry),
  * used as a dict key (e.g. memoizing `engine.compile` results),
  * passed across threads safely — unlike the old module-level
    `_DEFAULT_BACKEND` / `_INTERPRET` list stacks this module replaces.

Ambient resolution keeps working via a *thread-local* stack of configs:
`using_config(cfg)` (and the thin `using_backend(name)` shim over it)
pushes for the dynamic extent of a block; `current_config()` reads the top.
The process-wide base config is set with `set_default_config`, which
raises `RuntimeError` when called inside an active context instead of being
silently shadowed until the context pops.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Iterator, List, Optional

from repro.engine.parallel import ParallelConfig

_POLICIES = ("fixed", "auto")
_TUNING_MODES = ("off", "cached", "autotune")
_PRECISIONS = ("fp32", "int8")
_FALLBACKS = ("none", "chain")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Frozen engine execution config (hashable; jit-static friendly).

    backend   — registry name ("xla" | "pallas" | "ref" | custom); with
                policy="auto" it is the fallback for layers the auto policy
                does not send to Pallas.
    interpret — run Pallas kernels in interpret mode. None (default)
                derives it from the platform: True exactly where JAX's
                default backend is the CPU (`kernels.ops.resolve_interpret`),
                so a TPU never interprets a kernel unless asked to.
    accum     — accumulation policy: None keeps each op's own default
                (fp32 for conv2d/dense, native for einsum); "native" forces
                plain `@` numerics; any dtype name ("float32", "bfloat16")
                forces that `preferred_element_type`.
    policy    — backend selection: "fixed" uses `backend` everywhere;
                "auto" picks pallas-vs-`backend` per op from its plan
                (see `plan.auto_backend`).
    row_align — None keeps native GEMM numerics. An int R makes FC-mode
                ops *batch-invariant*: the engine zero-pads the leading
                (batch) row dim of every dense contraction up to a multiple
                of R before dispatch and slices the result back, so each
                row always flows through the same fixed-granularity GEMM
                kernel regardless of how many requests share the batch —
                the serving analogue of the MMIE's fixed 192-PE row tiling.
                Batched execution under `row_align` is bitwise identical,
                row for row, to batch-1 execution (what the
                `serve.scheduler` parity contract relies on).
    tuning    — kernel tile selection for the Pallas backend (engine/tune.py):
                "off" keeps the kernels' built-in default tiling; "cached"
                uses per-op winners from the committed tile cache
                (`.tuning/<device_kind>.json`), silently falling back to the
                defaults on a miss; "autotune" benchmarks missing ops at
                `engine.compile` time and persists the winners to the cache.
                Tile keys are batch-invariant (dense keys drop the row dim,
                conv keys the batch dim), so batched and batch-1 execution
                always share one tile config — the accumulation-order
                guarantee the scheduler's bitwise parity contract needs.
    parallel  — None keeps single-device execution. A frozen
                `engine.parallel.ParallelConfig` makes `engine.compile`
                emit a `shard_map`ped `CompiledNet.apply` over a
                (data, model) mesh with a per-op replicate / shard-K /
                shard-N placement chosen from the analytic plan (the
                device-level twin of `policy="auto"`), and lets the
                serving schedulers spread replicas over the data axis.
                With the default `exact_only=True` policy, sharded
                outputs stay bitwise identical to single-device ones.
    precision — numeric execution precision. "fp32" (default) keeps the
                fp32 datapath. "int8" quantizes conv2d and canonical-GEMM
                dense ops symmetrically (per-row / per-example activation
                scales, per-channel weight scales — batch-invariant so
                scheduler parity holds), accumulates exactly in int32, and
                fuses dequant+bias+act into the kernel epilogue; the
                quantize→dequantize semantics are identical across the
                pallas/xla/ref backends (bitwise). Ops the int8 contract
                does not cover (non-canonical einsums, depthwise conv1d,
                gather) silently stay fp32; `accum` is ignored on int8
                ops. Per-op overrides: every engine op takes
                `precision=`, which wins over the config (and over a
                compiled plan's pinned precision) exactly like `backend=`.
    fallback  — kernel-failure policy at dispatch. "none" (default) keeps
                fail-stop semantics: a backend exception propagates.
                "chain" degrades gracefully: when an op's planned backend
                raises, dispatch retries the op down the degradation chain
                (pallas -> xla -> ref), records the hop into every active
                `Ledger` (`ledger.fallbacks`), and only raises once the
                whole chain failed. Safe for results by construction: the
                three built-in backends are pinned bitwise-identical on
                every covered op (the parity suites), so a fallback changes
                where an op ran, never what it returned. The serving
                schedulers default to "chain".
    """

    backend: str = "xla"
    interpret: Optional[bool] = None
    accum: Optional[str] = None
    policy: str = "fixed"
    row_align: Optional[int] = None
    tuning: str = "off"
    parallel: Optional[ParallelConfig] = None
    precision: str = "fp32"
    fallback: str = "none"

    def __post_init__(self) -> None:
        if self.parallel is not None and not isinstance(self.parallel,
                                                        ParallelConfig):
            raise ValueError(
                "parallel must be None or an engine.parallel.ParallelConfig; "
                f"got {self.parallel!r}")
        if self.policy not in _POLICIES:
            raise ValueError(
                f"unknown backend-selection policy {self.policy!r}; "
                f"expected one of {_POLICIES}")
        if self.tuning not in _TUNING_MODES:
            raise ValueError(
                f"unknown tuning mode {self.tuning!r}; "
                f"expected one of {_TUNING_MODES}")
        if self.precision not in _PRECISIONS:
            raise ValueError(
                f"unknown precision {self.precision!r}; "
                f"expected one of {_PRECISIONS}")
        if self.fallback not in _FALLBACKS:
            raise ValueError(
                f"unknown fallback policy {self.fallback!r}; "
                f"expected one of {_FALLBACKS}")
        if self.row_align is not None and (
                not isinstance(self.row_align, int) or self.row_align < 1):
            raise ValueError(
                f"row_align must be None or a positive int; "
                f"got {self.row_align!r}")
        if self.accum is not None and self.accum != "native":
            import numpy as np
            try:
                np.dtype(self.accum)
            except TypeError as e:
                raise ValueError(
                    f"accum must be None, 'native' or a dtype name; "
                    f"got {self.accum!r}") from e

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)


# Process-wide base config (bottom of every thread's resolution order).
_BASE: List[EngineConfig] = [EngineConfig()]  # analyze: allow[mutable-global] the sanctioned base slot under _TLS


class _Stack(threading.local):
    def __init__(self) -> None:
        self.configs: List[EngineConfig] = []


_TLS = _Stack()


def current_config() -> EngineConfig:
    """The ambient config: innermost active `using_config` block on this
    thread, else the process-wide default."""
    return _TLS.configs[-1] if _TLS.configs else _BASE[0]


def default_backend() -> str:
    return current_config().backend


def in_config_context() -> bool:
    return bool(_TLS.configs)


@contextlib.contextmanager
def using_config(cfg: Optional[EngineConfig]) -> Iterator[None]:
    """Ambient `EngineConfig` for every engine call in the block
    (None = no-op, so call sites can thread an optional config)."""
    if cfg is None:
        yield
        return
    from repro.engine import dispatch
    dispatch.get_backend(cfg.backend)       # validate eagerly
    _TLS.configs.append(cfg)
    try:
        yield
    finally:
        _TLS.configs.pop()


def using_backend(name: Optional[str]):
    """Compat shim over `using_config`: ambient backend for the block,
    keeping every other knob of the current config (None = no-op)."""
    if name is None:
        return contextlib.nullcontext()
    return using_config(current_config().replace(backend=name))


def _require_no_context(what: str) -> None:
    if _TLS.configs:
        raise RuntimeError(
            f"{what} inside an active using_backend()/using_config() "
            "context would be silently shadowed until the context exits; "
            "pass a config/backend to the context instead, or call this "
            "outside it")


def set_default_config(cfg: EngineConfig) -> None:
    """Replace the process-wide base config. Errors inside an active
    ambient context (the old list stack silently ignored the write)."""
    from repro.engine import dispatch
    dispatch.get_backend(cfg.backend)
    _require_no_context("set_default_config()")
    _BASE[0] = cfg
